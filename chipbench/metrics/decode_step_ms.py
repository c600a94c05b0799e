"""Device time of one decode step: the time the engine's decode program
ran on the device in the traced window, over its executions; averaged
over the chips the cell uses."""


def read(run, trace):
    if trace is None:
        return None
    per_dev = []
    for d in sorted(trace.devices)[:run.chips]:
        progs = trace.programs(d, "decode")
        if progs:
            per_dev.append(sum(b - a for a, b, _ in progs) / len(progs))
    return sum(per_dev) / len(per_dev) * 1e-6 if per_dev else None
