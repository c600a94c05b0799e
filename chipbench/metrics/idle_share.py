"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the chips the cell uses."""


def read(run, trace):
    if trace is None or not trace.devices:
        return None
    used = sorted(trace.devices)[:run.chips]
    busy = sum(trace.busy_ns(d) for d in used) / len(used) * 1e-9
    return 100.0 * (1.0 - busy / trace.seconds)
