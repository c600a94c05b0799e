"""Host time to enqueue one step program: the mean duration of the
``repro.launch.*`` spans that ``tklqt_ms`` pairs with the step programs
that started in the traced window; averaged over the chips used."""


def read(run, trace):
    if trace is None or not getattr(trace, "program", None):
        return None
    per_dev = []
    for d in sorted(trace.devices)[:run.chips]:
        pairs = trace.launched_programs(d)
        if pairs:
            per_dev.append(sum(s[1] - s[0] for s, _ in pairs) / len(pairs))
    return sum(per_dev) / len(per_dev) * 1e-6 if per_dev else None
