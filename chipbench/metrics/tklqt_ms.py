"""The paper's TKLQT measured on the chip: the mean time from the start
of the ``repro.launch.*`` span that enqueued a step program (decode or
prefill chunk) to the program's start on the device, over the step
programs that started in the traced window; averaged over the chips
used.  A program whose launch began before the window is left out.
Reads the program's own spans, so it is silent on a trace without them."""


def read(run, trace):
    if trace is None or not getattr(trace, "program", None):
        return None
    per_dev = []
    for d in sorted(trace.devices)[:run.chips]:
        pairs = trace.launched_programs(d)
        if pairs:
            per_dev.append(sum(p[0] - s[0] for s, p in pairs) / len(pairs))
    return sum(per_dev) / len(per_dev) * 1e-6 if per_dev else None
