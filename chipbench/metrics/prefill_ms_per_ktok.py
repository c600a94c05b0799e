"""Device time of the prefill-chunk programs in the traced window per
1000 prompt tokens they wrote (token counts from the harness's
annotations of each chunk call); averaged over the chips used."""


def read(run, trace):
    if trace is None:
        return None
    tokens = sum(h[3]["tokens"] for h in trace.host_spans("prefill_chunk"))
    per_dev = [sum(b - a for a, b, _ in trace.programs(d, "prefill"))
               for d in sorted(trace.devices)[:run.chips]]
    if not tokens or not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) * 1e-6 / (tokens / 1000.0)
