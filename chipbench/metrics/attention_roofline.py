"""Share of its roofline reached by the work under the model's ``attn``
scope, in percent: the least time the chip's peaks allow for the work the
algorithm needs (``harness/work.py``: projections, rotary embedding, the
new K/V entries, live K and V read once, scores and weighted sum), over
the device time of the ops the engine's step programs ran under ``attn``
in the traced window.  Averaged over the chips used; their peaks add."""


def read(run, trace):
    if trace is None:
        return None
    flops = bytes_ = 0.0
    for name in ("decode", "prefill_chunk"):
        for h in trace.host_spans(name):
            flops += h[3]["attn_flops"]
            bytes_ += h[3]["attn_bytes"]
    used = sorted(trace.devices)[:run.chips]
    spent = 0.0
    for d in used:
        progs = trace.programs(d, "decode") + trace.programs(d, "prefill")
        spent += sum(op[1] - op[0] for op in trace.ops_within(d, progs)
                     if "/attn/" in f"/{op[3]}/")
    if not flops or not spent:
        return None
    spent = spent / len(used) * 1e-9
    least = max(flops / (run.chips * run.peaks["bf16_flops"]),
                bytes_ / (run.chips * run.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / spent
