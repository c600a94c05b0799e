"""Whole-step model FLOP/s utilization, in percent: the operations the
model needs for every token the engine's steps processed in the traced
window (from shapes, ``harness/work.py``), over the window times the
chips times the chip's peak.  Idle time counts against it."""


def read(run, trace):
    if trace is None:
        return None
    flops = sum(h[3]["model_flops"] for name in ("decode", "prefill_chunk")
                for h in trace.host_spans(name))
    if not flops:
        return None
    return 100.0 * flops / (trace.seconds * run.chips
                            * run.peaks["bf16_flops"])
