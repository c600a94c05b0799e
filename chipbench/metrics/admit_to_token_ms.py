"""95th percentile, over the ``repro.first_token`` spans in the traced
window, of the wall time from a request's first admission to the end of
the tick that hands its first token to the caller."""
from chipbench.harness.stats import percentile


def read(run, trace):
    if trace is None or not getattr(trace, "program", None):
        return None
    vals = [p[3]["admit_to_token_ms"]
            for p in trace.program_spans("first_token")]
    return percentile(vals, 95) if vals else None
