"""95th percentile of every gap between consecutive output tokens of every
request, where both tokens were stamped inside the window."""
from chipbench.harness.stats import percentile


def read(run, trace):
    gaps = [b - a for r in run.reqs for a, b in zip(r.stamps, r.stamps[1:])
            if run.in_window(a) and run.in_window(b)]
    return percentile(gaps, 95) * 1e3 if gaps else None
