"""95th percentile of time to first token over every request due in the
window, from its due time to its first token on the host.  A request
that got none before the drain ended counts with the time it waited."""
from chipbench.harness.stats import percentile


def read(run, trace):
    due = run.due_in_window()
    if not due:
        return None
    waits = [(r.stamps[0] if r.stamps else run.drain_end) - r.due_abs
             for r in due]
    return percentile(waits, 95) * 1e3
