"""Device idle time between consecutive model-step programs (prefill
chunks and decode steps) while the engine had work, per step program:
the measured form, on this chip, of the time the device waits for the
host to launch the next kernel.  Gaps in which the harness slept for
want of requests are left out.  Averaged over the chips used."""
from chipbench.harness.trace import merged


def read(run, trace):
    if trace is None:
        return None
    sleeps = [(h[0], h[1]) for h in trace.host_spans("sleep")]
    per_dev = []
    for d in sorted(trace.devices)[:run.chips]:
        progs = sorted(trace.programs(d, "decode")
                       + trace.programs(d, "prefill"))
        if len(progs) < 2:
            continue
        busy = merged([(a, b) for a, b, *_ in trace.devices[d]["ops"]])
        idle, j = 0.0, 0
        for (_, end, _), (start, _, _) in zip(progs, progs[1:]):
            if start <= end or any(s < start and e > end for s, e in sleeps):
                continue
            covered = 0.0
            while j < len(busy) and busy[j][1] <= end:
                j += 1
            k = j
            while k < len(busy) and busy[k][0] < start:
                covered += min(busy[k][1], start) - max(busy[k][0], end)
                k += 1
            idle += (start - end) - max(0.0, covered)
        per_dev.append(idle / len(progs))
    return sum(per_dev) / len(per_dev) * 1e-6 if per_dev else None
