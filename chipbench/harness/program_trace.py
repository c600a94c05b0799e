"""A profiler trace with the program's own spans beside the harness's.

``TraceView`` keeps the harness's ``chipbench.*`` annotations in ``host``.
``ProgramTraceView`` reads the same ``.xplane.pb`` and adds, in a list of
its own (``program``), the ``repro.*`` spans that the engine and its
backends record on the profiler's clock: ``repro.tick`` per scheduling
round, ``repro.launch.<step>`` around the call that enqueues a step
program, ``repro.first_token`` where a first token reaches the caller,
and the rest that ``docs/metrics.md`` lists.  Everything that reads
``host`` reads what it read before.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from chipbench.harness.trace import STEP_PROGRAMS, TraceView

PROGRAM_PREFIX = "repro."
# launch span -> the kind of step program it enqueues (STEP_PROGRAMS)
LAUNCHES = {"launch.decode": "decode", "launch.prefill_chunk": "prefill"}
# how far the device's clock may sit from the host's in the trace: on a
# TPU v5e a chunk's program was seen to start 0.83 ms before the launch
# span that enqueued it.  Programs of one kind start far further apart.
CLOCK_SLACK_NS = 2_000_000


@dataclass
class ProgramTraceView(TraceView):
    program: list = field(default_factory=list)   # [t0, t1, name, args]

    @classmethod
    def from_xplane(cls, path) -> "ProgramTraceView":
        from jax.profiler import ProfileData
        base = TraceView.from_xplane(path)
        program = []
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        program.append([ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        ev.name[len(PROGRAM_PREFIX):],
                                        dict(ev.stats)])
        program.sort(key=lambda p: p[0])
        return cls(window=base.window, devices=base.devices, host=base.host,
                   program=program)

    def to_json(self) -> dict:
        return dict(super().to_json(), program=self.program)

    @classmethod
    def from_json(cls, d: dict) -> "ProgramTraceView":
        base = TraceView.from_json(d)
        return cls(window=base.window, devices=base.devices, host=base.host,
                   program=d.get("program", []))

    # ------------------------------------------------------------ queries
    def program_spans(self, name: str) -> list:
        """The program's ``repro.<name>`` spans inside the window."""
        t0, t1 = self.window
        return [p for p in self.program
                if p[2] == name and p[0] >= t0 and p[1] <= t1]

    def launched_programs(self, dev: int) -> list:
        """(launch span, program) for each step program of device ``dev``
        that started in the window.  A kind's launch spans and programs
        pair in issue order: each launch takes the first program of its
        kind that starts after the launch began, less ``CLOCK_SLACK_NS``.
        A program whose launch began before the window is left out."""
        t0, t1 = self.window
        out = []
        for span, kind in LAUNCHES.items():
            progs = [p for p in self.devices[dev]["programs"]
                     if STEP_PROGRAMS[kind].search(p[2])]
            j = 0
            for launch in (p for p in self.program if p[2] == span):
                while j < len(progs) and \
                        progs[j][0] < launch[0] - CLOCK_SLACK_NS:
                    j += 1
                if j == len(progs):
                    break
                prog = progs[j]
                j += 1
                if launch[0] >= t0 and t0 <= prog[0] < t1:
                    out.append((launch, prog))
        return sorted(out, key=lambda lp: lp[1][0])

    def innermost(self, t: float, below_tick: bool = True):
        """The innermost program span open at host time ``t`` (the one
        that began last), or None; ``below_tick`` passes over
        ``repro.tick``."""
        best = None
        for p in self.program:
            if p[0] > t:
                break
            if p[1] > t and not (below_tick and p[2] == "tick") and \
                    (best is None or p[0] >= best[0]):
                best = p
        return best
