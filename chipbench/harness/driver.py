"""Open-loop driver on the wall clock around ``ServeEngine.submit``/``tick``.

Each request is submitted when it is due on ``time.perf_counter``.  While
the engine has work the driver ticks it, else it sleeps to the next due
time.  After every tick it stamps each token that appeared in a request's
``generated`` list with the host clock: ``tick()`` ends by copying the
logits to the host, so a token stamped there is one a client could see.
Time to first token runs from the due time, not from the submit, so a
late generator or a stalled tick counts against the server.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque

from chipbench.harness.traffic import Req, prompt_tokens


def annotation(name: str, **args):
    import jax
    return jax.profiler.TraceAnnotation(f"chipbench.{name}", **args)


class Driver:
    """Feeds one engine from a generated request list."""

    def __init__(self, engine, reqs: list[Req], seed: int, vocab: int,
                 annotate: bool = False):
        from repro.inference.engine import Request
        self.eng = engine
        self.seed = seed
        self.vocab = vocab
        self._Request = Request
        self.todo = deque(reqs)
        self.sent: list[Req] = []
        self.live: dict = {}            # rid -> (Req, engine Request)
        self.annotate = annotate
        self.lateness: list[float] = []
        self.start = 0.0

    def _span(self, name: str, **args):
        return annotation(name, **args) if self.annotate \
            else contextlib.nullcontext()

    def _send(self, r: Req, due_abs: float, now: float) -> None:
        r.due_abs = due_abs
        self.lateness.append(now - due_abs)
        req = self._Request(r.rid, prompt=prompt_tokens(
            self.seed, r.rid, r.prompt_len, self.vocab),
            max_new_tokens=r.max_new)
        self.eng.submit(req)
        self.sent.append(r)
        self.live[r.rid] = (r, req)

    def _submit_due(self, now: float) -> None:
        while self.todo and self.start + self.todo[0].due_s <= now:
            r = self.todo.popleft()
            self._send(r, self.start + r.due_s, now)

    def _stamp(self, t: float) -> None:
        for rid in list(self.live):
            r, req = self.live[rid]
            while len(r.stamps) < len(req.generated):
                r.stamps.append(t)
            if req.done:
                r.status, r.generated = req.status, list(req.generated)
                del self.live[rid]

    def run_until(self, t_end: float, accept: bool = True,
                  until=None) -> None:
        """Submit, tick and stamp until ``t_end`` on the host clock, or
        until ``until()`` is true.  ``accept=False`` stops new submits."""
        while True:
            now = time.perf_counter()
            if now >= t_end or (until is not None and until()):
                return
            if accept:
                with self._span("generator"):
                    self._submit_due(now)
            if self.eng.busy:
                with self._span("tick"):
                    self.eng.tick()
                t = time.perf_counter()
                with self._span("stamp"):
                    self._stamp(t)
                continue
            nxt = self.start + self.todo[0].due_s \
                if accept and self.todo else None
            wake = t_end if nxt is None else min(nxt, t_end)
            if not accept and nxt is None and until is not None:
                return          # nothing left that could satisfy until()
            with self._span("sleep"):
                time.sleep(max(0.0, wake - time.perf_counter()))
