"""Mean, over the ``repro.first_token`` spans in the traced window, of
how long a sampled first token waited inside its tick (the rest of the
tick's work, a decode step among it) before the engine returned it."""


def read(run, trace):
    if trace is None or not getattr(trace, "program", None):
        return None
    vals = [p[3]["holdback_ms"] for p in trace.program_spans("first_token")]
    return sum(vals) / len(vals) if vals else None
