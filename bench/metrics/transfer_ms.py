"""Mean host time per request in the ``transfer`` spans: each bucket's copy
of its result to the host."""
from bench.readings import per_request_ms, spans


def read(run):
    t = spans(run, "transfer")
    return per_request_ms(run, sum(s["dur_us"] for s in t)) if t else None
