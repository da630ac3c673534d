"""Mean host time per request in the executor's ``launch`` spans: the
dispatch calls that put each bucket's program on the device."""
from bench.readings import per_request_ms, spans


def read(run):
    t = spans(run, "launch")
    return per_request_ms(run, sum(s["dur_us"] for s in t)) if t else None
