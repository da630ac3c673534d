"""Mean host time per request in the executor's ``device_wait`` spans: the
host blocked on the device before its first read of each bucket's
result."""
from bench.readings import per_request_ms, spans


def read(run):
    t = spans(run, "device_wait")
    return per_request_ms(run, sum(s["dur_us"] for s in t)) if t else None
