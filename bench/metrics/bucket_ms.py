"""Mean over requests of the summed bucket dispatch time: the
``elapsed_us`` attribute of the ``dispatch`` spans (the executor's own
per-bucket measurement, not the spans' durations)."""
from bench.readings import per_request_ms, spans


def read(run):
    d = spans(run, "dispatch")
    return per_request_ms(run, sum(s["attrs"]["elapsed_us"] for s in d)) \
        if d else None
