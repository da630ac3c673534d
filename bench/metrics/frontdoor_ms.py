"""Mean host time per request in parse, admission and planning, from the
session's spans: the part of each ``request`` span before its
``latency_us`` window (parse plus admission) and the ``plan`` spans
inside it."""
from bench.readings import per_request_ms, spans


def read(run):
    reqs = spans(run, "request")
    if not reqs:
        return None
    ids = {r["id"] for r in reqs}
    before = sum(r["dur_us"] - r["attrs"]["latency_us"] for r in reqs)
    plan = sum(p["dur_us"] for p in spans(run, "plan") if p["parent"] in ids)
    return per_request_ms(run, before + plan)
