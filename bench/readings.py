"""Shared arithmetic of the metric readers in ``metrics/``.

The readers take the harness's ``Run``: the requests served in the window
(``served``, host clock), the session tracer's records (``spans``), the
reduced device trace (``device``) and the counts of every answer.
"""
from __future__ import annotations

import math


def latencies_ms(run) -> list:
    return sorted(s.latency_s * 1e3 for s in run.served)


def percentile(sorted_values: list, q: float) -> float | None:
    """Nearest-rank percentile (``q`` in 0..100) of sorted values."""
    if not sorted_values:
        return None
    k = max(math.ceil(q / 100.0 * len(sorted_values)) - 1, 0)
    return sorted_values[k]


def spans(run, name: str) -> list:
    return [r for r in run.spans or () if r.get("type") == "span"
            and r["name"] == name]


def per_request_ms(run, total_us: float) -> float | None:
    """``total_us`` spread over the window's requests, in ms."""
    n = len(spans(run, "request"))
    return total_us / n / 1e3 if n else None


def least_bytes(run) -> float:
    """The least bytes any implementation moves on the device for the
    window's answers: each returned row's stored bytes read once and all
    its bytes written once, plus each returned vertex plane written once
    (``answer_counts`` holds ``(rows, bytes per row, plane bytes)``).  A
    stored column that feeds the answer without being returned, such as an
    SSSP weight, is not counted, so this is a lower bound."""
    return float(sum(rows * 2 * per_row + plane
                     for rows, per_row, plane in run.answer_counts or ()))
