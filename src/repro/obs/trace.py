"""Structured tracing: spans and events, on the profiler's clock.

A :class:`Tracer` records two record kinds into one in-memory list:

* **spans** — named intervals (``parse``/``stats``/``cost``/``compile``/
  ``dispatch``/``transfer``/...) with microsecond start/duration relative
  to the tracer's epoch, a unique ``id`` and the enclosing span's
  ``parent`` id (spans are recorded on EXIT, so children precede their
  parent in the record stream but nest inside it in time);
* **events** — named instants (per-traversal-level progress, deadline
  skips, stragglers) attributed to the enclosing span.

Each span an enabled tracer opens also enters a
``jax.profiler.TraceAnnotation`` named ``span:<name>``.  When a
``jax.profiler`` trace is active, the spans therefore land on the host
thread that opened them, on the same clock as the device's operations:
one timeline holds the program's host work and the chip's, and loads in
Perfetto.  With no trace active an annotation costs a check in native
code; a disabled or uninstalled tracer enters none.

Per-level traversal events are derived HOST-SIDE from an executed
:class:`~repro.core.operators.BFSResult` (:func:`emit_level_events`): the
fixed-point driver is one jitted ``lax.while_loop``, so per-iteration
host callbacks are off the table — instead ``row_depths`` (BFS level per
result row) is histogrammed into per-level edge counts and ``level_dirs``
decodes each level's taken push/pull direction.  This keeps the traced
numbers exactly the executed result's numbers, and keeps the hot loop
untouched.

The module-global ``current_tracer()`` seam is how the engine and serving
layers find the active tracer: installing one (``set_tracer``) turns
tracing on everywhere downstream; the disabled path is a module attribute
read plus a ``None`` check (measured at parity with no tracing at all —
the perf gate's ``disabled_tracer_ratio`` cell holds it there).

Schema (JSON-lines, one record per line; see docs/observability.md):

.. code-block:: text

    {"type": "header", "schema_version": 1, "clock": "...", "meta": {...}}
    {"type": "span",  "id": 3, "parent": 1, "name": "dispatch",
     "ts_us": 12.5, "dur_us": 480.2, "attrs": {...}}
    {"type": "event", "name": "level", "parent": 3, "ts_us": 200.1,
     "attrs": {"level": 2, "dir": "pull", "edges": 4096, ...}}
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Iterator, Optional

from jax.profiler import TraceAnnotation

__all__ = ["TRACE_SCHEMA_VERSION", "Tracer", "current_tracer", "set_tracer",
           "trace_span", "trace_event", "emit_level_events", "read_jsonl"]

TRACE_SCHEMA_VERSION = 1

_CLOCK = "perf_counter, microseconds since tracer epoch"


class Tracer:
    """Span/event recorder.  ``enabled=False`` makes every call a cheap
    no-op (kept for symmetry with a config flag; an uninstalled tracer is
    cheaper still).  ``level_events=False`` suppresses the per-level
    traversal events (which require a device->host read of ``row_depths``)
    while keeping the spans."""

    def __init__(self, *, enabled: bool = True, level_events: bool = True,
                 meta: Optional[dict] = None):
        self.enabled = enabled
        self.level_events = level_events
        self.meta = dict(meta or {})
        self.records: list[dict] = []
        self._stack: list[int] = []
        self._next_id = 0
        self._epoch = time.perf_counter()

    # -- recording ---------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Record a named interval.  Yields the (mutable) attrs dict so the
        body can attach results discovered mid-span."""
        if not self.enabled:
            yield attrs
            return
        sid = self._next_id
        self._next_id += 1
        parent = self._stack[-1] if self._stack else None
        self._stack.append(sid)
        with TraceAnnotation("span:" + name):
            t0 = self._now_us()
            try:
                yield attrs
            finally:
                self._stack.pop()
                self.records.append({
                    "type": "span", "id": sid, "parent": parent,
                    "name": name, "ts_us": t0,
                    "dur_us": self._now_us() - t0, "attrs": attrs})

    def event(self, name: str, **attrs) -> None:
        """Record a named instant inside the current span (if any)."""
        if not self.enabled:
            return
        self.records.append({
            "type": "event", "name": name,
            "parent": self._stack[-1] if self._stack else None,
            "ts_us": self._now_us(), "attrs": attrs})

    # -- exporters ---------------------------------------------------------
    def _header(self) -> dict:
        return {"type": "header", "schema_version": TRACE_SCHEMA_VERSION,
                "clock": _CLOCK, "meta": self.meta}

    def iter_records(self) -> Iterator[dict]:
        yield self._header()
        yield from self.records

    def write_jsonl(self, path: str) -> str:
        """One JSON record per line, header first."""
        with open(path, "w") as f:
            for rec in self.iter_records():
                f.write(json.dumps(rec, sort_keys=True) + "\n")
        return path


def read_jsonl(path: str) -> list[dict]:
    """Read a JSONL trace back (header first) — the roundtrip inverse of
    :meth:`Tracer.write_jsonl`.  Raises ``ValueError`` on a missing or
    version-incompatible header."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    if not records or records[0].get("type") != "header":
        raise ValueError(f"{path}: not a trace (no header record)")
    v = records[0].get("schema_version")
    if v != TRACE_SCHEMA_VERSION:
        raise ValueError(f"{path}: unsupported trace schema_version {v!r} "
                         f"(this reader handles {TRACE_SCHEMA_VERSION})")
    return records


# ---------------------------------------------------------------------------
# the module-global seam (what the engine / serving layers consult)
# ---------------------------------------------------------------------------

_CURRENT: Optional[Tracer] = None
_NOOP = contextlib.nullcontext({})     # reentrant: one shared instance


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install ``tracer`` as the process-global tracer; returns the
    previous one (restore it when done)."""
    global _CURRENT
    prev = _CURRENT
    _CURRENT = tracer
    return prev


def current_tracer() -> Optional[Tracer]:
    t = _CURRENT
    return t if (t is not None and t.enabled) else None


def trace_span(name: str, **attrs):
    """Span on the current tracer, or a shared no-op context manager —
    this is the only cost a hot path pays when tracing is off."""
    t = _CURRENT
    if t is None or not t.enabled:
        return _NOOP
    return t.span(name, **attrs)


def trace_event(name: str, **attrs) -> None:
    t = _CURRENT
    if t is not None and t.enabled:
        t.event(name, **attrs)


# ---------------------------------------------------------------------------
# per-level traversal events, derived from an executed BFSResult
# ---------------------------------------------------------------------------

def _dir_name(code: int) -> Optional[str]:
    return {0: "push", 1: "pull"}.get(int(code))


def emit_level_events(tracer: Tracer, result, *, bytes_per_row: float = 0.0,
                      **attrs) -> dict:
    """Emit one ``level`` event per executed BFS level of ``result`` (a
    single-root or batched ``BFSResult``), derived host-side:

    * ``edges`` — result rows whose ``row_depths`` equal the level (the
      edges emitted while that level's frontier expanded), summed over
      lanes for a batched result;
    * ``frontier`` — the rows that ENTERED the level (the previous level's
      emitted edges; 1 root row at level 0);
    * ``dir`` — the taken push/pull direction decoded from ``level_dirs``
      (``None`` for push-only engines; ``"mixed"`` when a batched
      dispatch's lanes disagree);
    * ``bytes_est`` — ``edges * bytes_per_row`` when a per-row byte width
      is supplied (e.g. the plan's ``total_bytes / result_rows``).

    Returns the dispatch's direction agreement for its ``dispatch`` span,
    from the same decode: ``levels_uniform`` (levels whose deciding lanes,
    the non-``-1`` entries of ``level_dirs``, all took one direction) and
    ``levels_mixed`` (levels where they disagree, so a batch ran both
    sides); an empty dict for engines without a switch, or with level
    events off.

    Forcing ``row_depths`` to host synchronizes the dispatch — level
    events are an enabled-tracing cost only."""
    if tracer is None or not tracer.enabled or not tracer.level_events:
        return {}
    if getattr(result, "row_depths", None) is None:
        return {}
    import numpy as np

    rd = np.asarray(result.row_depths)
    count = np.asarray(result.count).reshape(-1)
    depth = int(np.max(np.asarray(result.depth)))
    if rd.ndim == 1:
        rd = rd[None, :]
    # per-lane valid-row masks -> pooled per-level edge counts
    lanes = np.arange(rd.shape[1])[None, :] < count[:, None]
    valid = rd[lanes]
    valid = valid[valid >= 0]
    edges = np.bincount(valid.astype(np.int64), minlength=depth or 1)

    dirs = getattr(result, "level_dirs", None)
    taken = None
    if dirs is not None:
        dv = np.asarray(dirs)
        if dv.size:
            taken = dv if dv.ndim == 2 else dv[None, :]
    n_lanes = int(count.shape[0])
    agreement = ({"levels_uniform": 0, "levels_mixed": 0}
                 if taken is not None else {})
    for lvl in range(depth):
        d = None
        if taken is not None and lvl < taken.shape[1]:
            codes = {int(c) for c in taken[:, lvl] if int(c) >= 0}
            if len(codes) == 1:
                d = _dir_name(codes.pop())
                agreement["levels_uniform"] += 1
            elif codes:
                d = "mixed"
                agreement["levels_mixed"] += 1
        n = int(edges[lvl]) if lvl < edges.shape[0] else 0
        frontier = n_lanes if lvl == 0 else (
            int(edges[lvl - 1]) if lvl - 1 < edges.shape[0] else 0)
        ev = {"level": lvl, "dir": d, "edges": n, "frontier": frontier}
        if bytes_per_row:
            ev["bytes_est"] = n * float(bytes_per_row)
        tracer.event("level", **ev, **attrs)
    return agreement
