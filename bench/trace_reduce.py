"""From a profiler trace to busy time, idle share, top ops and idle gaps.

``jax.profiler`` writes one ``*.xplane.pb`` per traced run.  Its planes named
``/device:<kind>:<n>`` hold the device's timeline, and the line ``XLA Ops``
holds one event per operation that ran (``XLA Modules``, one event per
program, stands in where a plane has no op line).  The harness's own
``TraceAnnotation`` events sit on the host plane, on the same clock.

* busy: the union of the device's op intervals inside the window, where the
  window is the host event named ``bench.window`` (the whole trace
  without one);
* idle share: 1 minus busy over the window's length;
* top ops: device time summed per op name (an op that holds others, such
  as a ``while``, counts their time too);
* idle gaps: the longest stretches inside the window in which no op ran,
  each named by the innermost host event, and the innermost span of the
  session, that hold its middle.

Every figure is averaged over the device planes found (one per chip).
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
OP_LINES = ("XLA Ops", "XLA Modules")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> list:
    """Planes as plain data: ``[(plane, [(line, [(name, start_ns, dur_ns)])])]``."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, float(e.start_ns),
                                  float(e.duration_ns)) for e in ln.events])
                      for ln in p.lines]) for p in pd.planes]


def device_ops(planes) -> list:
    """One list of op events per device plane."""
    out = []
    for name, lines in planes:
        if not name.startswith("/device:") or "CPU" in name:
            continue
        by = dict(lines)
        for want in OP_LINES:
            if by.get(want):
                out.append(by[want])
                break
    return out


def host_events(planes) -> list:
    """The events of the host thread that holds the window (all host
    events where no window was annotated)."""
    lines = [evs for name, ls in planes if name.startswith("/host:")
             for _, evs in ls]
    main = [evs for evs in lines if any(n == WINDOW for n, _, _ in evs)]
    return [ev for evs in (main or lines) for ev in evs]


def short(op: str) -> str:
    """An op's HLO text cut to its name, opcode and fusion kind."""
    head, sep, rest = op.partition(" = ")
    if not sep:
        return op[:120]
    code = re.search(r"\b([a-z][\w-]*)\(", rest)
    kind = re.search(r"kind=(k\w+)", rest)
    return " ".join([head] + [m.group(1) for m in (code, kind) if m])


def merge(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` covering the same time."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(planes) -> tuple[float, float]:
    wins = [(s, s + d) for n, s, d in host_events(planes) if n == WINDOW]
    if wins:
        return wins[0]
    spans = [(s, s + d) for _, lines in planes for _, evs in lines
             for _, s, d in evs]
    return min(s for s, _ in spans), max(e for _, e in spans)


def _label(host, t: float) -> str:
    """The innermost host event and the innermost session span (named
    ``span:...``) that hold time ``t``."""
    inner = {}
    for n, s, d in host:
        if s <= t <= s + d and n != WINDOW:
            k = n.startswith("span:")
            if k not in inner or d < inner[k][0]:
                inner[k] = (d, n)
    names = [inner[k][1] for k in (True, False) if k in inner]
    return " | ".join(names) if names else "no host event"


def reduce(planes, top: int = 10, extra_host=()) -> dict:
    """Busy and window seconds, idle share, top ops and the longest idle
    gaps.  ``extra_host`` adds ``(name, start_ns, dur_ns)`` events on the
    trace's clock to name gaps by.  Raises where the trace holds no device
    operation."""
    lo, hi = window_of(planes)
    devs = device_ops(planes)
    if not devs or not any(devs):
        raise ValueError("the trace holds no device operation")
    host = host_events(planes) + list(extra_host)
    busy, ops, gaps = 0.0, {}, []
    for evs in devs:
        inside = [(max(s, lo), min(s + d, hi), n) for n, s, d in evs
                  if s + d > lo and s < hi]
        spans = merge((s, e) for s, e, _ in inside)
        busy += sum(e - s for s, e in spans)
        for s, e, n in inside:
            n = short(n)
            ops[n] = ops.get(n, 0.0) + (e - s)
        edges = [lo] + [x for s, e in spans for x in (s, e)] + [hi]
        gaps += [(edges[i + 1] - edges[i], (edges[i] + edges[i + 1]) / 2)
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n_dev = len(devs)
    window_ns = hi - lo
    gaps.sort(reverse=True)
    return {
        "busy_s": busy / n_dev / 1e9,
        "window_s": window_ns / 1e9,
        "idle_pct": 100.0 * (1.0 - busy / n_dev / window_ns),
        "device_ops": [[n, t / n_dev / 1e9] for n, t in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(host, mid), g / 1e9] for g, mid in gaps[:top]],
    }
