"""Device self time per operator scope, from a profiler trace.

The program runs every operator call of its fixed-point drivers in a
``jax.named_scope`` named for the operator's class
(``repro.core.operators``).  Each device op then carries its name stack in
the ``tf_op`` stat of its metadata in the trace, for example
``jit(fixed_point_batch)/while/body/vmap(DirectionSwitch)/PullStep/gather``.

* self time: an op's duration less the part of it that ops nested inside
  it on the same line cover (a ``while`` holds the ops of its body), so
  the self times of a line add up to its busy time;
* scope: the innermost operator class in the op's name stack; an op under
  none counts as ``while`` inside the fixed-point loop (the loop's own
  control, and ops the compiler emits without metadata, which the
  profiler names by the loop: on a TPU, the fusion around a scatter) and
  as ``outside_operators`` elsewhere;
* loop: the ops under the fixed-point loop's ``while``, that is a
  ``while`` that no operator scope encloses, its own self time included;
* finish: the ops whose outermost operator scope is a finisher (a class
  with a ``finish`` method).

Ops count where they start inside the window, the host event
``bench.window`` (the whole trace without one).  Every figure is averaged
over the device planes found.  ``jax.profiler.ProfileData`` does not show
the metadata's stats, so this reads the few fields of the trace's XSpace
protobuf that it needs from the wire format.
"""
from __future__ import annotations

import gzip
import re

from bench import trace_reduce

OPS_LINE = "XLA Ops"
TF_OP = "tf_op"
LOOP = "while"
NO_SCOPE = "outside_operators"
_CLASS = re.compile(r"[/(]([A-Z]\w*)")


# ---------------------------------------------------------------------------
# the XSpace wire format
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, the
    bytes for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unknown protobuf wire type {wire}")
        yield key >> 3, value


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def _stat(buf, stat_names: dict) -> tuple[str, str | None]:
    """(name, string value) of one XStat; the value is None unless the
    stat holds a string or a reference to one."""
    f = dict(_fields(buf))
    name = stat_names.get(f.get(1), "")
    if 5 in f:
        return name, _text(f[5])
    if 7 in f:
        return name, stat_names.get(f[7])
    return name, None


def _plane_ops(buf) -> tuple[str, list]:
    """(plane name, ``[(tf_op, start_ps, dur_ps)]`` of its op line)."""
    name, lines, meta_bufs, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            entry = dict(_fields(v))
            meta_bufs[entry.get(1, 0)] = entry.get(2, b"")
        elif f == 5:
            sm = dict(_fields(dict(_fields(v)).get(2, b"")))
            stat_names[sm.get(1, 0)] = _text(sm.get(2, b""))
    if not name.startswith("/device:") or "CPU" in name:
        return name, []
    tf_op = {}
    for mid, mbuf in meta_bufs.items():
        for f, v in _fields(mbuf):
            if f == 5:
                sname, value = _stat(v, stat_names)
                if sname == TF_OP:
                    tf_op[mid] = value
    for lbuf in lines:
        line = dict((f, v) for f, v in _fields(lbuf) if f != 4)
        if _text(line.get(2, b"")) != OPS_LINE:
            continue
        t0_ps = line.get(3, 0) * 1000
        ops = []
        for f, v in _fields(lbuf):
            if f != 4:
                continue
            ev, op = {}, None
            for g, x in _fields(v):
                if g == 4:
                    sname, value = _stat(x, stat_names)
                    if sname == TF_OP:
                        op = value
                else:
                    ev[g] = x
            ops.append((op if op is not None else tf_op.get(ev.get(1)),
                        t0_ps + ev.get(2, 0), ev.get(3, 0)))
        return name, ops
    return name, []


def device_op_lines(space: bytes) -> list:
    """One ``[(tf_op, start_ns, dur_ns)]`` per device plane with ops."""
    out = []
    for f, v in _fields(memoryview(space)):
        if f == 1:
            _, ops = _plane_ops(v)
            if ops:
                out.append([(op, s / 1e3, d / 1e3) for op, s, d in ops])
    return out


def read_space(path: str) -> bytes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


# ---------------------------------------------------------------------------
# self time and scopes
# ---------------------------------------------------------------------------

def self_times(ops) -> list:
    """``[(tf_op, start_ns, self_ns)]``: each op's duration less the part
    of it that the ops nested in it cover."""
    order = sorted(range(len(ops)), key=lambda k: (ops[k][1], -ops[k][2]))
    own = [d for _, _, d in ops]
    stack = []                      # (end_ns, index) of enclosing ops
    for k in order:
        _, s, d = ops[k]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            end, parent = stack[-1]
            own[parent] -= min(s + d, end) - s
        stack.append((s + d, k))
    return [(ops[k][0], ops[k][1], own[k]) for k in range(len(ops))]


def scope_of(tf_op: str | None) -> tuple[str, bool, str | None]:
    """(scope, inside the fixed-point loop, outermost operator class) of
    an op's name stack."""
    stack = (tf_op or "").split(";")[0]
    stack = stack.rpartition(":")[0] if ":" in stack else stack
    parts = stack.split("/")
    classes = [(k, m) for k, p in enumerate(parts)
               for m in _CLASS.findall("/" + p)]
    first_loop = next((k for k, p in enumerate(parts) if p == LOOP), None)
    in_loop = first_loop is not None and (not classes
                                          or first_loop < classes[0][0])
    if classes:
        return classes[-1][1], in_loop, classes[0][1]
    return (LOOP if in_loop else NO_SCOPE), in_loop, None


def finishers() -> frozenset:
    """The program's finisher classes: those with a ``finish`` method."""
    from repro.core import operators

    return frozenset(name for name, c in vars(operators).items()
                     if isinstance(c, type)
                     and callable(getattr(c, "finish", None)))


def reduce_lines(lines, lo: float, hi: float, finish, top: int = 10
                 ) -> dict:
    """Device self time of the ops of ``lines`` (``device_op_lines``)
    that start in ``[lo, hi)``, in seconds: ``busy_s`` (all of it),
    ``loop_s``, ``finish_s`` (the ops under a class named in ``finish``)
    and ``scopes``, the ``top`` scopes with the most."""
    busy = loop = fin = 0.0
    scopes: dict = {}
    for ops in lines:
        for op, s, own in self_times(ops):
            if not lo <= s < hi:
                continue
            scope, in_loop, outer = scope_of(op)
            busy += own
            loop += own if in_loop else 0.0
            fin += own if outer in finish else 0.0
            scopes[scope] = scopes.get(scope, 0.0) + own
    n = max(len(lines), 1) * 1e9
    return {"busy_s": busy / n, "loop_s": loop / n, "finish_s": fin / n,
            "scopes": [[k, v / n] for k, v in sorted(
                scopes.items(), key=lambda kv: -kv[1])[:top]]}


def reduce(path: str, top: int = 10) -> dict:
    """``reduce_lines`` of a trace file over its window, with the
    program's finisher classes."""
    lo, hi = trace_reduce.window_of(trace_reduce.load(path))
    lines = device_op_lines(read_space(path))
    if not lines:
        raise ValueError("the trace holds no device operation")
    return reduce_lines(lines, lo, hi, finishers(), top)
