"""One run of one cell of ``BENCHMARK.json``, end to end.

A cell names a configuration (``configs/<name>.json``: generator, sizes,
session options), a traffic mix (``traffic/<name>.json``, read by
``loadgen``) and the metrics it reports, each a reader of its own
(``metrics/<name>.py``).  The plain reference of a configuration is
``reference/<name>.py``.  All are found by name, so a new cell or metric is
new files and entries, never an edit here.

A run: make the graph from the seed; build a ``ServingSession`` with the
configuration's options; serve warm-up traffic, from a stream of its own,
until a stretch of requests compiles nothing; serve the window's traffic
for ``seconds``; read the device's memory peak; free the program's state;
compare a sample of the window's answers, drawn from the seed, with the
reference.  ``--trace 1`` runs the same window with the session's tracer and
the profiler on, and reports the per-layer metrics instead of the
end-to-end ones.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import loadgen, trace_reduce

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding a cell's parts by name
# ---------------------------------------------------------------------------

def find(kind: str, name: str, suffix: str, dirs) -> Path:
    for d in dirs:
        p = Path(d) / kind / f"{name}{suffix}"
        if p.is_file():
            return p
    raise FileNotFoundError(f"no {kind}/{name}{suffix} under "
                            f"{[str(d) for d in dirs]}")


def load_module(path: Path):
    """Import a file by path (its name may hold '-' or '.')."""
    mod = "bench_part_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path))
    if mod in sys.modules:
        return sys.modules[mod]
    spec = importlib.util.spec_from_file_location(mod, path)
    m = importlib.util.module_from_spec(spec)
    sys.modules[mod] = m
    spec.loader.exec_module(m)
    return m


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    metrics: dict          # metric name -> BENCHMARK.json entry
    dirs: tuple

    def part(self, kind: str, name: str, suffix: str = ".py"):
        return load_module(find(kind, name, suffix, self.dirs))


def _reports(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(workload: str, trace: bool, bench_json: Path = None,
              dirs=()) -> Cell:
    """The cell ``workload`` of ``bench_json``, with its parts looked up in
    ``dirs`` and then in this directory."""
    bench_json = Path(bench_json or REPO / "BENCHMARK.json")
    spec = json.loads(bench_json.read_text())
    dirs = tuple(dirs) + (BENCH,)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg = json.loads(find("configs", w["config"], ".json", dirs).read_text())
    traffic = json.loads(
        find("traffic", w["traffic"], ".json", dirs).read_text())
    group = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {m["name"]: m for m in group if _reports(m, workload)}
    return Cell(workload, cfg, traffic, int(w["chips"]), metrics, dirs)


# ---------------------------------------------------------------------------
# the device and the compile cache
# ---------------------------------------------------------------------------

def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program.  Its key
    covers each op's metadata, so that a program loaded from the cache
    carries its own build's operator scopes into a trace, not those of
    whichever build wrote the entry."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int) -> list:
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked, {len(devs)} found")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


class CompileCount:
    """Programs compiled or loaded from the persistent cache, counted from
    JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.n = 0

        def listen(event: str, duration_secs: float, **_):
            if event == COMPILE_EVENT:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------------------
# data and session
# ---------------------------------------------------------------------------

def build(cell: Cell, seed: int):
    """The generated columns (as made) and the served session."""
    import jax.numpy as jnp

    from repro.core.engine import Dataset
    from repro.core.table import ColumnTable
    from repro.planner import ServingSession
    from repro.planner.calibrate import Calibrator
    from repro.planner.cost import DEFAULT_CONSTANTS

    gen = cell.part("generators", cell.config["generator"])
    cols, num_vertices = gen.generate(cell.config["params"], seed)
    table = ColumnTable({k: jnp.asarray(v) for k, v in cols.items()})
    ds = Dataset.prepare(table, num_vertices)
    opts = dict(cell.config.get("session", {}))
    constants = opts.pop("constants", None)
    if constants:
        opts["calibrator"] = Calibrator(
            dataclasses.replace(DEFAULT_CONSTANTS, **constants))
    return cols, num_vertices, ServingSession(ds, **opts)


def host_columns(cols: dict, names=None) -> dict:
    return {k: np.asarray(v) for k, v in cols.items()
            if names is None or k in names}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """One request as the client saw it."""

    roots: list
    answers: list          # one per root; None where the root failed
    latency_s: float
    t0: float
    why: str | None = None  # why the request failed


class Client:
    """Offers one mix's requests to a session: one ``submit`` after another
    (a closed loop)."""

    def __init__(self, session, sql: str, loop: dict):
        self.session = session
        self.sql = sql
        if loop["kind"] != "closed":
            raise ValueError(f"unknown loop {loop['kind']!r}")

    def serve(self, roots: list) -> Served:
        from repro.planner.guards import AdmissionError

        s = self.session
        t0 = time.perf_counter()
        try:
            answers = s.submit(self.sql, roots)
            why = "truncated or degraded" if s.last_report.truncated else None
        except AdmissionError as e:
            answers, why = [None] * len(roots), f"rejected: {e}"
        except Exception as e:          # a failed request, not a failed run
            answers, why = [None] * len(roots), f"{type(e).__name__}: {e}"
        dt = time.perf_counter() - t0
        if why is not None:
            answers = [None] * len(roots)
        return Served(roots, answers, dt, t0, why)


def warm_up(client: Client, requests, warm: dict, compiles: CompileCount):
    """Serve until ``quiet_requests`` in a row compile nothing, or
    ``max_requests`` have been served.  Returns (served, quiet at the end)."""
    quiet, n = 0, 0
    while quiet < int(warm["quiet_requests"]) and n < int(
            warm["max_requests"]):
        before = compiles.n
        client.serve(next(requests))
        n += 1
        quiet = quiet + 1 if compiles.n == before else 0
    return n, quiet


def answer_bytes(r) -> tuple[int, int, int]:
    """(rows, bytes per returned row, bytes of the vertex plane) of one
    served answer, from its own shapes."""
    per_row = sum(int(np.prod(np.shape(v)[1:], dtype=np.int64))
                  * np.asarray(v).dtype.itemsize for v in r.values.values())
    vv = getattr(r, "vertex_values", None)
    return int(r.count), per_row, (0 if vv is None else np.asarray(vv).nbytes)


class Sample:
    """The answers a run compares: a reservoir of ``n`` of the window's
    answers drawn from the seed, plus the largest one.

    Every answer it takes is held until the window has closed, also once
    the reservoir has let it go.  The program copies each answer into
    fresh host buffers; an answer the client frees inside the window
    leaves room in the host heap that a later copy reuses without faulting
    its pages in, so which requests paid for fresh pages would follow the
    seed's draws.  Held to the end, the sample frees nothing that a later
    answer could land in."""

    def __init__(self, n: int, seed: int):
        self.n = n
        self.rng = np.random.default_rng([seed, 4])
        self.kept, self.largest, self.seen, self.held = [], None, 0, []

    def offer(self, root, r):
        self.seen += 1
        took = len(self.kept) < self.n
        if took:
            self.kept.append((root, r))
        else:
            j = int(self.rng.integers(0, self.seen))
            took = j < self.n
            if took:
                self.kept[j] = (root, r)
        if self.largest is None or int(r.count) > int(self.largest[1].count):
            self.largest, took = (root, r), True
        if took:
            self.held.append(r)

    def answers(self) -> list:
        """(root, answer) pairs to compare."""
        kept = list(self.kept)
        if self.largest is not None and all(self.largest[1] is not r
                                            for _, r in kept):
            kept.append(self.largest)
        return kept


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    served: list
    window_s: float
    setup_s: float
    spans: list = None          # the session tracer's records (traced run)
    device: dict = None         # trace_reduce.reduce() of the window
    answer_counts: list = None  # answer_bytes() of every answer served
    peaks: dict = None


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, bench_json=None, dirs=(),
        trace_dir: Path = None, save_trace: str = None,
        compile_cache: bool = True, out=sys.stdout, err=sys.stderr) -> dict:
    """One run of one cell; prints its result line and returns it."""
    import jax

    cell = load_cell(workload, trace, bench_json, dirs)
    if compile_cache:
        enable_compile_cache()
    compiles = CompileCount()
    devs = require_chips(cell.chips) if require_tpu else jax.devices()[:1]
    peaks = peaks_for(devs[0].device_kind) if require_tpu else None

    from repro.obs.trace import Tracer

    cols, num_vertices, session = build(cell, seed)
    cfg, traffic = cell.config, cell.traffic
    pop = None
    if traffic["roots"]["kind"] != "fixed":
        pop = loadgen.population(traffic["roots"].get("population", {}),
                                 np.asarray(cols["from"]), num_vertices)
    roots = loadgen.Roots(traffic["roots"], seed, pop)
    client = Client(session, loadgen.sql(traffic["query"], cfg["query"]),
                    traffic["loop"])
    n_warm, quiet = warm_up(client, loadgen.requests(
        traffic["loop"], roots, loadgen.WARMUP), traffic["warmup"], compiles)
    print(f"[setup] warm-up requests={n_warm} quiet_at_end={quiet} "
          f"programs_compiled_or_loaded={compiles.n}", file=err, flush=True)

    tracer, t_epoch = None, None
    if trace:
        tracer = Tracer(level_events=False)
        t_epoch = time.perf_counter()      # the tracer's clock starts here
        session.tracer = tracer
        trace_dir = Path(trace_dir or REPO / ".bench_trace" / workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    # the window
    window = loadgen.requests(traffic["loop"], roots, loadgen.WINDOW)
    sample = Sample(int(traffic["check"]["answers"]), seed)
    served, counts = [], []
    compiles_before = compiles.n
    t_first = time.perf_counter()
    setup_s = t_first - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t_first < seconds:
            req = next(window)
            with jax.profiler.TraceAnnotation("bench.request"):
                s = client.serve(req)
            served.append(dataclasses.replace(s, answers=[]))
            for root, r in zip(s.roots, s.answers):
                if r is not None:
                    counts.append(answer_bytes(r))
                    sample.offer(root, r)
            # the next request is served while the client holds no answer
            # of this one but those in the sample
            del s, r
    window_s = time.perf_counter() - t_first
    compiles_in_window = compiles.n - compiles_before
    device = None
    if trace:
        jax.profiler.stop_trace()
        session.tracer = None

    engines = {}
    for s in served:
        for c in session.plan_for(client.sql, s.roots).bucket_choices:
            engines[c.label] = engines.get(c.label, 0) + 1
    print("[engines] buckets of the window's requests by engine: "
          + " ".join(f"{k}={v}" for k, v in sorted(engines.items())),
          file=out, flush=True)

    mem = [d.memory_stats() or {} for d in devs]
    peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    if trace:
        path = trace_reduce.find_xplane(str(trace_dir))
        if save_trace:
            shutil.copy(path, save_trace)
        planes = trace_reduce.load(path)
        if require_tpu or trace_reduce.device_ops(planes):
            device = trace_reduce.reduce(planes, extra_host=_aligned_spans(
                planes, tracer.records, served, t_epoch))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the reference: the program's state freed first
    reference = cell.part("reference", cfg["name"])
    needs = getattr(reference, "NEEDS", None)
    host = host_columns(cols, needs)
    del session, client, cols
    gc.collect()
    kept = sample.answers()
    ref = reference.Reference(host, num_vertices, cfg, traffic)
    t_ref = time.perf_counter()
    readings, bad = ref.compare([k[0] for k in kept], [k[1] for k in kept])
    ref_s = time.perf_counter() - t_ref

    attempted = sum(len(s.roots) for s in served)
    failed = sum(len(s.roots) for s in served if s.why is not None)
    checks = {"failed": {"value": failed, "limit": 0}}
    checks["answers_compared"] = {"value": len(kept), "limit": 1}
    for k, v in readings.items():
        checks[k] = {"value": v, "limit": reference.LIMITS[k]}
    correct = (failed == 0 and len(kept) >= 1 and all(
        c["value"] <= c["limit"] for k, c in checks.items()
        if k != "answers_compared"))

    r = Run(served=served, window_s=window_s, setup_s=setup_s,
            spans=tracer.records if tracer else None, device=device,
            answer_counts=counts, peaks=peaks)
    metrics = {}
    for name, entry in cell.metrics.items():
        value = cell.part("metrics", name).read(r)
        if value is not None:
            metrics[name] = {"value": value, "unit": entry["unit"]}

    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(peak)}
    if device is not None:
        dev["busy_s"] = device["busy_s"]
        dev["window_s"] = device["window_s"]
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if device is not None:
        result["breakdown"] = {"device_ops": device["device_ops"],
                               "idle_gaps": device["idle_gaps"]}
    result["checks"] = checks
    for root, why in bad[:5]:
        print(f"[check] root {root}: {why}", file=err)
    for s in [s for s in served if s.why][:5]:
        print(f"[check] request {s.roots[:4]}: {s.why}", file=err)
    print("[window] root:ms " + " ".join(
        f"{s.roots[0]}:{s.latency_s * 1e3:.1f}" for s in served[:64]),
        file=err)
    print(f"[window] requests={len(served)} answers={attempted} "
          f"window_s={window_s} setup_s={setup_s} "
          f"compiles_in_window={compiles_in_window} reference_s={ref_s}",
          file=err)
    for k, c in checks.items():
        op = ">=" if k == "answers_compared" else "<="
        print(f"check {k}={c['value']} limit {op} {c['limit']}", file=err,
              flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result


def _aligned_spans(planes, records, served, t_epoch) -> list:
    """The session's spans on the profiler's clock, named ``span:<name>``:
    the offset is the median distance between each request's start on the
    host clock and its ``bench.request`` annotation in the trace.  The
    ``dispatch`` spans are left out: they are recorded after the work they
    name."""
    starts = sorted(s for n, s, _ in trace_reduce.host_events(planes)
                    if n == "bench.request")
    if not starts or len(starts) != len(served):
        return []
    offset = float(np.median([a - s.t0 * 1e9
                              for a, s in zip(starts, served)]))
    return [("span:" + rec["name"], (t_epoch + rec["ts_us"] / 1e6) * 1e9
             + offset, rec["dur_us"] * 1e3) for rec in records
            if rec.get("type") == "span" and rec["name"] != "dispatch"]
