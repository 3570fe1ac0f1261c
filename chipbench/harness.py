"""One benchmark cell, run once: set up, warm up, measure, check, report.

    python3 chipbench/run.py --workload lubm30-litemat.rounds --seed 7 \
        --seconds 44 --trace 0

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in the file that entry names, its traffic mix in
``chipbench/traffic/<mix>.json``, the mix's queries in
``chipbench/queries/<set>.json`` and each metric's reader in
``chipbench/metrics/<metric>.py``.

A run, in order:

  1. refuses a backend other than a TPU, or fewer chips than the cell asks
     for (exit 3, no result line);
  2. generates the configuration's LUBM data on the host and builds the
     store (``KnowledgeBase.build``);
  3. starts a ``ServingRuntime`` with the configuration's settings;
  4. warms up on the queries the window will send first (a replay of
     them, constants included), in passes until a whole pass creates no
     executable;
  5. measures a closed loop of the mix's clients for ``--seconds``, with
     what the run keeps for its check frozen out of Python's collector
     (a full collection would otherwise rescan every answer kept so far,
     and its pauses would grow through the window);
  6. reads the peak device memory, decodes every answer through the
     store's dictionary, frees the store, and compares each answer with
     the plain reference (chipbench/reference.py);
  7. prints each compared number beside its limit on stderr, then the
     result line on stdout.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import threading
import time
from contextlib import nullcontext
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path

import numpy as np

from chipbench import lubm, traffic
from chipbench.reference import ALL_RULES, Reference

CHECKOUT = Path(__file__).resolve().parent.parent
WINDOW_SPAN = "chipbench.window"
REQUEST_SPAN = "client.request"
# (module, class, method, span): where the traced run opens a span around
# the calls into each layer of the served path
LAYER_SPANS = (
    ("repro.serving.runtime", "ServingRuntime", "_handle_batch", "runtime.batch"),
    ("repro.core.snapshot", "SnapshotRegistry", "pin", "snapshot.pin"),
    ("repro.core.query", "QueryEngine", "_plan", "query.plan"),
    ("repro.core.query", "QueryEngine", "_run_planned", "query.execute"),
)
WARMUP_THREADS = 8
WARMUP_MAX_PASSES = 6


class Refused(Exception):
    """The run cannot be made here (no chip, unknown name): no result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- the benchmark as data -----------------------------------------------------


@dataclass
class Cell:
    root: Path
    bench: dict
    workload: dict
    config: dict
    mix: dict
    qset: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    def metrics(self, kind: str) -> list:
        """The cell's metrics of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.bench[kind]
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(root: Path, name: str) -> Cell:
    bench = traffic.load_json(root / "BENCHMARK.json")
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise Refused(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    config = traffic.load_json(root / conf["file"])
    mix = traffic.load_json(root / "chipbench" / "traffic"
                            / f"{work['traffic']}.json")
    qset = traffic.load_json(root / "chipbench" / "queries"
                             / f"{mix['query_set']}.json")
    return Cell(root, bench, work, config, mix, qset)


def load_reader(root: Path, metric: str):
    path = root / "chipbench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- what a run records ---------------------------------------------------------


@dataclass
class Request:
    client: int
    query: traffic.Query
    t0: float  # submit, host clock
    t1: float  # answers on the host
    status: str
    queue_s: float
    exec_s: float
    answers: object = None
    error: str | None = None

    @property
    def latency_s(self) -> float:
        return self.t1 - self.t0


@dataclass
class Run:
    """Everything a metric reader may read (chipbench/metrics/*.py)."""

    cell: str
    seconds: float
    setup_s: float = 0.0
    build_s: float = 0.0
    warmup_s: float = 0.0
    warmup_passes: list = field(default_factory=list)  # executables per pass
    window_open: float = 0.0
    window_close: float = 0.0
    requests: list = field(default_factory=list)  # issued in the window
    executables_in_window: int = 0  # compiled or read from the cache
    cache_loads_in_window: int = 0
    device_bytes: int = 0  # live device arrays at the window's open
    n_explicit: int = 0
    memory_peak: int = 0  # peak_bytes_in_use after the window
    trace: object = None  # trace_reduce.TraceSummary in a traced run

    @property
    def completed(self) -> list:
        return [r for r in self.requests
                if r.status == "ok" and r.t1 <= self.window_close]


class Executables:
    """Executables JAX creates (compiled, or read from the persistent
    cache), counted from its monitoring events."""

    def __init__(self):
        import jax

        self.lock = threading.Lock()
        self.n = self.loads = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self.lock:
                self.n += 1
                self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self.lock:
                self.loads += 1

    def read(self) -> tuple:
        with self.lock:
            return self.n, self.loads


# -- the served path --------------------------------------------------------------


class Client:
    """Binds queries to the store's ids and sends them through the runtime."""

    def __init__(self, rt, qset: dict, mode: str, ids: dict):
        from repro.core.query import Pattern

        self.rt, self.mode, self.ids = rt, mode, ids
        self._pattern = Pattern
        self.qset = qset

    def request(self, q: traffic.Query):
        t = self.qset["templates"][q.template]
        bound = dict(q.params)
        pats = [self._pattern(*(self.ids[bound[x]] if x in bound else x
                                for x in pat)) for pat in t["patterns"]]
        return pats, tuple(t["select"])

    def submit(self, q: traffic.Query):
        pats, sel = self.request(q)
        return self.rt.submit(pats, select=sel, mode=self.mode)


def closed_loop(client: Client, streams: list, until: float | None = None,
                count: int | None = None, annotate=None,
                start: threading.Event | None = None):
    """Each stream is one client that sends its next query when the last
    one is answered: until ``until`` on the host clock, or ``count``
    queries each.  Starts the clients (after ``start`` is set, where given)
    and returns (threads, requests), for :func:`join_all`."""
    out, lock = [], threading.Lock()
    annotate = annotate or (lambda name: nullcontext())

    def run(i, stream):
        if start is not None:
            start.wait()
        n = 0
        while (until is None or time.perf_counter() < until) and \
                (count is None or n < count):
            q = next(stream)
            t0 = time.perf_counter()
            with annotate(REQUEST_SPAN):
                o = client.submit(q).result()
            t1 = time.perf_counter()
            with lock:
                out.append(Request(i, q, t0, t1, o.status, o.queue_s,
                                   o.exec_s, o.answers, o.error))
            gc.freeze()  # the kept answers leave the collector's view
            n += 1

    threads = [threading.Thread(target=run, args=(i, s), daemon=True,
                                name=f"chipbench-client-{i}")
               for i, s in enumerate(streams)]
    for t in threads:
        t.start()
    return threads, out


class CollectorPauses:
    """Python's collections and their pauses, for the run's log."""

    def __init__(self):
        self.pauses, self._t = [], 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)

    def __str__(self):
        p = self.pauses
        return (f"{len(p)} collections, longest "
                f"{max(p, default=0.0) * 1e3:.1f} ms, total {sum(p) * 1e3:.1f} ms")


def join_all(threads_out) -> list:
    """Wait for every client; -> every request sent, answered."""
    threads, out = threads_out
    for t in threads:
        t.join()
    return out


def warm_up(cell: Cell, client: Client, pools: dict, seed: int,
            execs: Executables) -> list:
    """Replay the queries the window will send first until a whole pass
    creates no executable (at most ``WARMUP_MAX_PASSES`` passes).

    A pass runs each distinct query among every client's first
    ``traffic.WARMUP_QUERIES`` once, on threads against one pinned
    snapshot, so their compiles overlap.  A second pass sees the
    selectivities the first observed, as the window will.  Returns the
    executables each pass created.
    """
    queries = traffic.warmup_queries(cell.mix, cell.qset, pools, seed)
    passes = []
    for _ in range(WARMUP_MAX_PASSES):
        before = execs.read()[0]
        with client.rt.registry.pin() as pin:
            calls = [partial(pin.query, *client.request(q), mode=client.mode)
                     for q in queries]
            with ThreadPoolExecutor(WARMUP_THREADS) as ex:
                for f in [ex.submit(c) for c in calls]:
                    f.result()
        passes.append(execs.read()[0] - before)
        log(f"warm-up pass {len(passes) - 1}: {len(queries)} queries, "
            f"{passes[-1]} executables")
        if passes[-1] == 0:
            break
    return passes


# -- correctness ------------------------------------------------------------------


def decode(K, requests: list, qset: dict) -> list:
    """Each answer as a sorted unique int64 array of fingerprint tuples,
    decoded through the store's own dictionary."""
    import jax.numpy as jnp
    from repro.utils import pair64

    arrays = []
    for r in requests:
        width = len(qset["templates"][r.query.template]["select"])
        rows = list(r.answers) if r.status == "ok" and r.answers else []
        arrays.append(np.asarray(rows, np.int64).reshape(len(rows), width))
    ids = np.unique(np.concatenate([np.zeros(1, np.int64)]
                                   + [a.reshape(-1) for a in arrays]))
    n = 1 << max(int(ids.size - 1).bit_length(), 10)  # pow2: few shapes
    padded = np.zeros(n, np.int32)
    padded[:ids.size] = ids
    hi, lo, hit = K.kb.table.extract_fp(jnp.asarray(padded))
    fps = pair64.combine_np(np.asarray(hi), np.asarray(lo))[:ids.size]
    fps = np.where(np.asarray(hit)[:ids.size], fps, -1)
    out = []
    for a in arrays:
        d = fps[np.searchsorted(ids, a)] if a.size else a
        out.append(np.unique(d, axis=0) if d.size else d.reshape(0, a.shape[1]))
    return out


def reference_answers(ref: Reference, qset: dict, queries) -> dict:
    """query key -> sorted unique fingerprint array from the reference."""
    out = {}
    for q in queries:
        if q.key() in out:
            continue
        t = qset["templates"][q.template]
        bound = dict(q.params)
        pats = [tuple(bound.get(x, x) for x in pat) for pat in t["patterns"]]
        out[q.key()] = ref.answers(pats, t["select"])
    return out


def compare(requests: list, decoded: list, want: dict) -> dict:
    """The numbers compared, each with its limit."""
    wrong = 0
    unanswered = 0
    for r, got in zip(requests, decoded):
        if r.status != "ok":
            unanswered += 1
            continue
        exp = want[r.query.key()]
        if got.shape != exp.shape or not np.array_equal(got, exp):
            wrong += 1
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0}}


# -- the run ------------------------------------------------------------------------


def parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench/run.py",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache in ``.jax_cache`` inside the checkout: a
    fixed path (it is part of the cache's key) that no other checkout
    shares, so only a cell's first run there compiles."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (backend {devs[0].platform})")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def live_device_bytes() -> int:
    import jax

    return int(sum(a.nbytes for a in jax.live_arrays()))


def program_dataset(data: lubm.Triples, onto: dict):
    """The store's input type, built from the benchmark's own data."""
    from repro.core.tbox import Ontology
    from repro.rdf.generator import RawDataset

    o = Ontology(concepts=list(onto["concepts"]),
                 properties=list(onto["properties"]),
                 subclass=[tuple(e) for e in onto["subclass"]],
                 subprop=[tuple(e) for e in onto["subprop"]],
                 domain={k: list(v) for k, v in onto["domain"].items()},
                 range_={k: list(v) for k, v in onto["range"].items()})
    return RawDataset(s=data.s, p=data.p, o=data.o, onto=o)


def locate_ids(K, fps: np.ndarray) -> dict:
    """Entity fingerprints -> the store's ids (one device lookup)."""
    import jax.numpy as jnp
    from repro.utils import pair64

    hi, lo = pair64.split_np(fps)
    ids, hit = K.kb.table.locate(jnp.asarray(hi), jnp.asarray(lo))
    ids, hit = np.asarray(ids), np.asarray(hit)
    if not hit.all():
        raise RuntimeError("a query constant is not in the store")
    return dict(zip(fps.tolist(), ids.tolist()))


def span_context(enabled: bool):
    if not enabled:
        return lambda name: nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def install_layer_spans() -> list:
    """Wrap each layer's entry method in a profiler span; returns names."""
    import importlib

    import jax

    names = []
    for mod_name, cls_name, meth, span in LAYER_SPANS:
        cls = getattr(importlib.import_module(mod_name), cls_name, None)
        fn = getattr(cls, meth, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _span=span, **kw):
            with jax.profiler.TraceAnnotation(_span):
                return _fn(*a, **kw)

        setattr(cls, meth, wrapped)
        names.append(span)
    return names


@dataclass
class Session:
    """A built store behind a started runtime: what every window of a
    process measures."""

    cell: Cell
    dev: dict
    onto: dict
    data: lubm.Triples
    K: object
    rt: object
    execs: Executables
    build_s: float
    spans: list = field(default_factory=list)  # installed in a traced run
    _refs: dict = field(default_factory=dict)

    def reference(self, rules=ALL_RULES) -> Reference:
        rules = tuple(rules)
        if rules not in self._refs:
            self._refs[rules] = Reference(self.data, self.onto, rules)
        return self._refs[rules]

    def close(self) -> None:
        """Stop the runtime and free the store (the reference comes after)."""
        if self.rt is not None:
            self.rt.stop()
        self.K = self.rt = None
        gc.collect()


def open_session(cell: Cell, require_tpu: bool = True) -> Session:
    """Refuse a missing chip, generate the data, build, start serving."""
    dev = device_info(int(cell.workload["chips"]), require_tpu)
    import jax

    execs = Executables()
    from repro.core.engine import KnowledgeBase
    from repro.serving.runtime import ServingRuntime

    conf = cell.config
    onto = lubm.ontology(conf["ontology"])
    data = lubm.generate(int(conf["universities"]), int(conf["data_seed"]),
                         onto)
    log(f"{cell.name}: {dev['kind']} x{dev['count']}; "
        f"LUBM-{conf['universities']}: {data.n} explicit triples")
    t = time.perf_counter()
    K = KnowledgeBase.build(program_dataset(data, onto))
    jax.block_until_ready((K.lite_spo, K.full_spo))
    build_s = time.perf_counter() - t
    log(f"built {K.sizes()} in {build_s:.3f}s")
    rt = ServingRuntime(K, modes=(conf["mode"],), **conf["runtime"])
    rt.start()
    return Session(cell, dev, onto, data, K, rt, execs, build_s)


def measure(s: Session, seed: int, seconds: float, trace: bool = False,
            t_start: float | None = None) -> Run:
    """Warm up on what the window sends first, then measure one window."""
    import jax

    cell = s.cell
    run = Run(cell=cell.name, seconds=seconds, build_s=s.build_s,
              n_explicit=s.data.n)
    if trace and not s.spans:
        s.spans = install_layer_spans() + [REQUEST_SPAN]
    pools = traffic.candidates(s.data, cell.qset, cell.mix["templates"],
                               s.onto["rdf_type"])
    fps = np.unique(np.concatenate([np.zeros(0, np.int64), *pools.values()]))
    client = Client(s.rt, cell.qset, cell.config["mode"],
                    locate_ids(s.K, fps) if fps.size else {})
    t = time.perf_counter()
    run.warmup_passes = warm_up(cell, client, pools, seed, s.execs)
    run.warmup_s = time.perf_counter() - t

    streams = [traffic.client_stream(cell.mix, cell.qset, pools, seed, i)
               for i in range(int(cell.mix["clients"]))]
    gc.collect()
    run.device_bytes = live_device_bytes()
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
    go = threading.Event()
    annotate = span_context(trace)
    collector = CollectorPauses()
    gc.callbacks.append(collector)
    gc.freeze()
    e0 = s.execs.read()
    run.window_open = time.perf_counter()
    run.window_close = run.window_open + seconds
    run.setup_s = run.window_open - (t_start if t_start is not None
                                     else run.window_open)
    pending = closed_loop(client, streams, until=run.window_close,
                          annotate=annotate, start=go)
    with annotate(WINDOW_SPAN):
        go.set()
        time.sleep(max(0.0, run.window_close - time.perf_counter()))
    run.requests = sorted(join_all(pending), key=lambda r: r.t0)
    gc.callbacks.remove(collector)
    gc.unfreeze()
    e1 = s.execs.read()
    run.executables_in_window = e1[0] - e0[0]
    run.cache_loads_in_window = e1[1] - e0[1]
    if trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    run.memory_peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"seed {seed}: window of {seconds}s: {len(run.requests)} requests "
        f"sent, {len(run.completed)} answered inside it; "
        f"{run.executables_in_window} executables created in it "
        f"({run.cache_loads_in_window} from the cache); "
        f"peak {run.memory_peak} B; collector: {collector}")
    by_template = {}
    for r in run.requests:
        by_template.setdefault(r.query.template, []).append(r.latency_s * 1e3)
    for name, lat in sorted(by_template.items()):
        log(f"  {name}: {len(lat)} sent, latency median {np.median(lat):.1f} ms,"
            f" max {max(lat):.1f} ms")
    log("latencies_ms " + " ".join(f"{r.latency_s * 1e3:.1f}"
                                   for r in run.requests))
    if trace:
        from chipbench import trace_reduce

        t = time.perf_counter()
        run.trace = trace_reduce.reduce(trace_reduce.find_xplane(logdir),
                                        WINDOW_SPAN, s.spans)
        shutil.rmtree(logdir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t:.3f}s")
    return run


def check(s: Session, run: Run, decoded: list, rules=ALL_RULES) -> dict:
    """Compare the decoded answers with the reference.  ``rules`` short of
    all of them puts the reference's control in the program's place."""
    t = time.perf_counter()
    queries = [r.query for r in run.requests]
    want = reference_answers(s.reference(), s.cell.qset, queries)
    if tuple(rules) != tuple(ALL_RULES):
        control = reference_answers(s.reference(rules), s.cell.qset, queries)
        decoded = [control[q.key()] for q in queries]
    checks = compare(run.requests, decoded, want)
    log(f"checked {len(queries)} answers ({len(want)} distinct queries) "
        f"against the reference in {time.perf_counter() - t:.3f}s")
    return checks


def result_line(cell: Cell, run: Run, dev: dict, checks: dict,
                trace: bool) -> dict:
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(kind):
        v = load_reader(cell.root, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=run.memory_peak)
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": len(run.requests),
           "failed": sum(r.status != "ok" for r in run.requests),
           "metrics": metrics, "device": device}
    if trace and run.trace is not None and run.trace.busy_s is not None:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = checks
    return out


def main(argv=None, t_start: float | None = None, root: Path | None = None,
         require_tpu: bool = True) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    root = Path(root or CHECKOUT)
    try:
        cell = load_cell(root, args.workload)
        enable_compile_cache(root)
        session = open_session(cell, require_tpu)
    except Refused as e:
        log(f"refused: {e}")
        return 3
    log(f"seed {args.seed}, {args.seconds}s window, trace {args.trace}")
    try:
        run = measure(session, args.seed, args.seconds, bool(args.trace),
                      t_start)
        decoded = decode(session.K, run.requests, cell.qset)
    finally:
        session.close()
    checks = check(session, run, decoded)
    out = result_line(cell, run, session.dev, checks, bool(args.trace))
    log(f"setup {run.setup_s:.3f}s (build {run.build_s:.3f}s, warm-up "
        f"{run.warmup_s:.3f}s, passes {run.warmup_passes}); "
        f"{run.n_explicit} explicit triples; device bytes {run.device_bytes}")
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0

