"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the result line.

Everything a cell is made of is found by name under ``bench/``:
``workloads/<cell>.json`` names the configuration, the traffic mix and the
cell's own parameters; ``configs/<config>.json`` holds the deployment
(stream, sketch, guarantees); ``traffic/<mix>.json`` the mix's parameters,
which the one load generator (``loadgen.py``) reads; ``metrics/<metric>.py``
reads one per-layer metric. ``BENCHMARK.json`` at the checkout's root says
which end-to-end and per-layer metrics each cell reports.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import re
import shutil
import sys
import tempfile
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from . import loadgen
from .reference import Reference, Sketch
from .stream import EdgeStream, StreamParams

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
SKETCH_KEYS = ("d", "n_blocks", "F", "r", "s", "c", "k", "window_size",
               "pool_capacity", "pool_probes", "seed")
# an open loop's query latency percentiles: those the log shows; a cell
# reports each ``query_p<n>_ms`` that BENCHMARK.json names for it
QUERY_PERCENTILES = (50, 90, 95, 99)
QUERY_METRIC = re.compile(r"query_p(\d+)_ms")


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


class Cell:
    """A workload cell with its configuration and traffic mix resolved."""

    def __init__(self, name: str, overrides: dict | None = None):
        self.name = name
        self.benchmark = load_json(ROOT / "BENCHMARK.json")
        entry = [w for w in self.benchmark["workloads"] if w["name"] == name]
        if not entry:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.entry = entry[0]
        wl = load_json(BENCH / "workloads" / f"{name}.json")
        cfg_entry = [c for c in self.benchmark["configs"]
                     if c["name"] == self.entry["config"]][0]
        self.config = load_json(ROOT / cfg_entry["file"])
        mix = load_json(BENCH / "traffic" / f"{self.entry['traffic']}.json")
        self.mix = loadgen.merge(mix, wl.get("params", {}))
        if overrides:
            self.config = loadgen.merge(self.config, overrides.get("config",
                                                                   {}))
            self.mix = loadgen.merge(self.mix, overrides.get("mix", {}))
        self.chips = int(self.entry["chips"])
        self.sketch = {k: self.config["sketch"][k] for k in SKETCH_KEYS}

    def end_to_end(self) -> list:
        return [m for m in self.benchmark["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.benchmark["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def spec_of(cell: Cell):
    import jax.numpy as jnp
    from repro import sketch as skt
    from repro.core import LSketchConfig
    dtype = cell.config["sketch"]["count_dtype"]
    cfg = LSketchConfig(**cell.sketch, count_dtype=getattr(jnp, dtype))
    return skt.SketchSpec(kind=cell.config["sketch"]["kind"], config=cfg,
                          n_shards=int(cell.config["n_shards"]))


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) while
    armed, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.names = []

        def on_event(event, duration, **kw):
            if self.armed and event == BACKEND_COMPILE:
                self.count += 1
                self.names.append(kw.get("fun_name", "?"))
        jax.monitoring.register_event_duration_secs_listener(on_event)


def enable_cache() -> str:
    """The program's persistent compilation cache (inside the checkout
    unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), holding every
    program however fast it compiled, so later runs load all of them."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


def sub_edges(cell: Cell) -> float:
    """Edges per subwindow: the stream's rate times the sketch's
    subwindow length."""
    e, t = cell.config["stream"]["edges_per_time_unit"]
    return e * Sketch(cell.sketch).sub / t


def warm_edges(cell: Cell) -> int:
    """The warm-up's edges, whole batches: ``warmup.windows`` full
    windows (every ring slot claimed) and ``warmup.subwindows`` more (at
    least one slot reclaimed)."""
    w = cell.mix["warmup"]
    subs = w.get("windows", 0) * cell.sketch["k"] + w.get("subwindows", 0)
    batch = int(cell.mix["ingest"]["batch"])
    return int(np.ceil(subs * sub_edges(cell) / batch)) * batch


def check_queries(cell: Cell, plan: loadgen.Plan, server, n_total: int):
    """After the window, a sample of every query group the mix names,
    drawn from the seed's newest edges, through the served path (plane
    cache and query programs)."""
    chk = cell.mix.get("check")
    if not chk:
        return None
    rng = np.random.default_rng([plan.stream.seed, 3])
    cols = plan.stream.cols
    recent = int(chk["recent_subwindows"] * sub_edges(cell))
    reqs = []
    for g in chk["groups"]:
        edges = n_total - 1 - rng.integers(0, recent, int(chk["per_group"]))
        reqs += [loadgen.request(g, cols, int(e)) for e in edges]
    handles = [server.submit(kind, **args) for kind, args in reqs]
    server.flush()
    return {"reqs": reqs, "answers": np.array([h.answer for h in handles]),
            "n_at": np.full(len(reqs), n_total)}


def reference_answers(ref: Reference, reqs: list, n_at: np.ndarray):
    """The reference's answer to each ``(kind, args)`` request, grouped
    like the server groups them."""
    out = np.zeros(len(reqs), np.int64)
    groups = {}
    for i, (kind, a) in enumerate(reqs):
        key = (kind, "le" in a, a.get("direction", "out"))
        groups.setdefault(key, []).append(i)
    for (kind, with_le, direction), idx in groups.items():
        idx = np.array(idx)
        col = lambda f: np.array([reqs[i][1][f] for i in idx])
        le = col("le") if with_le else None
        if kind == "edge":
            out[idx] = ref.edges(col("src"), col("la"), col("dst"),
                                 col("lb"), le, n_at[idx])
        elif kind == "vertex":
            out[idx] = ref.vertices(col("v"), col("lv"), le, direction,
                                    n_at[idx])
        else:
            out[idx] = ref.labels(col("lv"), le, direction, n_at[idx])
    return out


def program_state(shards) -> dict:
    """The program's state read back, in the reference's format
    (``Reference.state``): the shard axis dropped, every leaf flat, ``P``
    as its nonzero ``(flat index, value)`` pairs, so that no dense copy of
    ``P`` is made."""
    out = {}
    for name in ("key", "C", "P", "pool_key", "pool_C", "pool_P",
                 "pool_lost", "slot_widx", "cur_widx"):
        leaf = np.asarray(getattr(shards, name)).reshape(-1)
        if name == "P":
            nz = np.flatnonzero(leaf)
            out[name] = (nz, leaf[nz])
        else:
            out[name] = leaf
    return out


def state_mismatches(got: dict, want: dict) -> dict:
    """Elements of each state leaf that differ, both sides in the
    reference's format; ``P`` compares its nonzero pairs."""
    out = {}
    for name, w in want.items():
        g = got[name]
        if name == "P":
            (gi, gv), (wi, wv) = g, w
            both, pg, pw = np.intersect1d(gi, wi, assume_unique=True,
                                          return_indices=True)
            diff = len(gi) + len(wi) - 2 * len(both)
            out[name] = int(diff + np.sum(gv[pg] != wv[pw]))
        elif g.shape == w.shape:
            out[name] = int(np.sum(g != w))
        else:
            out[name] = int(max(g.size, w.size))
    return out


def metric_file(name: str) -> Path:
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``, or,
    for ``<base>.<part>`` (one quantity split by the end-to-end metric
    each cell reports) with no file of its own, ``metrics/<base>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if path.exists():
        return path
    return BENCH / "metrics" / f"{name.split('.')[0]}.py"


def load_metric(name: str):
    path = metric_file(name)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_COMPILES = None


def compile_counter() -> "CompileCounter":
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileCounter()
    _COMPILES.count, _COMPILES.names = 0, []
    return _COMPILES


class GcPauses:
    """Durations of the host's garbage collections while armed."""

    def __init__(self):
        self.armed = False
        self.times = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = loadgen.CLOCK()
        elif self.armed and self._t0 is not None:
            self.times.append(loadgen.CLOCK() - self._t0)

    def close(self):
        gc.callbacks.remove(self._on)


def log_slowest_calls(spans, plan, sk, res, top: int = 5) -> None:
    """The window's slowest ingest calls, each with its batch's index and
    the number of subwindow boundaries the batch spans: where a run reads
    far off, this says which call held it and whether the batch was one
    the boundary scan takes."""
    t = spans.times.get("ingest", [])
    if not t:
        return
    order = np.argsort(t)[::-1][:top]
    rows = []
    for i in order:
        w = plan.batches[i]["time"] // sk.sub
        rows.append(f"#{i} {t[i] * 1e3:.1f} ms ({int(w[-1] - w[0])} "
                    "boundaries)")
    log(f"window: median ingest call {np.median(t) * 1e3:.1f} ms; "
        f"slowest {', '.join(rows)}; final wait "
        f"{max(spans.times.get('wait', [0.0])) * 1e3:.1f} ms")


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float) -> SimpleNamespace:
    """Set-up, the measured window, and the read-back of what it produced:
    the plan, the window's record, the served state on the host, the
    post-window check answers and the device readings. The program's
    state is freed before this returns."""
    import jax
    from repro.launch.serve_sketch import SketchServer
    from repro.sketch.query import PLANES_BUILD_COUNTS

    compiles = compile_counter()
    t = loadgen.CLOCK()
    spec = spec_of(cell)
    sk = Sketch(cell.sketch)
    stream = EdgeStream(StreamParams.from_config(cell.config["stream"]), seed)
    plan = loadgen.Plan(cell.mix, stream, warm_edges(cell), seconds, seed)
    log(f"set-up: {plan.n_edges} edges generated in "
        f"{loadgen.CLOCK() - t:.3f} s")
    t = loadgen.CLOCK()
    server = SketchServer(spec, max_batch=1 << 30, pipeline=True,
                          query_path="auto", prewarm=True)

    def sync():
        jax.block_until_ready(jax.tree.leaves(server.state.shards))

    loadgen.warm(server, plan, sync)
    log(f"set-up: warm-up ({plan.warm_edges} edges) in "
        f"{loadgen.CLOCK() - t:.3f} s")
    spans = loadgen.Spans(annotate=trace)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the benchmark's spans suffice
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    planes0 = dict(PLANES_BUILD_COUNTS)
    pauses = GcPauses()
    compiles.armed = pauses.armed = True
    with spans("window"):
        res = loadgen.run_window(server, plan, spans, sync)
    compiles.armed = pauses.armed = False
    pauses.close()
    planes = {k: PLANES_BUILD_COUNTS[k] - planes0[k] for k in planes0}
    if trace:
        t = loadgen.CLOCK()
        jax.profiler.stop_trace()
        log(f"trace written in {loadgen.CLOCK() - t:.3f} s")
    dev = jax.devices()[0]
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    n_total = plan.warm_edges + res["edges"]
    log(f"window: {res['batches']} batches, {res['edges']} edges in "
        f"{res['t1'] - res['t0']:.3f} s; compiles inside the window: "
        f"{compiles.count} {sorted(set(compiles.names))}; plane cache "
        f"{planes}; longest call " + ", ".join(
            f"{k} {max(v) * 1e3:.1f} ms" for k, v in spans.times.items()
            if k != "window"))
    log_slowest_calls(spans, plan, sk, res)
    log(f"window: garbage collections {len(pauses.times)}, "
        f"{sum(pauses.times) * 1e3:.1f} ms in all, longest "
        f"{max(pauses.times, default=0.0) * 1e3:.1f} ms")
    if res.get("ran_dry"):
        log("the stream ran dry before the window closed: raise "
            "max_edges_per_s in the cell's parameters")
    t = loadgen.CLOCK()
    chk = check_queries(cell, plan, server, n_total)
    state = program_state(jax.device_get(server.state.shards))
    del server
    gc.collect()
    log(f"check queries and state read back in {loadgen.CLOCK() - t:.3f} s")
    return SimpleNamespace(
        plan=plan, res=res, spans=spans, planes=planes, trace_dir=trace_dir,
        peak=peak, device=dev, n_total=n_total, chk=chk, state=state,
        sketch=sk, setup_s=res["t0"] - t_start, compiles=compiles.count,
        gc_s=sum(pauses.times))


def answered(m: SimpleNamespace):
    """Every request the run answered: ``(reqs, answers, n_at, failed)``,
    the window's queries first, then the post-window check."""
    reqs, got = [], np.zeros(0, np.int64)
    n_at, failed = np.zeros(0, np.int64), np.zeros(0, bool)
    if m.plan.queries is not None:
        reqs = list(m.plan.queries["reqs"])
        got, n_at = m.res["answers"], m.res["n_at"]
        failed = m.res["failed_mask"]
    if m.chk is not None:
        reqs += m.chk["reqs"]
        got = np.concatenate([got, m.chk["answers"]])
        n_at = np.concatenate([n_at, m.chk["n_at"]])
        failed = np.concatenate([failed, np.zeros(len(m.chk["reqs"]), bool)])
    return reqs, got, n_at, failed


def compare(cell: Cell, m: SimpleNamespace) -> dict:
    """The numbers compared, each with its limit: answers and state
    elements that differ from the plain reference's."""
    t = loadgen.CLOCK()
    ref = Reference(cell.sketch, m.plan.stream.slice(0, m.n_total))
    bad_state = state_mismatches(m.state, ref.state())
    reqs, got, n_at, failed = answered(m)
    want = reference_answers(ref, reqs, n_at)
    log(f"check: reference over {m.n_total} edges ({len(ref.pair_a)} "
        f"distinct pairs) in {loadgen.CLOCK() - t:.3f} s; compared "
        f"{len(reqs)} answers and the state "
        f"({', '.join(f'{k} {v}' for k, v in bad_state.items())})")
    return {"answers_wrong": {"value": int(np.sum((got != want) | failed)),
                              "limit": 0},
            "state_wrong": {"value": int(sum(bad_state.values())),
                            "limit": 0}}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    import jax
    m = measure(cell, seed, seconds, trace, t_start)
    checks = compare(cell, m)
    res, plan = m.res, m.plan
    values = {"setup_s": m.setup_s,
              "ingest_edges_per_s": res["edges"] / (res["t1"] - res["t0"])}
    if not plan.closed:
        lat = np.where(res["failed_mask"], np.inf, res["latency_s"]) * 1e3
        for x in cell.end_to_end():
            named = QUERY_METRIC.fullmatch(x["name"])
            if named:
                values[x["name"]] = float(np.percentile(lat,
                                                        int(named[1])))
        late = res["ingest_late_s"]
        log(f"queries: {len(lat)} in {len(m.spans.times.get('flush', []))} "
            "flushes, latency " + ", ".join(
                f"p{p} {v:.3f} ms" for p, v in zip(
                    QUERY_PERCENTILES, np.percentile(lat, QUERY_PERCENTILES)))
            + f", max {lat.max():.3f} ms; ingest batches late by p50 "
            f"{np.median(late) * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms "
            "(first half of the window "
            f"{late[:len(late) // 2].max(initial=0) * 1e3:.3f}, second "
            f"{late[len(late) // 2:].max(initial=0) * 1e3:.3f})")
    dev = m.device
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": m.peak}
    out = {"correct": is_correct(checks),
           "attempted": int(res["batches"] + len(answered(m)[0])),
           "failed": int(res["failed"]),
           "metrics": {}, "device": device}
    units = {x["name"]: x["unit"] for x in
             cell.benchmark["end_to_end"] + cell.benchmark["per_layer"]}
    if not trace:
        for x in cell.end_to_end():
            out["metrics"][x["name"]] = {"value": values[x["name"]],
                                         "unit": x["unit"]}
    else:
        from . import rooflines, trace_reduce
        t = loadgen.CLOCK()
        red = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(m.trace_dir)))
        shutil.rmtree(m.trace_dir, ignore_errors=True)
        log(f"trace read and reduced in {loadgen.CLOCK() - t:.3f} s; "
            f"events dropped: {red['dropped']}; the devices recorded "
            f"{red['window_s']:.3f} s of the {red['full_window_s']:.3f} s "
            f"window (cut: {red['cut']}), {red['host_n'].get('ingest', 0)} "
            f"of its {res['batches']} ingest calls; programs (launches, "
            "device s): " + ", ".join(
                f"{k} {v[1]} {v[0]:.4f}" for k, v in sorted(
                    red["modules"].items(), key=lambda kv: -kv[1][0])[:8]))
        # what a per-layer metric reader may read
        ctx = SimpleNamespace(trace=red, spans=m.spans.times, window=res,
                              plan=plan, sketch=m.sketch, planes=m.planes,
                              peaks=rooflines.peaks(dev.device_kind))
        for x in cell.per_layer():
            v = load_metric(x["name"])(ctx)
            if v is not None:
                out["metrics"][x["name"]] = {"value": v,
                                             "unit": units[x["name"]]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = trace_reduce.breakdown(red)
    out["window"] = {"compiles": m.compiles, "edges": res["edges"],
                     "seconds": res["t1"] - res["t0"]}
    out["checks"] = checks
    return out


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
