"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s>
                        --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``), its traffic
(``bench/traffic/<traffic>.json``) and its metrics (``bench/metrics/
<metric>.py``) are found by the names in ``BENCHMARK.json``. In one
process the run

1. exits nonzero, before any set-up, unless JAX sees a TPU with as many
   chips as the cell asks for;
2. makes the configuration's collection (from its ``collection_seed``)
   on the device, or, for a configuration whose ``store`` is ``host``,
   chunk by chunk into host memory; fits, encodes, sorts and indexes it
   through the program's own build calls (demoting the program's f32
   rerank store to host memory for a host store); builds the
   ``ServingEngine`` and ``ServingFrontend`` (which compiles every bucket
   shape); and draws the run's queries (from ``--seed``) on the device:
   all of that is ``setup_s``;
3. offers the traffic through ``ServingFrontend.enqueue`` for
   ``--seconds`` and fails if anything compiles inside the window;
4. reads the device's peak memory, frees the program's state, and checks
   a sample of the answers against the exact reference (``correct``);
5. prints, as the last line of standard output, one JSON object: with
   ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
   per-layer metrics, read from a profiler trace of a slice of the window.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, NamedTuple, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

# most answers compared with the reference per run (a sample drawn from
# the seed when the window answered more)
CHECK_MAX = 4096
# the traced slice of a --trace 1 run: it opens this long into the window
# and lasts at most TRACE_S
TRACE_LEAD_S = 1.0
TRACE_S = 2.0
LEARN_QUERIES = 512
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- the benchmark's files, found by name ---------------------------------

class Spec:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench = self.root / self.data["paths"][0]

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return json.loads((self.bench / "configs" / f"{name}.json")
                          .read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def reader(self, name: str):
        """``read(run)`` of ``bench/metrics/<name>.py``."""
        path = self.bench / "metrics" / f"{name}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read

    def metrics(self, cell: str, trace: bool) -> list:
        """The metrics a run of ``cell`` reports: end-to-end ones without
        the trace, per-layer ones with it."""
        e2e = [m for m in self.data["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]


# -- set-up ----------------------------------------------------------------

class Placement(NamedTuple):
    """The harness's functions for where a configuration keeps its f32
    rerank store."""
    make: Callable          # the collection (``datagen.make``'s arguments)
    with_pool: Callable     # its served pool
    exact_topk: Callable    # the reference's top-k
    scores_of: Callable     # the reference's scores of served ids


def store(cfg: dict) -> str:
    """Where the configuration keeps its f32 rerank store: ``device`` (the
    default) or ``host``, a deployment whose store lives in host memory."""
    where = cfg.get("store", "device")
    if where not in ("device", "host"):
        raise ValueError(f"unknown store {where!r}: device or host")
    return where


def placement(cfg: dict) -> Placement:
    """The functions for the configuration's :func:`store`. The host ones
    never hand the device more than one chunk of rows or one block of the
    reference's rows."""
    from bench import datagen, reference
    if store(cfg) == "host":
        return Placement(datagen.make_host, datagen.with_host_pool,
                         reference.exact_topk_host, reference.scores_of_host)
    return Placement(datagen.make, datagen.with_pool,
                     reference.exact_topk, reference.scores_of)


class Served(NamedTuple):
    engine: object
    frontend: object
    state: object


def build(cfg: dict, x, learn, capacity: int, buckets=None):
    """Fit (on a sample drawn from the configuration's ``collection_seed``),
    encode, sort and index ``x`` the way ``repro.launch.serve``'s
    ``run_search`` does, and build the engine and the frontend (whose
    construction compiles and warms every bucket shape). A host store
    (``x`` a host array) goes to the program's build calls as it is, and
    the program's store is demoted to host memory after the index is
    built, in the order of ``repro.launch.serve.build_state``'s
    ``--host-rerank``. Returns the :class:`Served` and the clock readings
    after the fit, the build and the engine (whose construction compiles
    its own batch shape)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import gleanvec as gv
    from repro.core import search as msearch
    from repro.index import ivf
    from repro.launch.serve import FIT_ROWS
    from repro.serve.engine import ServingEngine
    from repro.serve.frontend import ServingFrontend
    from bench import datagen

    seed = cfg["collection_seed"]
    host = store(cfg) == "host"
    n = x.shape[0]
    rows = x
    if n > FIT_ROWS:
        pick = jax.random.choice(datagen.seed_key(seed), n, (FIT_ROWS,),
                                 replace=False)
        if host:
            rows = jnp.asarray(x[np.asarray(jnp.sort(pick))])
        else:
            rows = x[jnp.sort(pick)]
    model = gv.fit(jax.random.PRNGKey(0), learn, rows, c=cfg["clusters"],
                   d=cfg["d"])
    jax.block_until_ready(model)
    del rows
    t_fit = time.perf_counter()
    artifacts = msearch.build_artifacts(cfg["mode"], x, model)
    index = None
    if cfg["index"] == "ivf-aligned":
        # over the store as build_artifacts placed it (``x`` itself for a
        # device store): a host ``x`` would cross to the device again
        index = ivf.build_aligned(model, artifacts.x_full,
                                  nprobe=cfg["nprobe"])
        if cfg["reduced_probe"]:
            index = ivf.with_reduced_centers(index, artifacts.scorer, model)
    elif cfg["index"] != "flat":
        raise ValueError(f"unknown index {cfg['index']!r}")
    if host:
        artifacts = msearch.demote_rerank_tier(artifacts)
    state = msearch.make_state(artifacts, index=index,
                               block=cfg["layout_block"])
    jax.block_until_ready(state)
    t_build = time.perf_counter()
    engine = ServingEngine(state, k=cfg["k"], kappa=cfg["kappa"],
                           batch_size=cfg["max_batch"], dim=cfg["dim"])
    t_engine = time.perf_counter()
    frontend = ServingFrontend(engine, capacity=capacity, buckets=buckets)
    return Served(engine, frontend, state), (t_fit, t_build, t_engine)


# -- work counts of the traced batches -------------------------------------

def batch_work(cfg: dict, state, batches) -> dict:
    """``{batch index: {"step": Work, "fine": Work | None}}`` of the given
    batches, from their served queries and the index's probe sets."""
    import jax
    import numpy as np
    from bench import work

    out = {}
    if cfg["index"] == "flat":
        n = int(np.sum(np.asarray(state.artifacts.scorer.perm) >= 0))
        for b in batches:
            out[b.index] = {
                "step": work.flat_scan_work(b.n_real, n, cfg["d"])
                + work.rerank_work(b.n_real, cfg["kappa"], cfg["dim"]),
                "fine": None}
        return out
    from repro.index import ivf

    @jax.jit
    def probes(q, state):
        qs = state.index.prepare_queries(state.artifacts.scorer, q)
        return jax.lax.top_k(ivf.coarse_scores(state.index, qs),
                             state.index.nprobe)[1]

    list_rows = np.asarray((state.index.lists >= 0).sum(axis=1))
    for b in batches:
        probe = np.asarray(probes(b.queries, state))[:b.n_real]
        fine = work.ivf_scan_work(probe, list_rows, cfg["d"])
        out[b.index] = {
            "step": fine + work.rerank_work(b.n_real, cfg["kappa"],
                                            cfg["dim"]),
            "fine": fine}
    return out


# -- one run ---------------------------------------------------------------

class Run:
    """What the metric readers see of one run (``bench/metrics/*.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class CompileCounter:
    """Backend compiles seen by ``jax.monitoring`` (as ``tests/conftest.py``
    counts them): persistent-cache hits and in-memory hits fire nothing."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self._m = monitoring
        monitoring.register_event_duration_secs_listener(self._listener)

    def _listener(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.count += 1

    def close(self):
        self._m.unregister_event_duration_listener(self._listener)


def _traced_slice(win, seconds: float, log_dir: str):
    """Start a thread that traces a slice of the window into ``log_dir``,
    inside one ``bench.trace_window`` host span."""
    import jax
    from bench.trace_reduce import WINDOW

    length = min(TRACE_S, max(0.2, seconds - TRACE_LEAD_S - 0.2))

    def trace():
        time.sleep(max(0.0, win.t_start + min(TRACE_LEAD_S, seconds / 4)
                       - time.perf_counter()))
        jax.profiler.start_trace(log_dir)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                time.sleep(length)
        finally:
            jax.profiler.stop_trace()

    t = threading.Thread(target=trace, name="bench-trace")
    t.start()
    return t


def measure(frontend, pool, traffic: dict, seconds: float, k: int,
            log_dir: Optional[str] = None):
    """Drive one window of ``traffic`` through ``frontend``, tracing a
    slice of it into ``log_dir`` when one is given. Fails when anything
    compiled inside the window or the query pool ran dry."""
    from bench import load

    compiles = CompileCounter()
    tracer = []
    try:
        win = load.drive(
            frontend, pool, traffic, seconds, k,
            on_start=(lambda w: tracer.append(
                _traced_slice(w, seconds, log_dir))) if log_dir else None)
        for th in tracer:
            th.join()
        n_compiles = compiles.count
    finally:
        compiles.close()
    frontend.close()
    if n_compiles:
        raise RuntimeError(f"{n_compiles} compile(s) inside the measured "
                           "window: a shape was not warmed up")
    if win.pool_exhausted:
        raise RuntimeError("the window used up the query pool: the window "
                           "served far faster than the set-up's steps")
    return win


def check_answers(cfg: dict, x, pool, win, seed: int):
    """Compare a sample (drawn from the seed) of the window's answers with
    the exact reference (over ``x`` where the configuration keeps it).
    Returns ``(correct, checks, comparison)``:
    ``checks`` holds each number compared beside its limit: the requests
    left without an answer, and each number the configuration's
    ``check_limits`` names."""
    import numpy as np
    from bench import load, reference

    place = placement(cfg)
    rows = load.answered(win)
    if len(rows) > CHECK_MAX:
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
        rows = np.sort(rng.choice(rows, CHECK_MAX, replace=False))
    queries = pool[rows]
    ref_scores, ref_ids = place.exact_topk(queries, x, cfg["k"])
    got = win.ids[rows]
    cmp = reference.compare(got, place.scores_of(queries, x, got),
                            ref_ids, ref_scores, cfg["n"])
    failed = int(np.sum(win.in_window & ~win.ok))
    checks = {"failed": {"value": failed, "limit": 0}}
    for name, limit in cfg["check_limits"].items():   # miss10, worst_gap
        checks[name] = {"value": getattr(cmp, name), "limit": limit}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return correct, checks, cmp


def full_batch_s(served: Served, queries, max_batch: int) -> float:
    """Least time of three warmed steps at the largest bucket."""
    import jax
    import numpy as np
    q = np.asarray(queries[:max_batch], np.float32)
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(served.engine.search_with(q, served.state))
        best = min(best, time.perf_counter() - t)
    return best


class GcPauses:
    """Collector passes while open: ``(generation, seconds)`` each."""

    def __init__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def close(self):
        gc.callbacks.remove(self._cb)


def log_host_stalls(win, inst, pauses: GcPauses) -> None:
    """One stderr line on where the window's host time between device
    steps went: the gaps between consecutive batches' spans, and the
    collector's passes."""
    from bench import load
    spans = load.batches_in(inst, win)
    gaps = sorted(b.t0 - a.t1 for a, b in zip(spans, spans[1:]))
    gc_s = [s for _, s in pauses.pauses]
    if gaps:
        log(f"[host] batches={len(spans)} gap_sum_s={sum(gaps):.4f} "
            f"gap_p50_ms={1e3 * gaps[len(gaps) // 2]:.2f} "
            f"gap_max_ms={1e3 * gaps[-1]:.2f} "
            f"gaps_over_50ms={sum(g > 0.05 for g in gaps)} "
            f"gc_passes={len(gc_s)} "
            f"gc_gen2={sum(g == 2 for g, _ in pauses.pauses)} "
            f"gc_sum_ms={1e3 * sum(gc_s):.2f} "
            f"gc_max_ms={1e3 * max(gc_s, default=0.0):.2f}")


def run_cell(spec: Spec, cell_name: str, seed: int, seconds: float,
             trace: bool, t_process: Optional[float] = None) -> dict:
    """One run of one cell; returns the result line as a dict. Needs no
    chip (``main`` checks for one)."""
    import jax
    import numpy as np
    from bench import load, work

    t0 = T_PROCESS if t_process is None else t_process
    cell = spec.cell(cell_name)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    place = placement(cfg)

    t = time.perf_counter()
    coll = place.make(cfg["collection_seed"], seed, cfg["n"], cfg["dim"],
                      LEARN_QUERIES, 0)
    jax.block_until_ready(coll.x)
    t_data = time.perf_counter()
    compiles = CompileCounter()
    try:
        served, (t_fit, t_build, t_engine) = build(
            cfg, coll.x, coll.learn, int(traffic["queue"]))
    finally:
        compiles.close()
    t_buckets = time.perf_counter()
    coll = place.with_pool(coll, seed, load.capacity(
        traffic, seconds, full_batch_s(served, coll.learn, cfg["max_batch"]),
        cfg["max_batch"]))
    inst = load.Instrumented(served.frontend, keep_queries=trace)
    # the set-up's objects are long-lived: keep the collector from
    # walking them inside the window
    gc.collect()
    gc.freeze()
    t_ready = time.perf_counter()
    split = {"start_s": t - t0, "data_s": t_data - t, "fit_s": t_fit - t_data,
             "build_s": t_build - t_fit, "engine_s": t_engine - t_build,
             "buckets_s": t_buckets - t_engine,
             "pool_s": t_ready - t_buckets, "setup_s": t_ready - t0}
    log("[setup] " + " ".join(f"{a}={b:.3f}" for a, b in split.items())
        + f" compiles_after_data={compiles.count} pool={len(coll.pool)}")

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    pauses = GcPauses()
    try:
        win = measure(served.frontend, coll.pool, traffic, seconds,
                      cfg["k"], log_dir)
    finally:
        pauses.close()
        gc.unfreeze()
    log_host_stalls(win, inst, pauses)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    reduced, per_batch, peaks = None, {}, None
    if trace:
        from bench import trace_reduce
        reduced = trace_reduce.Reduced(
            trace_reduce.load(trace_reduce.find_xplane(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
        traced = reduced.batches()
        per_batch = batch_work(cfg, served.state,
                               [b for b in inst.batches if b.index in traced])
        peaks = work.peaks(dev.device_kind)
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)

    # free the program's state before the reference runs
    run = Run(cell=cell, cfg=cfg, traffic=traffic, seconds=seconds,
              window=win, batches=load.batches_in(inst, win), setup=split,
              trace=reduced, work=per_batch, peaks=peaks)
    del served, inst
    if store(cfg) == "host":
        # JAX's tracing caches keep the tree structure of the arguments
        # they traced, and a host store rides the state's as static data
        jax.clear_caches()
    gc.collect()

    t = time.perf_counter()
    correct, checks, run.check = check_answers(cfg, coll.x, coll.pool, win,
                                               seed)
    log(f"[reference] {run.check.checked} answers checked in "
        f"{time.perf_counter() - t:.3f}s")
    metrics = {}
    for m in spec.metrics(cell_name, trace):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(np.sum(win.in_window)),
           "failed": checks["failed"]["value"], "metrics": metrics,
           "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced.device_ops(10),
                            "idle_gaps": reduced.idle_gaps(10)}
    out["checks"] = checks
    return out


def use_checkout_cache() -> None:
    """Keep JAX's persistent compilation cache in ``<checkout>/.jax_cache``
    (the program's ``runtime.configure()`` takes it from the environment),
    whatever the environment held, and cache every program, however fast
    it compiled, so that only a checkout's first run compiles. Call before
    JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    spec = Spec()
    cell = spec.cell(args.workload)
    use_checkout_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s). Nothing run.")
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils import runtime
    log(f"[device] {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{runtime.configure()}")
    out = run_cell(spec, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    for name, c in out["checks"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
