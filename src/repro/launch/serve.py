"""Serving driver: batched vector-search service (Algorithm 1) over a
synthetic collection with selectable scoring mode, index and placement.

    PYTHONPATH=src python -m repro.launch.serve --mode gleanvec --n 50000
    PYTHONPATH=src python -m repro.launch.serve --mode gleanvec-int8 \
        --index ivf --nprobe 12 --reduced-probe
    PYTHONPATH=src python -m repro.launch.serve --mode gleanvec \
        --index ivf --shards 4
    PYTHONPATH=src python -m repro.launch.serve --mode gleanvec-int8 \
        --stream --cycles 4

The three axes are orthogonal: every scorer mode (full / sphering /
gleanvec / sphering-int8 / gleanvec-int8 / gleanvec-sorted /
gleanvec-int8-sorted) x every index (flat scan / IVF / graph) x placement
(single device, or --shards N per-shard sub-indexes merged through the
ShardedIndex wrapper) runs through the same SearchArtifacts + Scorer +
Index protocol path -- the flags are the only thing that differs between a
full-precision flat service and a sharded cluster-contiguous GleanVec+int8
IVF one. ``--reduced-probe`` projects the IVF coarse centers into the
scorer's reduced space so the probe consumes the prepared queries (R^d).
``--fused-graph`` (sorted modes) binds the graph's edge lists to the
tag-sorted layout so every hop runs the gather-free fused beam-step kernel;
``--graph-build device`` constructs the graph on the accelerator
(CAGRA-style fused self-join) instead of numpy NN-descent.

``--stream`` drives the Section 3.2 lifecycle under live traffic: the
engine keeps serving drifted (OOD) queries while each cycle observes them
into K_Q, inserts new database rows into the fixed-capacity store, and
swaps the Eq. 11-12 refreshed state in -- zero recompiles after warmup,
asserted by the engine's compile counter. The stream loop runs through
the fault-tolerant lifecycle layer: every swap is GUARDED (non-finite
scan + version monotonicity + canary top-k overlap, `serve/lifecycle.py`)
and every refresh SUPERVISED (retry/backoff, stored->full escalation,
graceful degradation). ``--snapshot-dir`` persists the
ServingState + StreamingState pair each cycle; ``--restore`` resumes a
killed process from the newest restorable snapshot -- template model, NO
refit -- and continues the refresh cadence; ``--inject-fault <kind>``
drills one full fail -> degrade -> recover -> swap cycle end-to-end
(exits non-zero if the stack mishandles it).

``--frontend`` runs the ASYNC serving topology (`serve/frontend.py`) --
the ``--stream`` loop's observe/refresh/swap lifecycle moved off-thread,
with concurrent clients admitted through a bounded coalescing queue::

    clients ----> enqueue(query, deadline) ---------+   Rejected(queue-full
       |              |                             |   / deadline) -> client
       |       [bounded admission queue]            |
       |              | drain: shed expired -------+   Rejected(shed)
       |        [pad to static bucket shape]
       |              v
       |      dispatcher: search_with(state)  <- atomic state read
       |              |        ^
       |   slice per-request   | GuardedEngine.swap (validated)
       v              v        |
    futures <- ids  RefreshWorker thread: observe -> refresh (supervised:
                    retry/backoff -> escalate -> degrade -> recover)

Serving never blocks on a refresh; a stuck/crashed worker leaves the
stale-but-valid state answering (staleness grows, the alertable signal).
``--frontend --inject-fault {stuck-worker, slow-refresh, poison-burst,
queue-overflow}`` drills exactly those overload/concurrency faults,
asserting the frontend keeps answering within SLO or sheds predictably
(exits non-zero otherwise).
"""
from __future__ import annotations

import argparse
import contextlib
import tempfile
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gleanvec as gv, leanvec_sphering as lvs, metrics
from repro.core import search as msearch
from repro.core import streaming
from repro.core.scorer import MODES
from repro.data import vectors
from repro.index import distributed, graph, ivf
from repro.index.protocol import replace
from repro.serve import faults, frontend, lifecycle
from repro.serve.engine import ServingEngine
from repro.train import checkpoint
from repro.utils import runtime


def build_index(args, X, scorer, model):
    """The --index axis: an Index-protocol object (or None = flat scan)."""
    if args.index == "flat":
        return None
    if args.index == "ivf":
        if args.aligned:
            if not args.mode.endswith("-sorted"):
                raise SystemExit("--aligned needs a sorted scorer mode "
                                 "(gleanvec-sorted / gleanvec-int8-sorted)")
            idx = ivf.build_aligned(model, X, nprobe=args.nprobe)
        else:
            idx = ivf.build(jax.random.PRNGKey(1), X, n_lists=args.lists,
                            nprobe=args.nprobe)
        if args.reduced_probe:
            idx = ivf.with_reduced_centers(idx, scorer, model)
        return idx
    if args.index == "graph":
        idx = replace(graph.build(np.asarray(X), r=args.graph_degree,
                                  n_iters=4, seed=0,
                                  method=args.graph_build),
                      beam=args.beam, max_hops=args.max_hops,
                      expand=args.expand)
        if args.fused_graph:
            if not args.mode.endswith("-sorted"):
                raise SystemExit("--fused-graph needs a sorted scorer mode "
                                 "(gleanvec-sorted / gleanvec-int8-sorted)")
            idx = graph.with_fused_scan(idx, scorer)
        return idx
    raise ValueError(f"unknown index {args.index!r}")


def _stream_model(args, q_init, X, n0, template: bool):
    """The stream's DR model: a real fit, or (restore path) a structural
    template -- same classes/treedef, placeholder weights, NO refit."""
    if template:
        return lifecycle.template_model(args.mode, args.dim, args.d,
                                        clusters=args.clusters)
    if args.mode.startswith("sphering"):
        return lvs.fit(jnp.asarray(q_init), X[:n0], args.d)
    return gv.fit(jax.random.PRNGKey(0), jnp.asarray(q_init), X[:n0],
                  c=args.clusters, d=args.d)


def _drill_fail(msg):
    print(f"  drill FAIL: {msg}")
    raise SystemExit(1)


def _fault_drill(kind, guarded, supervisor, stream, obs, snap_dir):
    """Inject one ``--inject-fault`` kind mid-stream and verify the stack
    handles it. Immediate kinds (rejected swaps, snapshot fallback, query
    hardening) are checked here; deferred kinds (poisoned moments, a
    refresh exception) hand back a poisoned stream / failing refresh_fn
    plus a check to run after the cycle's supervised refresh. Returns
    ``(stream, refresh_fn, deferred_check)``; any mishandling exits 1."""
    eng = guarded.engine
    print(f"  -- injecting fault: {kind}")
    if kind == "nan-moments":
        def check(rep):
            if rep.outcome != "degraded":
                _drill_fail("poisoned moments were not degraded "
                            f"(outcome={rep.outcome})")
            if lifecycle.nonfinite_leaves(eng.state):
                _drill_fail("engine is serving non-finite state")
            print(f"  drill: refresh degraded after {rep.attempts} attempts "
                  "(still serving last-known-good) -> recovering")
        return faults.nan_moments(stream), streaming.refresh, check
    if kind == "refresh-exception":
        fn = faults.failing(streaming.refresh, n_failures=1)

        def check(rep):
            if rep.outcome != "ok" or rep.attempts < 2:
                _drill_fail("retry did not absorb the injected exception "
                            f"(outcome={rep.outcome} attempts={rep.attempts})")
            print(f"  drill PASS: refresh-exception absorbed on attempt "
                  f"{rep.attempts} (escalated={rep.escalated})")
        return stream, fn, check
    # immediate kinds: verified against a pre-fault result set
    before = guarded.submit(obs)
    if kind in ("corrupt-scorer", "scramble-scorer"):
        bad = (faults.corrupt_scorer_leaf if kind == "corrupt-scorer"
               else faults.scramble_scorer_leaf)(eng.state)
        want = "non-finite" if kind == "corrupt-scorer" else "canary-overlap"
        v0, s0 = guarded.version, eng.n_swaps
        try:
            guarded.swap(bad)
            _drill_fail("corrupted state was accepted")
        except lifecycle.SwapRejected as e:
            if e.reason != want:
                _drill_fail(f"rejected for {e.reason!r}, expected {want!r}")
        if (guarded.version, eng.n_swaps) != (v0, s0):
            _drill_fail("rejected swap mutated the engine")
        if not np.array_equal(guarded.submit(obs), before):
            _drill_fail("results changed across a rejected swap")
        print(f"  drill PASS: {kind} rejected ({want}), "
              "results bit-identical")
    elif kind == "truncated-snapshot":
        d = snap_dir or tempfile.mkdtemp(prefix="snap-drill-")
        lifecycle.snapshot(d, eng.state, stream, meta={"drill": 0})
        lifecycle.snapshot(d, eng.state, stream, meta={"drill": 1})
        steps = checkpoint.available_steps(d)
        faults.truncate_snapshot(d, what="manifest")
        serving, _, got, meta = lifecycle.restore(d, eng.state, stream)
        if got != steps[-2] or meta.get("drill") != 0:
            _drill_fail(f"restore did not fall back (got step {got})")
        lifecycle.restore_into(guarded, serving)
        if not np.array_equal(guarded.submit(obs), before):
            _drill_fail("restored state is not bit-identical")
        print(f"  drill PASS: truncated step {steps[-1]} fell back to "
              f"step {got}, restored results bit-identical")
    elif kind == "poison-queries":
        res = guarded.submit(faults.poison_queries(obs))
        if not (res[0] == -1).all():
            _drill_fail("poisoned row returned fabricated ids")
        if not np.array_equal(res[1:], before[1:]):
            _drill_fail("poisoned row contaminated its batch")
        print("  drill PASS: poisoned row sanitized to -1, "
              "batch uncontaminated")
    elif kind == "wrong-dim-queries":
        try:
            guarded.submit(faults.wrong_dim_queries(obs))
            _drill_fail("wrong-dimensionality batch was accepted")
        except ValueError as e:
            print(f"  drill PASS: wrong-dim batch refused ({e})")
    else:
        raise SystemExit(f"unknown fault kind {kind!r}")
    return stream, streaming.refresh, None


def run_stream(args):
    """Section 3.2 lifecycle under live traffic: serve drifted queries,
    observe them into K_Q, insert rows, refresh, hot-swap -- one compiled
    executable throughout, every swap guarded and every refresh
    supervised (see module docstring)."""
    n0 = int(args.n * 0.7)
    step = (args.n - n0) // args.cycles
    ds = vectors.make_dataset("serve-stream", n=args.n, d=args.dim,
                              n_queries=max(512, args.batch * args.cycles),
                              ood=True, seed=args.seed)
    X = jnp.asarray(ds.database)
    QT = np.asarray(ds.queries_test)
    rng = np.random.default_rng(0)
    # the model serving at t=0 was fit on ID (database-like) queries; the
    # live traffic below is OOD -- the drift the refreshes adapt to
    q_init = np.asarray(X)[rng.integers(0, n0, 1024)] \
        + 0.1 * rng.standard_normal((1024, args.dim)).astype(np.float32)
    restoring = False
    if args.restore:
        if not args.snapshot_dir:
            raise SystemExit("--restore needs --snapshot-dir")
        restoring = bool(checkpoint.available_steps(args.snapshot_dir))
        if not restoring:
            print(f"no snapshots under {args.snapshot_dir}; cold start")
    model = _stream_model(args, q_init, X, n0, template=restoring)
    artifacts = streaming.build_streaming_artifacts(
        args.mode, X[:n0], model, capacity=args.n, sort_block=256,
        slack_blocks=2, host_rerank=args.host_rerank)
    index = None
    if args.index == "graph":
        index = replace(graph.build(np.asarray(X[:n0]), r=args.graph_degree,
                                    n_iters=4, seed=0,
                                    method=args.graph_build),
                        beam=args.beam, max_hops=args.max_hops,
                        expand=args.expand)
        # pre-allocate edge rows for every future insert (shape-preserving
        # growth, like IVF's list slack)
        index = graph.with_capacity(index, args.n)
        if args.fused_graph:
            if not args.mode.endswith("-sorted"):
                raise SystemExit("--fused-graph needs a sorted scorer mode")
            index = graph.with_fused_scan(index, artifacts.scorer)
    elif args.index == "ivf":
        if args.aligned:
            if not args.mode.endswith("-sorted"):
                raise SystemExit("--aligned needs a sorted scorer mode")
            index = ivf.build_aligned(model, X[:n0], nprobe=args.nprobe)
        else:
            index = ivf.build(jax.random.PRNGKey(1), X[:n0],
                              n_lists=args.lists, nprobe=args.nprobe)
        # slack is per list: expected fill + 4x skew headroom, NOT the
        # total insert count (that would inflate every probe's gather);
        # sized from the BUILT index's list count (--aligned has
        # model.n_clusters lists, not --lists)
        slack = 4 * max(1, (args.n - n0) // index.n_lists)
        index = ivf.with_list_slack(index, slack)
        if args.reduced_probe:
            index = ivf.with_reduced_centers(index, artifacts.scorer, model)
    serving = msearch.make_state(artifacts, index=index)
    stream, cycle0 = None, 0
    if restoring:
        # templates above supplied STRUCTURE; leaves come from the snapshot
        serving, stream, snap_step, meta = lifecycle.restore(
            args.snapshot_dir, serving,
            lifecycle.template_stream(model, refresh_every=step))
        cycle0 = int(meta.get("cycle", -1)) + 1
        print(f"restored snapshot step {snap_step} -> resuming at cycle "
              f"{cycle0} (version {int(np.asarray(serving.version))}, "
              "no refit)")
    engine = ServingEngine(serving, k=10, kappa=args.kappa,
                           batch_size=args.batch, dim=args.dim)
    guarded = lifecycle.GuardedEngine(engine, canary_queries=QT[:args.batch],
                                      min_overlap=args.min_overlap)
    supervisor = lifecycle.RefreshSupervisor(guarded)
    if stream is None:
        stream = streaming.init_from_artifacts(artifacts, q_init,
                                               refresh_every=step)
    print(f"stream mode={args.mode} index={args.index} n0={n0} "
          f"capacity={args.n} D={args.dim} d={args.d} "
          f"cycles={args.cycles} inserts/cycle={step} "
          f"guard(min_overlap={args.min_overlap})")
    drill_cycle = -1
    if args.inject_fault:
        if args.inject_fault == "nan-moments" and args.cycles - cycle0 < 2:
            raise SystemExit("--inject-fault nan-moments needs >= 2 cycles "
                             "(degrade, then the recovered swap)")
        drill_cycle = max(cycle0, min(args.cycles // 2, args.cycles - 2))
    for cycle in range(cycle0, args.cycles):
        obs = QT[(cycle * args.batch) % len(QT):][:args.batch]
        refresh_fn, deferred = streaming.refresh, None
        if cycle == drill_cycle:
            stream, refresh_fn, deferred = _fault_drill(
                args.inject_fault, guarded, supervisor, stream, obs,
                args.snapshot_dir)
        live_idx = np.nonzero(streaming.live_mask(guarded.state.artifacts))[0]
        served = guarded.submit(obs)          # live traffic keeps flowing
        supervisor.note_queries(obs)
        gt = live_idx[vectors.exact_topk(
            obs, np.asarray(guarded.state.artifacts.x_full)[live_idx], 10)]
        rec = float(metrics.recall_at_k(jnp.asarray(served),
                                        jnp.asarray(gt)))
        stream = streaming.observe_queries(stream, jnp.asarray(obs))
        # the next unconsumed slice of X -- indexed off the LIVE count, not
        # the cycle number, so a restored run (possibly with a different
        # --cycles) continues exactly where the snapshot's store left off
        rows = X[live_idx.size: min(live_idx.size + step, args.n)]
        if rows.shape[0]:
            arts2, new_ids = streaming.insert_rows(guarded.state.artifacts,
                                                   rows)
            stream = streaming.insert(stream, rows)
            state2 = guarded.state._replace(artifacts=arts2)
            if index is not None:
                if args.index == "graph":
                    # connect the new rows: beam-search-for-neighbors +
                    # reverse-edge fill (full-D distances via the rerank
                    # tier, host or device)
                    state2 = state2._replace(index=graph.insert_ids(
                        state2.index, rows, np.asarray(new_ids),
                        arts2.scorer, arts2.x_full))
                else:
                    state2 = state2._replace(
                        index=ivf.insert_ids(state2.index, rows, new_ids))
            guarded.swap(state2)
        stream, rep = supervisor.refresh_and_swap(
            stream, source=args.refresh_source, refresh_fn=refresh_fn)
        if deferred is not None:
            deferred(rep)
        if rep.outcome == "degraded":
            # keep serving stale-but-valid; rebuild the moments from the
            # last-known-good store + retained queries for the next cycle
            stream = supervisor.recover(stream)
        bad = lifecycle.nonfinite_leaves(guarded.state)
        if bad:
            raise SystemExit(f"SERVE INVARIANT VIOLATED: non-finite leaves "
                             f"in served state: {bad[:4]}")
        print(f"  cycle {cycle}: served {served.shape[0]} queries "
              f"recall@10={rec:.3f} live_rows="
              f"{int(streaming.live_mask(guarded.state.artifacts).sum())} "
              f"version={guarded.version} compiles={guarded.n_compiles} "
              f"refresh={rep.outcome}/{rep.source} "
              f"swap_p50={np.median(engine.stats.swap_ms):.2f}ms")
        if args.snapshot_dir:
            lifecycle.snapshot(args.snapshot_dir, guarded.state, stream,
                               meta={"cycle": cycle})
    if args.inject_fault == "nan-moments":
        if supervisor.n_degraded < 1 or supervisor.n_recoveries < 1:
            _drill_fail("degrade/recover cycle did not complete")
        if supervisor.reports[-1].outcome != "ok":
            _drill_fail("post-recovery refresh did not swap")
        print("  drill PASS: nan-moments -> degraded -> recovered -> "
              "swapped")
    s = engine.stats
    h = supervisor
    print(f"QPS={s.qps:.0f} p50={s.percentile_ms(50):.1f}ms "
          f"p99={s.percentile_ms(99):.1f}ms "
          f"swaps={engine.n_swaps} compiles={engine.n_compiles} "
          f"(zero recompiles after warmup: "
          f"{engine.n_compiles in (None, 1)})")
    print(f"guard: accepted={guarded.health.accepted} "
          f"rejected={guarded.health.rejected} "
          f"rollbacks={guarded.health.rollbacks} "
          f"last_overlap={guarded.health.last_overlap:.3f} | "
          f"supervisor: refreshes={h.n_refreshes} retries={h.n_retries} "
          f"escalations={h.n_escalations} degraded={h.n_degraded} "
          f"recoveries={h.n_recoveries}")


def _frontend_traffic(fe, queries, n_clients=4, deadline_ms=None,
                      timeout_s=60.0):
    """Fire ``queries`` at the frontend from ``n_clients`` concurrent
    client threads. Returns ``(results {row -> (k,) ids}, rejected
    {row -> reason})`` -- every offered request is accounted for, served
    or loudly refused."""
    results, rejected = {}, {}
    lock = threading.Lock()

    def client(rows):
        for i in rows:
            try:
                ids = fe.enqueue(queries[i],
                                 deadline_ms=deadline_ms).result(timeout_s)
                with lock:
                    results[i] = ids
            except frontend.Rejected as e:
                with lock:
                    rejected[i] = e.reason

    threads = [threading.Thread(target=client,
                                args=(range(c, len(queries), n_clients),))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results, rejected


def _await(cond, timeout_s=30.0, poll_s=0.01):
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout_s:
            return False
        time.sleep(poll_s)
    return True


def _frontend_drill(args, fe, guarded, worker, release, refresh_fn, QT):
    """One ``--frontend --inject-fault`` overload/concurrency drill; any
    mishandling exits 1 through ``_drill_fail``."""
    kind = args.inject_fault
    eng = guarded.engine
    print(f"  -- injecting fault: {kind}")
    if kind == "poison-burst":
        burst = faults.burst_overflow(args.dim, args.batch * 4, seed=1,
                                      poison_frac=0.25)
        bad = ~np.isfinite(burst).all(axis=1)
        res, rej = _frontend_traffic(fe, burst)
        if rej:
            _drill_fail(f"in-capacity burst was rejected: {rej}")
        got = np.stack([res[i] for i in range(len(burst))])
        if not (got[bad] == -1).all():
            _drill_fail("poisoned rows returned fabricated ids")
        ref = eng.submit(burst)      # same sanitize gate, unbatched path
        if not np.array_equal(got, ref):
            _drill_fail("burst results diverge from direct submit")
        print(f"  drill PASS: {int(bad.sum())}/{len(burst)} poisoned rows "
              "-> -1, clean rows bit-identical to submit")
    elif kind == "queue-overflow":
        cap = 8
        fe_q = frontend.ServingFrontend(guarded, capacity=cap, start=False,
                                        warmup=False)
        burst = faults.burst_overflow(args.dim, cap + args.batch, seed=2)
        admitted, n_rej = [], 0
        for q in burst:              # no dispatcher: the queue must fill
            try:
                admitted.append(fe_q.enqueue(q))
            except frontend.Rejected as e:
                if e.reason != "queue-full":
                    _drill_fail(f"overflow rejected as {e.reason!r}")
                n_rej += 1
        if n_rej != len(burst) - cap:
            _drill_fail(f"admitted {len(admitted)}/{len(burst)} past "
                        f"capacity {cap}")
        if eng.stats.n_rejected < n_rej:
            _drill_fail("rejections not counted in ServeStats")
        while fe_q.queue_depth:
            fe_q.drain_once()
        if any((f.result(5)).shape != (eng.k,) for f in admitted):
            _drill_fail("admitted requests did not resolve after overflow")
        print(f"  drill PASS: {n_rej} overflow requests rejected loudly, "
              f"all {cap} admitted requests served")
    elif kind == "slow-refresh":
        n0 = worker.n_cycles
        worker.observe(QT[:args.batch])
        worker.request_refresh()
        # serving must proceed WHILE the slowed refresh runs
        res, rej = _frontend_traffic(fe, QT[:args.batch * 2])
        if len(res) + len(rej) != args.batch * 2:
            _drill_fail("requests lost during slow refresh")
        if not _await(lambda: worker.n_cycles > n0):
            _drill_fail("slowed refresh never completed")
        if refresh_fn.calls < 1:
            _drill_fail("slow_refresh injector never ran")
        print(f"  drill PASS: served {len(res)} requests during a "
              f"{refresh_fn.delay_s * 1e3:.0f}ms-delayed refresh "
              f"(staleness peaked, then swap landed)")
    elif kind == "stuck-worker":
        v0 = guarded.version
        worker.observe(QT[:args.batch])
        worker.request_refresh()
        if not _await(lambda: refresh_fn.calls >= 1):
            _drill_fail("stuck refresh never entered")
        time.sleep(0.05)
        if not worker.stuck(0.02):
            _drill_fail("watchdog did not flag the stuck worker")
        # the frontend must keep answering on the stale-but-valid state
        res, rej = _frontend_traffic(fe, QT[:args.batch * 2])
        if len(res) != args.batch * 2 or rej:
            _drill_fail("requests failed while the worker was stuck")
        if guarded.version != v0:
            _drill_fail("version moved while the refresh was stuck")
        release.set()
        if not _await(lambda: guarded.version > v0):
            _drill_fail("released worker never swapped")
        print(f"  drill PASS: {len(res)} requests served on the stale "
              f"state while stuck; release -> swap (version {v0} -> "
              f"{guarded.version})")
    else:
        raise SystemExit(f"unknown frontend fault kind {kind!r}")


def run_frontend(args):
    """Async serving topology: bounded-queue coalescing frontend over a
    guarded engine, refresh lifecycle on a supervised background worker,
    mixed ID/OOD traffic from concurrent clients (see module docstring
    diagram)."""
    ds = vectors.make_dataset("serve-frontend", n=args.n, d=args.dim,
                              n_queries=max(512, args.batch * 8), ood=True,
                              seed=args.seed)
    X = jnp.asarray(ds.database)
    QT = np.asarray(ds.queries_test)              # OOD (drifted) traffic
    rng = np.random.default_rng(0)
    q_id = np.asarray(X)[rng.integers(0, args.n, 1024)] \
        + 0.1 * rng.standard_normal((1024, args.dim)).astype(np.float32)
    model = _stream_model(args, q_id, X, args.n, template=False)
    artifacts = streaming.build_streaming_artifacts(
        args.mode, X, model, capacity=args.n, sort_block=256,
        slack_blocks=2, host_rerank=args.host_rerank)
    engine = ServingEngine(msearch.make_state(artifacts), k=10,
                           kappa=args.kappa, batch_size=args.batch,
                           dim=args.dim)
    guarded = lifecycle.GuardedEngine(engine, canary_queries=QT[:args.batch],
                                      min_overlap=args.min_overlap)
    supervisor = lifecycle.RefreshSupervisor(guarded)
    stream = streaming.init_from_artifacts(artifacts, q_id,
                                           refresh_every=args.batch)
    release, refresh_fn = None, streaming.refresh
    if args.inject_fault == "slow-refresh":
        refresh_fn = faults.slow_refresh(delay_s=0.25)
    elif args.inject_fault == "stuck-worker":
        release = threading.Event()
        refresh_fn = faults.stuck_worker(release, timeout_s=60.0)
    worker = frontend.RefreshWorker(supervisor, stream,
                                    source=args.refresh_source,
                                    refresh_fn=refresh_fn).start()
    fe = frontend.ServingFrontend(guarded, capacity=args.queue_capacity,
                                  default_deadline_ms=args.deadline_ms)
    compiles0 = engine.n_compiles
    print(f"frontend mode={args.mode} n={args.n} D={args.dim} d={args.d} "
          f"buckets={fe.buckets} capacity={args.queue_capacity} "
          f"deadline={args.deadline_ms}ms slo={args.slo_ms}ms "
          f"compiles(warm)={compiles0}")

    # warm wave: mixed ID/OOD traffic with a background refresh mid-wave
    mixed = np.empty((args.batch * 4, args.dim), np.float32)
    mixed[0::2] = q_id[: args.batch * 2]
    mixed[1::2] = QT[: args.batch * 2]
    worker.observe(mixed[: args.batch])
    if args.inject_fault not in ("stuck-worker", "slow-refresh"):
        worker.request_refresh()
    res, rej = _frontend_traffic(fe, mixed,
                                 deadline_ms=args.deadline_ms)
    if len(res) + len(rej) != len(mixed):
        raise SystemExit("TRAFFIC INVARIANT VIOLATED: requests lost "
                         f"({len(res)} served + {len(rej)} refused "
                         f"!= {len(mixed)} offered)")
    if args.inject_fault not in ("stuck-worker", "slow-refresh"):
        if not _await(lambda: worker.n_cycles >= 1):
            raise SystemExit("background refresh never completed")

    if args.inject_fault:
        _frontend_drill(args, fe, guarded, worker, release, refresh_fn, QT)

    # end-state invariants: ALWAYS a valid serving state, zero recompiles
    bad = lifecycle.nonfinite_leaves(guarded.state)
    if bad:
        raise SystemExit(f"SERVE INVARIANT VIOLATED: non-finite leaves "
                         f"in served state: {bad[:4]}")
    final = guarded.submit(QT[: args.batch])
    if final.shape != (args.batch, engine.k):
        raise SystemExit("engine not serving after the run")
    if engine.n_compiles != compiles0:
        raise SystemExit(f"RECOMPILED while serving: {compiles0} -> "
                         f"{engine.n_compiles} executables")
    fe.close()
    stopped = worker.stop(timeout=1.0)
    s = engine.stats
    print(f"QPS={s.qps:.0f} request_p50={s.request_percentile_ms(50):.1f}ms "
          f"request_p99={s.request_percentile_ms(99):.1f}ms "
          f"(slo={args.slo_ms}ms) shed_rate={s.shed_rate:.3f} "
          f"rejected={s.n_rejected} shed={s.n_shed} "
          f"deadline_miss={s.n_deadline_miss} sanitized={s.n_sanitized}")
    print(f"worker: cycles={worker.n_cycles} degraded={worker.degraded} "
          f"staleness={worker.staleness_s:.2f}s stopped={stopped} | "
          f"swaps={engine.n_swaps} compiles={engine.n_compiles} "
          f"(zero recompiles after warmup: True)")


# The DR model is fit on at most this many database rows, drawn uniformly
# (the paper fits its k-means on a 1e5-row sample): the fit's (rows, D)
# temporaries then stay bounded at deployment scale.
FIT_ROWS = 1 << 18


class SearchRun(NamedTuple):
    """What :func:`run_search` built and measured: the warmed engine over
    the served state, the dataset and model it came from, recall@10 of
    ``engine.submit`` over the test queries, and the host-clock seconds of
    the build (data excluded) and of the engine's compile + warmup."""

    engine: ServingEngine
    ds: vectors.VectorDataset
    model: object
    ids: np.ndarray
    recall: float
    build_s: float
    compile_s: float


def make_data(args) -> vectors.VectorDataset:
    """The served collection: generated from ``--seed``, OOD queries."""
    return vectors.make_dataset("serve", n=args.n, d=args.dim,
                                n_queries=512, ood=True, seed=args.seed)


def fit_model(args, ds: vectors.VectorDataset):
    """The ``--mode``'s DR model (None for ``full``), fit on the learning
    queries and at most :data:`FIT_ROWS` uniformly drawn database rows."""
    if args.mode == "full":
        return None
    rows = ds.database
    if rows.shape[0] > FIT_ROWS:
        pick = np.random.default_rng(args.seed).choice(
            rows.shape[0], FIT_ROWS, replace=False)
        rows = rows[np.sort(pick)]
    X, Q = jnp.asarray(rows), jnp.asarray(ds.queries_learn)
    if args.mode.startswith("sphering"):
        return lvs.fit(Q, X, args.d)
    return gv.fit(jax.random.PRNGKey(0), Q, X, c=args.clusters, d=args.d)


def build_state(args, X, model) -> msearch.ServingState:
    """Artifacts + traversal for ``args`` over the database ``X``."""
    X = jnp.asarray(X)
    if args.shards:
        # the stacked per-shard scorer IS the serving scorer -- don't also
        # encode the whole database into a global one just to discard it
        index, stacked = distributed.build_sharded_index(
            args.index, args.mode, X, model, n_shards=args.shards,
            key=jax.random.PRNGKey(1), n_lists=args.lists,
            nprobe=args.nprobe, reduced_probe=args.reduced_probe,
            aligned=args.aligned, beam=args.beam, max_hops=args.max_hops,
            expand=args.expand, fused_graph=args.fused_graph,
            graph_kwargs={"r": args.graph_degree, "n_iters": 4, "seed": 0,
                          "method": args.graph_build})
        artifacts = msearch.SearchArtifacts(scorer=stacked, x_full=X,
                                            model=model)
        if args.host_rerank:
            # spill-to-host: per-shard rerank tiers demote to host buffers
            artifacts = msearch.demote_rerank_tier(artifacts,
                                                   shards=args.shards)
    else:
        artifacts = msearch.build_artifacts(args.mode, X, model)
        index = build_index(args, X, artifacts.scorer, model)
        if args.host_rerank:
            artifacts = msearch.demote_rerank_tier(artifacts)
    return msearch.make_state(artifacts, index=index)


def run_search(args, ds=None, model=None) -> SearchRun:
    """The one-shot serving run ``main`` performs: data (``ds`` when
    given), model (``model`` when given), served state, a warmed
    :class:`ServingEngine`, and recall@10 of the test queries against the
    exact ground truth."""
    ds = make_data(args) if ds is None else ds
    t0 = time.perf_counter()
    if model is None:
        model = fit_model(args, ds)
    state = build_state(args, ds.database, model)
    jax.block_until_ready(state)
    t1 = time.perf_counter()
    kappa = 10 if args.mode == "full" else args.kappa
    engine = ServingEngine(state, k=10, kappa=kappa, batch_size=args.batch,
                           dim=args.dim)
    t2 = time.perf_counter()
    ids = engine.submit(ds.queries_test)
    rec = metrics.recall_at_k(jnp.asarray(ids), jnp.asarray(ds.gt[:, :10]))
    return SearchRun(engine=engine, ds=ds, model=model, ids=np.asarray(ids),
                     recall=float(rec), build_s=t1 - t0, compile_s=t2 - t1)


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="gleanvec", choices=list(MODES))
    ap.add_argument("--index", default="flat",
                    choices=["flat", "ivf", "graph"])
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--clusters", type=int, default=48)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--kappa", type=int, default=50)
    ap.add_argument("--lists", type=int, default=64)
    ap.add_argument("--nprobe", type=int, default=12)
    ap.add_argument("--reduced-probe", action="store_true",
                    help="IVF coarse probe in the scorer's reduced space")
    ap.add_argument("--aligned", action="store_true",
                    help="IVF coarse quantizer = the GleanVec clustering "
                         "(sorted modes: gather-free range-scan fine step)")
    ap.add_argument("--beam", type=int, default=96)
    ap.add_argument("--max-hops", type=int, default=200)
    ap.add_argument("--expand", type=int, default=1,
                    help="graph frontier vertices expanded per hop "
                         "(multi-expansion beam search; 1 = classic)")
    ap.add_argument("--graph-degree", type=int, default=24)
    ap.add_argument("--graph-build", default="numpy",
                    choices=["numpy", "device", "auto"],
                    help="graph construction: numpy NN-descent, on-device "
                         "CAGRA-style self-join, or auto (device at large n)")
    ap.add_argument("--fused-graph", action="store_true",
                    help="sorted modes: bind the graph to the tag-sorted "
                         "layout (graph.with_fused_scan) so every hop runs "
                         "the gather-free fused beam-step kernel")
    ap.add_argument("--shards", type=int, default=0,
                    help="N per-shard sub-indexes merged via ShardedIndex "
                         "(0 = single index)")
    ap.add_argument("--host-rerank", action="store_true",
                    help="two-level memory hierarchy: demote the (n, D) "
                         "full-precision rerank tier to host memory (only "
                         "the kappa candidate rows per query cross "
                         "host->device); with --shards, each shard's tier "
                         "spills to its own host buffer")
    ap.add_argument("--stream", action="store_true",
                    help="drive the Section 3.2 observe -> insert -> "
                         "refresh -> swap lifecycle under live traffic")
    ap.add_argument("--frontend", action="store_true",
                    help="async serving topology: bounded-queue coalescing "
                         "frontend + supervised background refresh worker "
                         "(serve/frontend.py; see module docstring diagram)")
    ap.add_argument("--queue-capacity", type=int, default=256,
                    help="--frontend: admission-queue bound; a full queue "
                         "REJECTS new requests (backpressure, not a drop)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="--frontend: per-request latency budget; "
                         "unmeetable budgets are rejected at enqueue, "
                         "expired ones shed at dispatch (default: none)")
    ap.add_argument("--slo-ms", type=float, default=250.0,
                    help="--frontend: declared SLO the request p50/p99 "
                         "summary is reported against")
    ap.add_argument("--cycles", type=int, default=3,
                    help="streaming refresh cycles (--stream)")
    ap.add_argument("--refresh-source", default="stored",
                    choices=["stored", "full"],
                    help="refresh via Eq. 12 over stored vectors or exact "
                         "re-encode from the rerank store")
    ap.add_argument("--snapshot-dir", default=None,
                    help="--stream: persist ServingState + StreamingState "
                         "here after every cycle (atomic manifest steps)")
    ap.add_argument("--restore", action="store_true",
                    help="--stream: resume from the newest restorable "
                         "snapshot in --snapshot-dir (template model, no "
                         "refit); corrupted steps fall back to older ones")
    ap.add_argument("--min-overlap", type=float, default=0.3,
                    help="guarded-swap canary: reject a candidate whose "
                         "pinned-battery top-k overlap drops below this "
                         "(0 disables the canary)")
    ap.add_argument("--inject-fault", default=None,
                    choices=list(faults.FAULTS) + list(faults.FRONTEND_FAULTS),
                    help="drill one fault kind and verify the stack "
                         "handles it (exits non-zero on mishandling). "
                         "Lifecycle kinds need --stream; concurrency kinds "
                         "(stuck-worker / slow-refresh / poison-burst / "
                         "queue-overflow) need --frontend")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated collection (and of the fit "
                         "sample of a search run)")
    ap.add_argument("--trace-dir", default=None,
                    help="write a profiler trace of the run to this "
                         "directory (off by default): the serve.* host "
                         "spans and the search.* scopes of the step")
    return ap


def main(argv=None):
    args = parser().parse_args(argv)
    runtime.configure()
    with (jax.profiler.trace(args.trace_dir) if args.trace_dir
          else contextlib.nullcontext()):
        _run(args)


def _run(args):
    """The run ``args`` ask for: the frontend, the streaming lifecycle, or
    one search run."""
    if args.inject_fault in faults.FRONTEND_FAULTS and not args.frontend:
        raise SystemExit(f"--inject-fault {args.inject_fault} is a "
                         "concurrency drill: it needs --frontend")
    if args.inject_fault in faults.FAULTS and not args.stream:
        raise SystemExit(f"--inject-fault {args.inject_fault} is a "
                         "lifecycle drill: it needs --stream")
    if args.frontend:
        if args.stream:
            raise SystemExit("--frontend IS the async stream topology; "
                             "drop --stream")
        if args.mode == "full" or args.shards:
            raise SystemExit("--frontend needs a DR mode and a "
                             "single-device index")
        if args.index != "flat":
            raise SystemExit("--frontend serves the flat streaming store "
                             "(index slack/insert rides --stream)")
        run_frontend(args)
        return
    if args.stream:
        if args.mode == "full" or args.shards:
            raise SystemExit("--stream needs a DR mode and a "
                             "single-device index")
        run_stream(args)
        return
    if args.snapshot_dir or args.restore or args.inject_fault:
        raise SystemExit("--snapshot-dir/--restore/--inject-fault are "
                         "lifecycle flags: they need --stream")

    run = run_search(args)
    s = run.engine.stats
    placement = f"shards={args.shards}" if args.shards else "single"
    print(f"mode={args.mode} index={args.index} {placement} "
          f"n={args.n} D={args.dim} d={args.d} "
          f"reduced_probe={args.reduced_probe}")
    print(f"QPS={s.qps:.0f} p50={s.percentile_ms(50):.1f}ms "
          f"p99={s.percentile_ms(99):.1f}ms recall@10={run.recall:.3f}")


if __name__ == "__main__":
    main()
