"""Smoke run of the served vector-search path on a TPU.

    python chip_smoke.py        # one chip: kernels, flat, ivf, graph

Every phase builds its collection and index through the functions
``python -m repro.launch.serve`` runs (``serve.make_data`` / ``fit_model``
/ ``run_search``), from ``--seed``, and serves the test queries through the
compiled ``ServingEngine`` step. A phase fails the run when its recall@10
falls below its floor, when a kernel it must run is missing from the
compiled step, when a Pallas path falls back to its reference, or on any
exception. Only a run in which every phase passed prints the final line,
``{"ok": true, "device": {...}}``; without a TPU the script exits nonzero
before any phase.

One chip (T2I-10M-shaped: D=200, d=192, C=48, out-of-distribution
queries, k=10, kappa=100):

* ``kernels``: each Pallas kernel of the served and build paths against
  its ``ref.py`` oracle on a small seeded input.
* ``flat``: ``gleanvec-int8`` flat scan at n=7M (see ``N_ONE_CHIP`` for
  the cut from 10M), then concurrent requests through ``ServingFrontend``,
  whose ids must equal ``engine.submit``'s.
* ``ivf``: ``gleanvec-int8-sorted`` aligned IVF with reduced probing on
  the same collection (the ``ivf_scan`` kernel).
* ``graph``: ``gleanvec-int8-sorted`` fused graph at n=1M, built on the
  device (``ip_topk`` self-join) and traversed with ``graph_scan``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# recall@10 floors. The same phase configurations with this script's seed
# gave, on the CPU: flat 0.9996 at n=300,000 and at 3,000,000; ivf 0.9996
# at n=300,000; graph 0.9994 at n=8,192 and 0.9367 at n=100,000 (graph
# recall falls as n grows at a fixed beam). The floors leave room for 7M /
# 1M rows: 0.95 for the scans, 0.80 for the graph.
RECALL_FLOOR = {"flat": 0.95, "ivf": 0.95, "graph": 0.80}
# fused vs gathered traversal: top-10 ids that must agree (the chip's f32
# matmuls may split exact ties differently on the two paths)
MIN_OVERLAP = 0.99
# fused vs gathered ivf traversal at this probe count: the gathered fine
# step holds (m, nprobe * list_len, d) rows, so it stays small
IVF_PARITY_NPROBE = 4
T2I = dict(dim=200, d=192, clusters=48)
# T2I-10M is cut to 7M rows on one chip. The TPU's default layout of an
# (n, 200) f32 array is column-major (200 lanes would pad to 256), so the
# rerank's row gather relayouts the whole store into a padded row-major
# copy on every served batch. Store, copy and codes take 2.0 KB a row (AOT
# compile of the served step for v5e): 20 GB at 10M and 16.1 GB (15.0 GiB)
# at 8M, too close to the chip's 15.75 GiB; 14.1 GB (13.1 GiB) at 7M.
N_ONE_CHIP = 7_000_000
N_GRAPH = 1_000_000


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, failure: str) -> None:
    """Fail the run (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(failure)


def serve_args(n: int, mode: str, shape: dict, seed: int, *extra: str):
    from repro.launch import serve
    return serve.parser().parse_args(
        ["--mode", mode, "--n", str(n), "--dim", str(shape["dim"]),
         "--d", str(shape["d"]), "--clusters", str(shape["clusters"]),
         "--kappa", "100", "--batch", "64", "--seed", str(seed), *extra])


def overlap(a, b) -> float:
    """Mean fraction of row i's ids in ``a`` that also appear in row i of
    ``b`` (-1 padding never counts)."""
    a, b = np.asarray(a), np.asarray(b)
    hits = [len(set(x[x >= 0]) & set(y[y >= 0])) / max(1, (x >= 0).sum())
            for x, y in zip(a, b)]
    return float(np.mean(hits))


def has_kernel(engine) -> bool:
    """Whether the engine's compiled serving step calls a Pallas kernel
    (``tpu_custom_call`` in the optimized HLO text)."""
    from repro.analysis.hlo_rules import HLOProgram
    q = jnp.zeros((engine.batch_size, engine.dim), jnp.float32)
    program = HLOProgram.of(engine._fn.lower(q, engine.state).compile())
    return "tpu_custom_call" in program.text


@jax.jit
def _top10(queries, index, scorer):
    return index.search(queries, scorer, 10)[1]


def top10(index, queries, state):
    """Top-10 candidate ids of ``index``'s traversal alone (no rerank) over
    the state's scorer (compiled once per index variant and query
    shape)."""
    return _top10(queries, index, state.artifacts.scorer)


def kernel_calls(index, queries, scorer, name: str) -> int:
    """Calls of the Pallas kernel ``name`` in the compiled traversal of
    ``index`` (``_top10``) over ``queries``."""
    text = _top10.lower(queries, index, scorer).compile().as_text()
    return sum(1 for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and line.strip().lstrip("%").startswith(name))


def tripwires() -> None:
    """Make every fallback that would hide the chip raise instead: the
    ops-level reference paths, the sorted kernel's gathered-tile
    fallback, and ``scorer_topk``'s dense live-masked fallback."""
    from repro import kernels
    from repro.kernels.gleanvec_sq import ops as sq_ops
    from repro.kernels.graph_scan import ops as graph_ops
    from repro.kernels.ip_topk import ops as ip_ops
    from repro.kernels.ivf_scan import ops as ivf_ops

    def fail(where):
        def raiser(*args, **kwargs):
            raise RuntimeError(f"{where} ran on the chip path")
        return raiser

    for mod, name in ((ivf_ops, "ivf_scan_topk_ref"),
                      (graph_ops, "graph_scan_beam_step_ref"),
                      (ip_ops, "ip_topk_ref"),
                      (sq_ops, "gleanvec_sq_topk_ref")):
        setattr(mod, name, fail(f"{mod.__name__}.{name}"))
    kernels.scorer_scores = fail("scorer_topk's dense live-masked fallback")
    tiling = sq_ops._sorted_tiling

    def sorted_tiling(*args):
        out = tiling(*args)
        check(not out[2], "gleanvec_sq_topk took the gathered-tile fallback")
        return out

    sq_ops._sorted_tiling = sorted_tiling


def phase_kernels(seed: int) -> None:
    """Each Pallas kernel compiled for the chip against its oracle."""
    from repro.kernels.gleanvec_sq.gleanvec_sq import gleanvec_sq_topk
    from repro.kernels.gleanvec_sq.ref import gleanvec_sq_topk_ref
    from repro.kernels.graph_scan.graph_scan import graph_scan_beam_step
    from repro.kernels.graph_scan.ref import graph_scan_beam_step_ref
    from repro.kernels.ip_topk.ip_topk import ip_topk
    from repro.kernels.ip_topk.ref import ip_topk_ref
    from repro.kernels.ivf_scan.ivf_scan import ivf_scan_topk
    from repro.kernels.ivf_scan.ref import ivf_scan_topk_ref

    rng = np.random.default_rng(seed)
    m, c, d, n, lb, k = 16, 48, 192, 1 << 15, 512, 10
    qs = jnp.asarray(rng.standard_normal((m, c, d)), jnp.float32)
    qlo = jnp.asarray(rng.standard_normal((m, c)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, 256, (n, d)), jnp.uint8)
    block_tags = jnp.asarray(np.sort(rng.integers(0, c, n // lb)), jnp.int32)
    perm = jnp.asarray(rng.permutation(n), jnp.int32)
    row_tags = jnp.asarray(rng.integers(0, c, n), jnp.int32)
    # each query probes 8-12 distinct clusters (-1 pads the tail)
    probe = np.stack([rng.permutation(c)[:12] for _ in range(m)])
    probe = jnp.asarray(np.where(np.arange(12) < rng.integers(
        8, 13, (m, 1)), probe, -1), jnp.int32)
    nbr = jnp.asarray(rng.integers(-1, n, (m, 144)), jnp.int32)
    beam_v = jnp.full((m, 96), -3.4e38, jnp.float32)
    beam_i = jnp.full((m, 96), -1, jnp.int32)
    q = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    xf = jnp.asarray(rng.standard_normal((n, d + 9)), jnp.float32)
    cases = {
        "ip_topk/u8": (lambda: ip_topk(q, codes, k),
                       lambda: ip_topk_ref(q, codes, k)),
        "ip_topk/f32": (lambda: ip_topk(jnp.pad(q, ((0, 0), (0, 9))), xf,
                                        49),
                        lambda: ip_topk_ref(jnp.pad(q, ((0, 0), (0, 9))), xf,
                                            49)),
        "gleanvec_sq_topk/sorted": (
            lambda: gleanvec_sq_topk(qs, qlo, block_tags, codes, k,
                                     row_ids=perm, layout_block=lb),
            lambda: gleanvec_sq_topk_ref(qs, qlo, block_tags, codes, k,
                                         row_ids=perm, layout_block=lb)),
        "gleanvec_sq_topk/rows": (
            lambda: gleanvec_sq_topk(qs, qlo, row_tags, codes, k),
            lambda: gleanvec_sq_topk_ref(qs, qlo, row_tags, codes, k)),
        "ivf_scan_topk": (
            lambda: ivf_scan_topk(qs, qlo, block_tags, perm, codes, probe,
                                  100, layout_block=lb),
            lambda: ivf_scan_topk_ref(qs, qlo, block_tags, perm, codes,
                                      probe, 100, layout_block=lb)),
        "graph_scan_beam_step": (
            lambda: graph_scan_beam_step(qs, qlo, block_tags, perm, codes,
                                         nbr, beam_v, beam_i,
                                         layout_block=lb),
            lambda: graph_scan_beam_step_ref(qs, qlo, block_tags, perm,
                                             codes, nbr, beam_v, beam_i,
                                             layout_block=lb)),
    }
    for name, (kernel, ref) in cases.items():
        t0 = time.perf_counter()
        kv, ki = jax.block_until_ready(kernel())
        secs = time.perf_counter() - t0
        rv, ri = ref()
        ov = overlap(ki, ri)
        kv, rv = (np.sort(np.asarray(v), axis=1) for v in (kv, rv))
        live = rv > -1e38                  # slots a finite candidate filled
        # both sides sum the same products in different orders: the error
        # is relative to the largest score, not to each (cancelling) one
        err = float(np.max(np.abs(kv - rv), where=live, initial=0.0)
                    / np.max(np.abs(rv), where=live, initial=1.0))
        log(f"[kernels] {name}: id overlap vs ref {ov:.4f} "
            f"max value err / max score {err:.2e} (compile+run {secs:.1f}s)")
        check(ov >= MIN_OVERLAP and err < 1e-5
              and np.array_equal(kv > -1e38, live),
              f"{name} disagrees with ref")


def phase_report(name: str, args, run, kernel: bool, fused_overlap,
                 need_kernel: bool = False) -> None:
    log(f"[{name}] n={args.n} D={args.dim} d={args.d} mode={args.mode} "
        f"index={args.index} build_s={run.build_s:.1f} "
        f"compile_s={run.compile_s:.1f} recall@10={run.recall:.4f} "
        f"(floor {RECALL_FLOOR[name]}) tpu_custom_call={kernel} "
        f"fused_vs_gathered_overlap="
        + ("n/a" if fused_overlap is None else f"{fused_overlap:.4f}"))
    check(run.recall >= RECALL_FLOOR[name], f"{name}: recall below floor")
    check(kernel or not need_kernel,
          f"{name}: no Pallas kernel (tpu_custom_call) in the served step")
    check(fused_overlap is None or fused_overlap >= MIN_OVERLAP,
          f"{name}: fused and gathered traversals disagree")


def phase_frontend(run, n_clients: int = 4, per_client: int = 16) -> None:
    """Concurrent single-query requests through the coalescing frontend
    over the flat phase's engine: every request answers, none with an
    exception, and the ids equal ``engine.submit``'s."""
    from repro.serve import frontend
    queries = np.asarray(run.ds.queries_test[:n_clients * per_client])
    futures = [None] * len(queries)
    fe = frontend.ServingFrontend(run.engine,
                                  buckets=(run.engine.batch_size,))

    def client(c):
        for i in range(c * per_client, (c + 1) * per_client):
            futures[i] = fe.enqueue(queries[i])

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fe.close()
    errors = [f.exception(timeout=60) for f in futures]
    check(not any(errors), f"frontend requests failed: {errors}")
    got = np.stack([f.result() for f in futures])
    want = np.asarray(run.engine.submit(queries))
    same = float(np.mean(np.all(got == want, axis=1)))
    log(f"[flat] frontend: {len(queries)} requests from {n_clients} "
        f"clients, {fe.stats.n_batches} batches, ids equal to submit's "
        f"for {same:.4f} of them")
    check(same == 1.0, "frontend ids differ from engine.submit")


def one_chip(seed: int, n_big: int, n_graph: int) -> None:
    from repro.index.protocol import replace
    from repro.launch import serve

    phase_kernels(seed)
    tripwires()

    args = serve_args(n_big, "gleanvec-int8", T2I, seed)
    t0 = time.perf_counter()
    ds = serve.make_data(args)
    log(f"[data] n={n_big} D={T2I['dim']} generated with exact ground "
        f"truth in {time.perf_counter() - t0:.1f}s")
    if n_big < 10_000_000:
        log(f"[cut] T2I-10M served at n={n_big} on one chip: the rerank "
            "gather's per-batch relayout of the (n, 200) f32 store does "
            "not fit 16 GB beside the store at 10M")
    flat = serve.run_search(args, ds=ds)
    model = flat.model
    phase_report("flat", args, flat, has_kernel(flat.engine), None)
    phase_frontend(flat)
    del flat
    gc.collect()         # free the flat state's device buffers

    args = serve_args(n_big, "gleanvec-int8-sorted", T2I, seed, "--index",
                      "ivf", "--aligned", "--reduced-probe")
    ivf = serve.run_search(args, ds=ds, model=model)   # same fit
    state = ivf.engine.state
    # fused vs gathered on one served batch. The fused scan is one kernel
    # call for the whole batch; the gathered fine step holds
    # (m, nprobe * list_len, d) rows: it runs one query at a time.
    fused = replace(state.index, nprobe=IVF_PARITY_NPROBE)
    gathered = replace(fused, aligned_layout=False)
    qt = jnp.asarray(ds.queries_test[:ivf.engine.batch_size])
    calls = kernel_calls(fused, qt, state.artifacts.scorer, "ivf_scan_topk")
    got = np.asarray(top10(fused, qt, state))
    want = np.concatenate([top10(gathered, qt[i:i + 1], state)
                           for i in range(qt.shape[0])])
    log(f"[ivf] fused scan of {qt.shape[0]} queries at "
        f"nprobe={fused.nprobe}: {calls} ivf_scan_topk call(s)")
    check(calls == 1, "the fused ivf scan is not one kernel call")
    phase_report("ivf", args, ivf, has_kernel(ivf.engine),
                 overlap(got, want), need_kernel=True)
    del ivf, state, ds
    gc.collect()

    args = serve_args(n_graph, "gleanvec-int8-sorted", T2I, seed, "--index",
                      "graph", "--fused-graph", "--graph-build", "device",
                      "--expand", "4")
    graph = serve.run_search(args)
    state = graph.engine.state
    qt = jnp.asarray(graph.ds.queries_test[:graph.engine.batch_size])
    phase_report("graph", args, graph, has_kernel(graph.engine),
                 overlap(top10(state.index, qt, state),
                         top10(replace(state.index, fused=False), qt, state)),
                 need_kernel=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated collection")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    from repro.utils import runtime
    log(f"[setup] {dev.device_kind} x{len(jax.devices())}, compile cache "
        f"{runtime.configure()}")
    t0 = time.perf_counter()
    one_chip(args.seed, n_big=N_ONE_CHIP, n_graph=N_GRAPH)
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
