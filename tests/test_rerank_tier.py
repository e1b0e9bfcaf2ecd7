"""Host-tier rerank: the two-level memory hierarchy
(``core/rerank_tier.py`` + the pipelined serving path).

Four guarantee layers:

* PARITY -- demoting ``x_full`` to the host tier changes WHERE the
  full-precision rows live, never WHAT the search returns: the two-stage
  pipeline (compiled ``state_candidates`` -> host kappa-row gather ->
  compiled ``rerank_candidates``) returns ids identical to the one-shot
  ``state_search``, for every scorer family x {flat, reduced-probe IVF,
  fused graph, mesh-free sharded spill}, on ID and OOD queries.
* SERVING -- the double-buffered ``ServingEngine.submit`` pipeline serves
  identical results to the all-HBM engine, moves EXACTLY
  batches*batch*kappa*D*4 bytes host->device (``host_bytes`` ==
  ``host_bytes_lb``), and swaps streamed refreshes with ZERO recompiles
  (the leafless-aux store keeps the state treedef stable); GuardedEngine
  guards and snapshot/restore round-trip the tier without promoting it.
* EDGE CASES -- an all-(-1) candidate row reranks to all -1 on both
  tiers; kappa > n and k > n pad with -1 identically on both tiers (flat
  and graph traversals).
* TRACE SAFETY -- ``rerank`` over a host store refuses to run inside jit
  (the gather is host-driven) with an actionable error.
* HOST BUILD -- a read-only host array is built from where it lies: the
  codes, tags, layout, IVF lists and served ids equal the device build's,
  ``x_full`` shares the caller's memory through demotion, and no transfer
  to the device is larger than one chunk of rows.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gleanvec as gv, leanvec_sphering as lvs, \
    rerank_tier, streaming
from repro.core import scorer as sc
from repro.core import search as msearch
from repro.data import vectors
from repro.index import distributed, graph, ivf
from repro.index.protocol import replace
from repro.serve import faults, lifecycle
from repro.serve.engine import ServingEngine

pytestmark = pytest.mark.tier1

ALL_MODES = ["full", "sphering", "gleanvec", "sphering-int8",
             "gleanvec-int8", "gleanvec-sorted", "gleanvec-int8-sorted"]

K, KAPPA = 10, 30


@pytest.fixture(scope="module")
def setup():
    ds = vectors.make_dataset("rerank-tier", n=2048, d=64, n_queries=64,
                              ood=True, seed=7)
    X = jnp.asarray(ds.database)
    Q = jnp.asarray(ds.queries_learn)
    lin = lvs.fit(Q, X, 24)
    gvm = gv.fit(jax.random.PRNGKey(0), Q, X, c=8, d=24)
    return ds, X, lin, gvm


def _model_for(mode, lin, gvm):
    if mode == "full":
        return None
    return lin if mode.startswith("sphering") else gvm


def _host_search(arts_host, q, k, kappa, index=None, block=256):
    """The two-stage pipeline as a plain function: compiled candidates,
    host gather + compiled rerank outside the trace."""
    state = msearch.make_state(arts_host, index=index, block=block)
    cand = jax.jit(msearch.state_candidates,
                   static_argnames=("kappa",))(q, state, kappa=kappa)
    return msearch.rerank(q, arts_host, np.asarray(cand), k)


# ---------------------------------------------------------------------------
# PARITY: host tier == HBM, every scorer family x traversal x regime.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ALL_MODES)
@pytest.mark.parametrize("regime", ["id", "ood"])
def test_host_matches_hbm_flat(setup, mode, regime):
    ds, X, lin, gvm = setup
    q = jnp.asarray(ds.queries_test if regime == "ood"
                    else ds.database[:48])
    arts = msearch.build_artifacts(mode, X, _model_for(mode, lin, gvm))
    ref = msearch.state_search(q, msearch.make_state(arts, block=256),
                               K, KAPPA)
    arts_host = msearch.demote_rerank_tier(arts)
    assert msearch.host_tier(arts_host) is not None
    got = _host_search(arts_host, q, K, KAPPA)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                  err_msg=f"{mode}/{regime}")
    # promote is the exact inverse
    back = msearch.promote_rerank_tier(arts_host)
    assert msearch.host_tier(back) is None
    np.testing.assert_array_equal(np.asarray(back.x_full),
                                  np.asarray(arts.x_full))


def test_host_matches_hbm_ivf_reduced_probe(setup):
    """The candidates stage is traversal-agnostic: reduced-space coarse
    probing composes with the host tier unchanged."""
    ds, X, lin, gvm = setup
    q = jnp.asarray(ds.queries_test)
    arts = msearch.build_artifacts("gleanvec-int8", X, gvm)
    iv = ivf.build(jax.random.PRNGKey(1), X, n_lists=16, nprobe=8)
    iv = ivf.with_reduced_centers(iv, arts.scorer, gvm)
    ref = msearch.state_search(q, msearch.make_state(arts, index=iv),
                               K, KAPPA)
    got = _host_search(msearch.demote_rerank_tier(arts), q, K, KAPPA,
                       index=iv)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("mode", ["gleanvec-sorted", "gleanvec-int8-sorted"])
def test_host_matches_hbm_fused_graph(setup, mode):
    """The gather-free fused beam step emits -1-padded original-id
    candidates; the host rerank consumes them identically to HBM."""
    ds, X, lin, gvm = setup
    q = jnp.asarray(ds.queries_test)
    arts = msearch.build_artifacts(mode, X, gvm)
    g = graph.build(ds.database, r=12, n_iters=3, seed=0)
    g = graph.with_fused_scan(replace(g, beam=32, max_hops=48), arts.scorer)
    assert g.fused
    ref = msearch.state_search(q, msearch.make_state(arts, index=g),
                               K, KAPPA)
    got = _host_search(msearch.demote_rerank_tier(arts), q, K, KAPPA,
                       index=g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref), mode)


@pytest.mark.parametrize("kind", ["flat", "ivf"])
def test_sharded_spill_matches_hbm(setup, kind):
    """``build_sharded_artifacts(spill_host=True)``: the sharded stack's
    global-id candidates route through per-shard host buffers and return
    ids identical to the all-HBM sharded search."""
    ds, X, lin, gvm = setup
    q = jnp.asarray(ds.queries_test)
    kwargs = dict(n_shards=4, key=jax.random.PRNGKey(1), n_lists=16,
                  nprobe=8)
    sh, arts = distributed.build_sharded_artifacts(
        kind, "gleanvec", X, gvm, spill_host=False, **kwargs)
    sh2, arts_host = distributed.build_sharded_artifacts(
        kind, "gleanvec", X, gvm, spill_host=True, **kwargs)
    store = msearch.host_tier(arts_host)
    assert isinstance(store, rerank_tier.ShardedHostStore)
    assert len(store.shards) == 4
    ref = msearch.state_search(q, msearch.make_state(arts, index=sh),
                               K, KAPPA)
    got = _host_search(arts_host, q, K, KAPPA, index=sh2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref), kind)


def test_sharded_host_store_routes_global_ids(setup):
    """The store itself: global-id gathers cross shard boundaries, -1
    clamps to row 0 (callers mask), ``.at[].set`` touches only the owning
    shard's buffer (copy-on-write)."""
    _, X, _, _ = setup
    Xn = np.asarray(X[:512])
    store = rerank_tier.demote(Xn, shards=4)
    ids = np.array([[0, 127, 128, 511], [-1, 300, 5, 400]], np.int32)
    np.testing.assert_array_equal(store.take(ids),
                                  Xn[np.maximum(ids, 0)])
    rows = np.full((2, Xn.shape[1]), 7.0, np.float32)
    store2 = store.at[np.array([3, 200])].set(rows)
    np.testing.assert_array_equal(store2.take(np.array([[3, 200]])),
                                  rows[None])
    # original untouched; non-owning shards share buffers (no n*D copy)
    np.testing.assert_array_equal(store.take(np.array([[3, 200]])),
                                  Xn[None, [3, 200]])
    assert store2.shards[2] is store.shards[2]


def test_host_store_is_leafless_aux(setup):
    """The pytree contract behind zero-recompile swaps, enforced by the
    registry's ONE definition (``LeaflessAuxHostTier``): HostStore and
    ShardedHostStore contribute NO leaves, aux equality is the store's
    (shape, dtype) aval -- content-stable, shape-guarded -- and
    demote/promote round-trips the rows exactly. Plus the ``.at[].set``
    path this module owns: an updated store stays treedef-equal too."""
    from repro.analysis import assert_rules
    from repro.analysis.protocol_rules import LeaflessAuxHostTier

    _, X, _, _ = setup

    class Ctx:
        pass

    ctx = Ctx()
    ctx.X = X[:64]
    assert_rules(ctx, [LeaflessAuxHostTier()], target="host-tier")
    a = rerank_tier.demote(np.asarray(X[:64]))
    b = a.at[np.array([0])].set(np.ones((1, X.shape[1]), np.float32))
    assert jax.tree_util.tree_flatten(a)[1] == \
        jax.tree_util.tree_flatten(b)[1]                 # update-stable


def test_rerank_refuses_host_gather_inside_jit(setup):
    ds, X, lin, gvm = setup
    arts = msearch.demote_rerank_tier(
        msearch.build_artifacts("gleanvec", X, gvm))
    q = jnp.asarray(ds.queries_test[:4])

    def traced(cand):
        return msearch.rerank(q, arts, cand, K)

    with pytest.raises(TypeError, match="state_candidates"):
        jax.jit(traced)(jnp.zeros((4, KAPPA), jnp.int32))


# ---------------------------------------------------------------------------
# HOST BUILD: a read-only host array stays where it is.
# ---------------------------------------------------------------------------

HOST_CHUNK = 600          # 2048 rows: three whole chunks and a short one
SCORER_FIELDS = ("codes", "x_low", "tags", "block_tags", "perm", "inv_perm",
                 "lo", "delta")


def _host_and_device_states(X, gvm, mode, index_kind):
    """The same rows built on the device and from a read-only host array
    (``index_kind``: flat or aligned IVF over every list)."""
    states = {}
    for where in ("device", "host"):
        x = X if where == "device" else rerank_tier.read_only(np.asarray(X))
        arts = msearch.build_artifacts(mode, x, gvm)
        index = None
        if index_kind == "ivf-aligned":
            index = ivf.with_reduced_centers(
                ivf.build_aligned(gvm, arts.x_full, nprobe=gvm.n_clusters),
                arts.scorer, gvm)
        states[where] = msearch.make_state(arts, index=index, block=256)
    return states, x


@pytest.mark.parametrize("mode,index_kind", [
    ("gleanvec-int8-sorted", "flat"), ("gleanvec-int8-sorted", "ivf-aligned"),
    ("gleanvec-sorted", "flat"), ("gleanvec-sorted", "ivf-aligned"),
    ("gleanvec-int8", "flat")])
def test_host_array_build_equals_device_build(setup, monkeypatch, mode,
                                              index_kind):
    ds, X, _, gvm = setup
    monkeypatch.setattr(rerank_tier, "HOST_CHUNK", HOST_CHUNK)
    states, x_host = _host_and_device_states(X, gvm, mode, index_kind)
    dev, host = (states[w].artifacts for w in ("device", "host"))
    assert msearch.host_tier(dev) is None
    assert isinstance(msearch.host_tier(host), rerank_tier.HostStore)
    fields = [f for f in SCORER_FIELDS if hasattr(dev.scorer, f)]
    assert len(fields) >= 4
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(host.scorer, f)),
                                      np.asarray(getattr(dev.scorer, f)),
                                      err_msg=f)
    if index_kind == "ivf-aligned":
        np.testing.assert_array_equal(
            np.asarray(states["host"].index.lists),
            np.asarray(states["device"].index.lists))
    # served ids through the engines (one step on the device, two stages
    # with the host gather) are the same
    q = np.asarray(ds.queries_test)
    served = {w: ServingEngine(st, k=K, kappa=KAPPA, batch_size=32,
                               dim=X.shape[1]).submit(q)
              for w, st in states.items()}
    np.testing.assert_array_equal(served["host"], served["device"])
    # reranking every row (kappa = n) answers the exact top-10
    n = X.shape[0]
    exact = np.argsort(-(q.astype(np.float64) @ x_host.T.astype(np.float64)),
                       axis=1)[:, :K]
    got = np.asarray(_host_search(host, jnp.asarray(q), K, n,
                                  index=states["host"].index))
    assert [set(r) for r in got] == [set(r) for r in exact]


def test_host_build_serves_the_callers_array(setup):
    """``x_full`` is a HostStore over the caller's read-only array: built
    from the array or from a HostStore over it, and demoted, it shares the
    caller's memory (no copy of the store anywhere)."""
    _, X, _, gvm = setup
    x = rerank_tier.read_only(np.asarray(X))
    assert rerank_tier.is_host(x)
    assert not rerank_tier.is_host(np.array(x))      # writable: device path
    for given in (x, rerank_tier.HostStore(x)):
        arts = msearch.build_artifacts("gleanvec-int8-sorted", given, gvm)
        store = msearch.host_tier(arts)
        assert isinstance(store, rerank_tier.HostStore)
        assert np.shares_memory(store.x, x)
        demoted = msearch.demote_rerank_tier(arts)
        assert demoted.x_full is store
        state = msearch.make_state(
            demoted, index=ivf.build_aligned(gvm, store, nprobe=4))
        assert np.shares_memory(msearch.host_tier(state.artifacts).x, x)


def test_serve_cli_host_rerank_keeps_the_store_on_the_host(setup):
    """``repro.launch.serve --host-rerank`` builds through the same calls:
    the served store is a view of the dataset's own rows."""
    from repro.launch import serve
    ds, _, _, gvm = setup
    args = serve.parser().parse_args(
        ["--mode", "gleanvec-int8-sorted", "--index", "ivf", "--aligned",
         "--reduced-probe", "--n", "2048", "--dim", "64", "--d", "24",
         "--clusters", "8", "--nprobe", "4", "--host-rerank"])
    state = serve.build_state(args, ds.database, gvm)
    store = msearch.host_tier(state.artifacts)
    assert isinstance(store, rerank_tier.HostStore)
    assert np.shares_memory(store.x, ds.database)
    assert ds.database.flags.writeable          # the caller's flag is kept


AVAL = re.compile(r"host-to-device transfer: aval=ShapedArray\((\w+)\[([\d,]*)\]")


def test_host_build_sends_the_device_one_chunk_at_a_time(setup, monkeypatch,
                                                         capfd):
    """Every host->device transfer of the host build, as the transfer
    guard logs it, is at most one chunk of rows; the scorer streams the
    store twice (tags with ranges, then codes) and the aligned IVF once."""
    _, X, _, gvm = setup
    monkeypatch.setattr(rerank_tier, "HOST_CHUNK", HOST_CHUNK)
    x = rerank_tier.read_only(np.asarray(X))
    capfd.readouterr()
    with jax.transfer_guard_host_to_device("log_explicit"):
        arts = msearch.build_artifacts("gleanvec-int8-sorted", x, gvm)
        index = ivf.build_aligned(gvm, arts.x_full, nprobe=4)
        jax.block_until_ready((arts.scorer, index))
    sizes = []
    for dtype, shape in AVAL.findall(capfd.readouterr().err):
        dims = [int(d) for d in shape.split(",") if d]
        sizes.append((int(np.prod(dims)) * np.dtype(dtype).itemsize, dims))
    n, dim = x.shape
    chunk_bytes = HOST_CHUNK * dim * 4
    assert sizes and max(b for b, _ in sizes) <= chunk_bytes
    store_chunks = [d for _, d in sizes if len(d) == 2 and d[1] == dim]
    assert len(store_chunks) == 3 * -(-n // HOST_CHUNK)
    assert sum(d[0] for d in store_chunks) == 3 * n


# ---------------------------------------------------------------------------
# EDGE CASES: dead candidate rows, kappa > n, k > n -- both tiers agree.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["full", "gleanvec-int8-sorted"])
def test_rerank_all_dead_candidate_row(setup, mode):
    """A query whose candidate row is entirely -1 (nothing survived the
    main search) returns all -1 from the rerank -- never row 0's id --
    on the device tier AND through the host gather (which clamps -1 to
    row 0 internally and relies on the mask)."""
    ds, X, lin, gvm = setup
    arts = msearch.build_artifacts(mode, X, _model_for(mode, lin, gvm))
    q = jnp.asarray(ds.queries_test[:3])
    cand = np.tile(np.arange(KAPPA, dtype=np.int32), (3, 1))
    cand[1, :] = -1                                   # dead row
    cand[2, K - 2:] = -1                              # < k live candidates
    ref = np.asarray(msearch.rerank(q, arts, jnp.asarray(cand), K))
    got = np.asarray(msearch.rerank(
        q, msearch.demote_rerank_tier(arts), cand, K))
    np.testing.assert_array_equal(got, ref, mode)
    assert (got[1] == -1).all(), mode
    assert (got[2, -2:] == -1).all() and (got[2, :-2] >= 0).all(), mode


@pytest.mark.parametrize("regime", ["id", "ood"])
@pytest.mark.parametrize("index_kind", ["flat", "graph"])
def test_kappa_and_k_exceed_n(setup, regime, index_kind):
    """kappa > n (the whole database fits in one candidate set) and
    k > n: both tiers return every live id exactly once and pad the tail
    with -1, identically."""
    ds, X, lin, gvm = setup
    n_small, k, kappa = 40, 50, 64
    Xs = X[:n_small]
    arts = msearch.SearchArtifacts(
        scorer=sc.build_scorer("gleanvec", Xs, gvm), x_full=Xs, model=gvm)
    index = None
    if index_kind == "graph":
        g = graph.build(np.asarray(Xs), r=8, n_iters=3, seed=0)
        index = replace(g, beam=32, max_hops=48, expand=4)
    q = jnp.asarray(ds.queries_test[:8] if regime == "ood"
                    else ds.database[:8])
    ref = np.asarray(msearch.state_search(
        q, msearch.make_state(arts, index=index, block=256), k, kappa))
    got = np.asarray(_host_search(msearch.demote_rerank_tier(arts), q, k,
                                  kappa, index=index))
    np.testing.assert_array_equal(got, ref,
                                  err_msg=f"{index_kind}/{regime}")
    assert got.shape == (8, k)
    for row in got:
        live = row[row >= 0]
        assert len(set(live.tolist())) == len(live)   # no duplicate ids
        assert (row[len(live):] == -1).all()          # -1 tail padding
    if index_kind == "flat":        # exhaustive scan: all n rows surface
        assert all((r >= 0).sum() == n_small for r in got)


# ---------------------------------------------------------------------------
# SERVING: pipelined engine parity, byte accounting, zero-recompile swaps,
# guarded swaps, snapshot/restore.
# ---------------------------------------------------------------------------

D, N, N0, CAP, BATCH = 32, 512, 384, 512, 16


@pytest.fixture(scope="module")
def serve_env():
    ds = vectors.make_dataset("rerank-serve", n=N, d=D, n_queries=256,
                              ood=True, seed=9)
    X = jnp.asarray(ds.database)
    rng = np.random.default_rng(0)
    q_init = np.asarray(X)[rng.integers(0, N0, 256)] \
        + 0.1 * rng.standard_normal((256, D)).astype(np.float32)
    model = gv.fit(jax.random.PRNGKey(0), jnp.asarray(q_init), X[:N0],
                   c=4, d=8)
    return ds, X, q_init, model


def _streaming_arts(env, host_rerank):
    _, X, _, model = env
    return streaming.build_streaming_artifacts(
        "gleanvec-int8", X[:N0], model, capacity=CAP, sort_block=64,
        slack_blocks=2, host_rerank=host_rerank)


def _engine(arts):
    return ServingEngine(msearch.make_state(arts), k=K, kappa=KAPPA,
                         batch_size=BATCH, dim=D)


def test_engine_pipeline_parity_and_byte_accounting(serve_env):
    """The double-buffered submit == the all-HBM engine on identical
    traffic, and the measured host->device traffic is EXACTLY
    batches*batch*kappa*D*4 bytes -- the m*kappa*D*4 contract with batch
    padding as the only slack, nothing proportional to n*D."""
    ds = serve_env[0]
    QT = np.asarray(ds.queries_test)
    e_hbm, e_host = _engine(_streaming_arts(serve_env, False)), \
        _engine(_streaming_arts(serve_env, True))
    assert msearch.host_tier(e_host.state.artifacts) is not None
    for q in (QT[:4 * BATCH], QT[: BATCH // 2], QT[: 3 * BATCH + 5]):
        np.testing.assert_array_equal(e_host.submit(q), e_hbm.submit(q))
    s = e_host.stats
    itemsize = 4
    assert s.host_bytes == s.host_bytes_lb \
        == s.n_batches * BATCH * KAPPA * D * itemsize
    assert s.host_bytes_ratio == 1.0
    assert len(s.prefetch_ms) == s.n_batches
    assert e_hbm.stats.host_bytes == 0       # single-tier engine: no traffic


def test_engine_swap_zero_recompiles_host_tier(serve_env, compile_counter):
    """Streaming cycles (insert + refresh + swap) over a host-tier store:
    the leafless-aux treedef survives every refresh, so after the warmup
    cycle there are ZERO XLA compiles -- and the store is still a
    HostStore (never silently promoted) serving correct results."""
    ds, X, q_init, model = serve_env
    engine = _engine(_streaming_arts(serve_env, True))
    stream = streaming.init_from_artifacts(engine.state.artifacts,
                                           jnp.asarray(q_init),
                                           refresh_every=64)
    QT = np.asarray(ds.queries_test)
    step = (CAP - N0) // 4

    def cycle(i):
        nonlocal stream
        engine.submit(QT[i * BATCH:(i + 1) * BATCH])
        rows = X[N0 + i * step: N0 + (i + 1) * step]
        arts2, _ = streaming.insert_rows(engine.state.artifacts, rows)
        engine.swap(engine.state._replace(artifacts=arts2))
        stream = streaming.observe_queries(
            stream, jnp.asarray(QT[i * 64:(i + 1) * 64]))
        stream = streaming.insert(stream, rows)
        stream = streaming.refresh(stream)
        engine.swap(streaming.refresh_state(engine.state, stream,
                                            source="full"))

    cycle(0)                                 # warmup
    compile_counter.reset()
    cycle(1)
    cycle(2)
    served = engine.submit(QT[:2 * BATCH])
    assert compile_counter.count == 0, \
        f"{compile_counter.count} recompiles across host-tier swap cycles"
    assert engine.n_swaps == 6
    store = msearch.host_tier(engine.state.artifacts)
    assert store is not None and len(store) == CAP
    # the streamed host store serves EXACTLY what its promoted (all-HBM)
    # twin would -- inserts and refreshes reached the host rows
    state_dev = engine.state._replace(
        artifacts=msearch.promote_rerank_tier(engine.state.artifacts))
    ref = msearch.state_search(jnp.asarray(QT[:2 * BATCH], jnp.float32),
                               state_dev, K, KAPPA)
    np.testing.assert_array_equal(served, np.asarray(ref))


def test_guarded_swaps_on_host_tier(serve_env, compile_counter):
    """GuardedEngine over a pipelined host-tier engine: the canary
    battery runs through the two-stage path, corrupt states are rejected
    atomically (bit-identical serving after), and a legitimate refresh is
    accepted with zero recompiles."""
    ds, X, q_init, model = serve_env
    engine = _engine(_streaming_arts(serve_env, True))
    guarded = lifecycle.GuardedEngine(
        engine, canary_queries=np.asarray(ds.queries_test)[:BATCH])
    obs = np.asarray(ds.queries_test)[BATCH:2 * BATCH]
    before = guarded.submit(obs)
    state0, swaps0 = engine.state, engine.n_swaps
    with pytest.raises(lifecycle.SwapRejected) as ei:
        guarded.swap(faults.corrupt_scorer_leaf(engine.state))
    assert ei.value.reason == "non-finite"
    assert engine.state is state0 and engine.n_swaps == swaps0
    np.testing.assert_array_equal(guarded.submit(obs), before)
    # a legitimate refresh passes the guards, zero recompiles
    stream = streaming.init_from_artifacts(engine.state.artifacts,
                                           jnp.asarray(q_init),
                                           refresh_every=64)
    stream = streaming.observe_queries(stream, jnp.asarray(obs))
    stream = streaming.refresh(stream)
    compile_counter.reset()
    guarded.swap(streaming.refresh_state(engine.state, stream,
                                         source="full"))
    assert engine.n_swaps == swaps0 + 1
    assert compile_counter.count == 0
    assert msearch.host_tier(engine.state.artifacts) is not None


def test_snapshot_restore_roundtrips_host_tier(serve_env, tmp_path,
                                               compile_counter):
    """snapshot/restore carries the host store through the manifest
    (``host_full``) and rebinds it on restore: the restored state serves
    bit-identical results, still host-resident, with zero recompiles on
    the original engine."""
    ds, X, q_init, model = serve_env
    engine = _engine(_streaming_arts(serve_env, True))
    stream = streaming.init_from_artifacts(engine.state.artifacts,
                                           jnp.asarray(q_init),
                                           refresh_every=64)
    probe = np.asarray(ds.queries_test)[:2 * BATCH]
    before = engine.submit(probe)
    lifecycle.snapshot(str(tmp_path), engine.state, stream)
    restored, _, step, _ = lifecycle.restore(str(tmp_path), engine.state,
                                             stream)
    store = msearch.host_tier(restored.artifacts)
    assert store is not None                 # restored ON the host tier
    np.testing.assert_array_equal(np.asarray(store),
                                  np.asarray(msearch.host_tier(
                                      engine.state.artifacts)))
    compile_counter.reset()
    engine.swap(restored._replace(
        version=engine.state.version + 1))
    np.testing.assert_array_equal(engine.submit(probe), before)
    assert compile_counter.count == 0
