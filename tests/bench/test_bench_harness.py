"""The harness finds a configuration, a traffic mix and a per-layer
metric by name: adding a cell takes new files only."""
import filecmp
import json
import time
import weakref

import numpy as np
import pytest

import benchkit
from bench import run, work

METRIC = '''"""Answers the window got (a reader dropped in by a test)."""


def read(run):
    return float(run.window.ok.sum())
'''


@pytest.fixture
def root(tmp_path):
    return benchkit.make_root(tmp_path, {"miss10": 0.1, "worst_gap": 0.05})


def test_new_config_traffic_and_metric_are_picked_up(root, monkeypatch):
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    bench = root / "bench"
    (bench / "configs" / "tiny-new.json").write_text(json.dumps(dict(
        benchkit.TINY_FLAT, n=4096, check_limits={"miss10": 0.1,
                                                  "worst_gap": 0.05})))
    (bench / "traffic" / "tiny-closed-3.json").write_text(json.dumps(
        {"loop": "closed", "clients": 3, "queue": 6}))
    (bench / "metrics" / "answers_seen.py").write_text(METRIC)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-new", "source": "x",
                            "file": "bench/configs/tiny-new.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-new-3", "config": "tiny-new",
                              "traffic": "tiny-closed-3", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "answers_seen", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "load generator",
                              "moves": "recall10",
                              "workloads": ["tiny-new-3"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    # the CPU has no entry in the table of peaks; a traced run reads them
    monkeypatch.setattr(work, "peaks",
                        lambda kind: work.Peaks(819e9, 393e12))
    out = run.run_cell(run.Spec(root), "tiny-new-3", 2 ** 32 + 9, 0.5,
                       True, t_process=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 3
    # its own reader, and no per-layer metric of the other cells
    assert out["metrics"] == {"answers_seen": {
        "value": float(out["attempted"]), "unit": "requests"}}
    assert list(out)[-1] == "checks"
    e2e = run.run_cell(run.Spec(root), "tiny-new-3", 2 ** 32 + 9, 0.5,
                       False, t_process=time.perf_counter())
    assert set(e2e["metrics"]) == {"recall10", "setup_s"}
    for rel, data in before.items():                 # nothing was edited
        assert (root / rel).read_bytes() == data, rel
    assert filecmp.cmp(root / "bench" / "run.py",
                       benchkit.REPO / "bench" / "run.py", shallow=False)


def test_end_to_end_metrics_of_a_closed_cell(root):
    out = run.run_cell(run.Spec(root), "tiny-ivf", 2 ** 33 + 1, 0.5, False,
                       t_process=time.perf_counter())
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"qps", "recall10", "setup_s"}
    assert out["metrics"]["recall10"]["value"] > 99.0
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] > 0 and out["failed"] == 0


def test_no_tpu_exits_nonzero_before_setup(monkeypatch, capsys):
    from bench import datagen

    def setup(*args, **kwargs):
        raise AssertionError("set-up ran without a TPU")

    monkeypatch.setattr(datagen, "make", setup)
    # main points the compile cache at the checkout; keep that setting
    # from reaching the processes that later tests start
    for name in ("JAX_COMPILATION_CACHE_DIR",
                 "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.setenv(name, "")
    rc = run.main(["--workload", "t2i-8m-ivf-closed", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    out = capsys.readouterr()
    assert out.out == "" and "needs 1 TPU" in out.err


# -- the check fails when the timed path is broken underneath --------------

def _limits():
    cfg = json.loads((benchkit.REPO / "bench" / "configs" / "t2i-8m-ivf.json")
                     .read_text())
    return cfg["check_limits"]


def _altered(orig, n):
    """Every answer's last id changed where the step produces it."""
    def step(self, queries, state):
        ids = np.array(orig(self, queries, state))
        ids[:, -1] = (ids[:, -1] + 1) % n
        return ids
    return step


def _half_batch(orig, n):
    """Half of each batch left out: its rows get the other half's answers."""
    def step(self, queries, state):
        ids = np.array(orig(self, queries, state))
        h = (len(ids) + 1) // 2
        ids[h:] = ids[:len(ids) - h]
        return ids
    return step


def _stale(orig, n):
    """A step that hands back its previous answers unchanged."""
    last = []

    def step(self, queries, state):
        ids = np.array(orig(self, queries, state))
        out = last[-1] if last and last[-1].shape == ids.shape else ids
        last.append(ids)
        return out
    return step


def _bf16_reference(orig, n):
    """The control: the plain reference put in the program's place with
    its inputs rounded to bf16, as the TPU's default precision does."""
    import jax.numpy as jnp
    from bench import reference

    def step(self, queries, state):
        # the store where the program keeps it: a device array or, for a
        # host store, a host array (``np.asarray`` reads both)
        x = jnp.asarray(np.asarray(state.artifacts.x_full), jnp.bfloat16
                        ).astype(jnp.float32)
        q = np.asarray(jnp.asarray(queries, jnp.bfloat16)
                       .astype(jnp.float32))
        return reference.exact_topk(q, x, self.k, query_block=len(q))[1]
    return step


FAULTS = [_altered, _half_batch, _stale, _bf16_reference]


@pytest.mark.parametrize("fault,cell", [
    *(pytest.param(f, "tiny-ivf", id=f.__name__) for f in FAULTS),
    *(pytest.param(f, "tiny-host-ivf", id=f"host{f.__name__}")
      for f in FAULTS)])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault,
                                          cell):
    from repro.serve.engine import ServingEngine
    root = benchkit.make_root(tmp_path, _limits())
    monkeypatch.setattr(ServingEngine, "search_with",
                        fault(ServingEngine.search_with,
                              benchkit.TINY["n"]))
    out = run.run_cell(run.Spec(root), cell, 2 ** 33 + 77, 0.3, False,
                       t_process=time.perf_counter())
    print(out["checks"])
    assert not out["correct"], out["checks"]


def test_sound_run_is_correct_under_the_cell_limits(tmp_path):
    root = benchkit.make_root(tmp_path, _limits())
    out = run.run_cell(run.Spec(root), "tiny-ivf", 2 ** 33 + 77, 0.3, False,
                       t_process=time.perf_counter())
    assert out["correct"], out["checks"]


def test_a_compile_inside_the_window_fails_the_run(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import ServingEngine
    root = benchkit.make_root(tmp_path, _limits())
    orig = ServingEngine.search_with

    def step(self, queries, state):
        # a program the warm-up never saw: compiles on every call
        jax.jit(lambda q: q + 1.0)(jnp.asarray(queries)).block_until_ready()
        return orig(self, queries, state)

    monkeypatch.setattr(ServingEngine, "search_with", step)
    with pytest.raises(RuntimeError, match="inside the measured window"):
        run.run_cell(run.Spec(root), "tiny-ivf", 2 ** 33 + 78, 0.3, False,
                     t_process=time.perf_counter())


# -- a store kept in host memory -------------------------------------------

class _Served:
    """Records, for each engine built, a weak reference to it and to its
    host store, and counts the host store's gathers of candidate rows."""

    def __init__(self, monkeypatch):
        from repro.core import rerank_tier
        from repro.core import search as msearch
        from repro.serve.engine import ServingEngine
        self.engines, self.stores, self.takes = [], [], [0]
        init, take = ServingEngine.__init__, rerank_tier.HostStore.take

        def record(engine, state, *args, **kwargs):
            init(engine, state, *args, **kwargs)
            host = msearch.host_tier(state.artifacts)
            self.engines.append(weakref.ref(engine))
            self.stores.append(None if host is None else weakref.ref(host))

        def counted(store, ids):
            self.takes[0] += 1
            return take(store, ids)

        monkeypatch.setattr(ServingEngine, "__init__", record)
        monkeypatch.setattr(rerank_tier.HostStore, "take", counted)


@pytest.mark.parametrize("cell", ["tiny-host-ivf", "tiny-host-flat"])
def test_host_store_cell_is_correct_through_the_host_branch(
        root, monkeypatch, cell):
    served = _Served(monkeypatch)
    out = run.run_cell(run.Spec(root), cell, 2 ** 33 + 5, 0.5, False,
                       t_process=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["metrics"]["recall10"]["value"] > 99.0
    assert out["attempted"] > 0 and out["failed"] == 0
    # the engine served a host store: every step gathered its candidate
    # rows from host memory
    assert len(served.stores) == 1 and served.stores[0] is not None
    assert served.takes[0] >= out["attempted"] / benchkit.TINY["max_batch"]


@pytest.mark.parametrize("cell,trace", [("tiny-ivf", False),
                                        ("tiny-host-ivf", True)])
def test_program_state_is_freed_before_the_reference(root, monkeypatch,
                                                     cell, trace):
    served = _Served(monkeypatch)
    check = run.check_answers
    gone = []

    def checked(*args, **kwargs):
        gone.append([r() is None for r in served.engines]
                    + [r() is None for r in served.stores if r is not None])
        return check(*args, **kwargs)

    monkeypatch.setattr(run, "check_answers", checked)
    monkeypatch.setattr(work, "peaks",
                        lambda kind: work.Peaks(819e9, 393e12))
    out = run.run_cell(run.Spec(root), cell, 2 ** 33 + 6, 0.5, trace,
                       t_process=time.perf_counter())
    assert out["correct"], out["checks"]
    assert len(served.engines) == 1 and gone == [[True] * len(gone[0])]
    assert len(gone[0]) == (2 if cell.startswith("tiny-host") else 1)
    if trace:           # the readers of the work counts read a host store
        assert {"step_ms", "ivf_scan_reuse"} <= set(out["metrics"])


@pytest.mark.parametrize("cfg", [benchkit.TINY_IVF, benchkit.TINY_FLAT])
def test_work_counts_read_the_same_for_a_host_store(cfg):
    """``batch_work`` reads the probe sets and the scorer's ``perm``, not
    the store: a host store's state gives the same counts."""
    from bench import datagen, load
    coll = datagen.make(cfg["collection_seed"], 11, cfg["n"], cfg["dim"],
                        run.LEARN_QUERIES, 64)
    batches = [load.Batch(i, 0.0, 0.0, 3, coll.pool[4 * i:4 * i + 4])
               for i in range(4)]
    counts = {}
    for where in ("device", "host"):
        c = dict(cfg, store=where)
        x = coll.x if where == "device" else np.asarray(coll.x)
        served, _ = run.build(c, x, coll.learn, 16)
        served.frontend.close()
        counts[where] = run.batch_work(c, served.state, batches)
    assert counts["host"] == counts["device"]
    assert all(w["step"].ops > 0 for w in counts["host"].values())
