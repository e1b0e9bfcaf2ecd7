"""Readings that set the limits of ``correct``: sound runs and controls.

    python bench/control.py --workload <cell> --seeds 11 12 13 --seconds 5

In one process it builds the cell as ``bench/run.py`` does (set-up is the
long part, so it is paid once) and reads, for each seed, the compared
numbers (``bench/reference.py``) of a short window of the cell's traffic
served by:

* ``program@highest``: the program as the configuration states it (every
  f32 contraction at HIGHEST): a sound run;
* ``program@high`` / ``program@default``: the same built state served with
  the program's contraction precision (``repro.core.linalg.F32``) lowered
  to three bf16 passes / the backend's default (one bf16 pass);
* ``reference@high`` / ``reference@default``: the plain reference at
  those precisions put in the program's place, answering the same queries.

Each reading is one JSON line on standard output. The benchmark's own runs
never run this; it needs a TPU (on another backend the lowered precisions
compute f32 in full and would read as sound).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PRECISIONS = ("high", "default")


def _reading(seed, variant, win, correct, checks, cmp) -> dict:
    return {"seed": seed, "variant": variant, "correct": bool(correct),
            "answered": int(win.ok.sum()), "checked": cmp.checked,
            "recall10": cmp.recall10,
            **{k: v["value"] for k, v in checks.items()}}


def control(spec, cell_name: str, seeds, seconds: float):
    """Every reading of every seed (see the module docstring), as each
    comes. The collection and the build are the configuration's, so they
    are made once; each precision compiles once and serves each seed's
    window in turn."""
    import jax
    import numpy as np
    from bench import load, run
    from repro.core import linalg
    from repro.serve.engine import ServingEngine
    from repro.serve.frontend import ServingFrontend

    cell = spec.cell(cell_name)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    place = run.placement(cfg)
    base = place.make(cfg["collection_seed"], 0, cfg["n"], cfg["dim"],
                      run.LEARN_QUERIES, 0)
    buckets = (cfg["max_batch"],)
    served, _ = run.build(cfg, base.x, base.learn, int(traffic["queue"]),
                          buckets=buckets)
    state = served.state
    n_pool = load.capacity(traffic, seconds,
                           run.full_batch_s(served, base.learn,
                                            cfg["max_batch"]),
                           cfg["max_batch"])
    pools = {s: place.with_pool(base, s, n_pool).pool for s in seeds}
    windows = {}
    for s in seeds:
        windows["program@highest", s] = run.measure(
            served.frontend, pools[s], traffic, seconds, cfg["k"])
        served = served._replace(frontend=ServingFrontend(
            served.engine, capacity=int(traffic["queue"]), buckets=buckets))
    served.frontend.close()
    del served
    highest = linalg.F32
    try:
        for name in PRECISIONS:
            linalg.F32 = jax.lax.Precision[name.upper()]
            jax.clear_caches()
            engine = ServingEngine(state, k=cfg["k"], kappa=cfg["kappa"],
                                   batch_size=cfg["max_batch"],
                                   dim=cfg["dim"])
            for s in seeds:
                fe = ServingFrontend(engine, capacity=int(traffic["queue"]),
                                     buckets=buckets)
                windows[f"program@{name}", s] = run.measure(
                    fe, pools[s], traffic, seconds, cfg["k"])
            del engine, fe
    finally:
        linalg.F32 = highest
        jax.clear_caches()
    # the reference runs with the program's state freed, as in run.py
    del state
    gc.collect()
    for (variant, s), win in windows.items():
        yield _reading(s, variant, win,
                       *run.check_answers(cfg, base.x, pools[s], win, s))
    for s in seeds:
        sound = windows["program@highest", s]
        rows = np.flatnonzero(sound.ok)
        for name in PRECISIONS:
            # the reference at this precision in the program's place; at
            # one bf16 pass XLA converts the whole store ahead of the
            # scan, which does not fit beside a 12 GB store
            try:
                _, ids = place.exact_topk(
                    pools[s][rows], base.x, cfg["k"],
                    precision=jax.lax.Precision[name.upper()])
            except jax.errors.JaxRuntimeError as e:
                yield {"seed": s, "variant": f"reference@{name}",
                       "error": str(e).splitlines()[0]}
                continue
            sound.ids[rows] = ids
            yield _reading(s, f"reference@{name}", sound,
                           *run.check_answers(cfg, base.x, pools[s], sound,
                                              s))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    from bench import run
    run.use_checkout_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU; nothing run", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils import runtime
    runtime.configure()
    t0 = time.perf_counter()
    for reading in control(run.Spec(), args.workload, args.seeds,
                           args.seconds):
        print(json.dumps(reading), flush=True)
    print(f"[control] {len(args.seeds)} seeds in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
