"""Split the serving steps of one traced window by the program's scopes.

    python bench/split.py --workload <cell> --seed <n> --seconds <s>

Sets up and drives the cell as ``bench/run.py`` does (the same
configuration, traffic, set-up and traced slice), keeps the raw trace,
reads the compiled text of the largest bucket's step and prints one JSON
line (:mod:`bench.scopes`): each ``search.*`` scope's device time per step,
the share of the steps' busy time the scopes cover, the operations left
unscoped, the dispatcher's host time per round, the idle gaps named by the
program's ``serve.*`` spans, and what tracing costs: the answers per second
and the host gaps between steps inside the traced slice against the rest
of the window. It checks no answers (``bench/run.py`` does). Exits nonzero
without a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _rate_and_gaps(win, batches, lo_s: float, hi_s: float) -> dict:
    """Answers per second and the median host gap between consecutive
    steps, inside [lo_s, hi_s) (host clock) and in the rest of the
    window."""
    import numpy as np
    done = win.done[win.ok]
    inside = (done >= lo_s) & (done < hi_s)
    rest = (done >= win.t_start) & (done < win.t_end) & ~inside
    gaps_in, gaps_out = [], []
    for a, b in zip(batches, batches[1:]):
        gap = b.t0 - a.t1
        if lo_s <= a.t1 and b.t0 < hi_s:
            gaps_in.append(gap)
        elif b.t0 < lo_s or a.t1 >= hi_s:
            gaps_out.append(gap)
    med = (lambda g: 1e3 * statistics.median(g) if g else None)
    return {"qps_traced": float(np.sum(inside)) / (hi_s - lo_s),
            "qps_untraced": float(np.sum(rest))
            / (win.seconds - (hi_s - lo_s)),
            "gap_p50_ms_traced": med(gaps_in),
            "gap_p50_ms_untraced": med(gaps_out)}


def split_cell(spec, cell_name: str, seed: int, seconds: float,
               **where) -> dict:
    """One traced window of ``cell_name``, split; needs no chip.
    ``where`` finds the device operations (:class:`bench.scopes.Split`)."""
    import jax
    from bench import load, run, scopes, trace_reduce as tr

    cell = spec.cell(cell_name)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    place = run.placement(cfg)
    coll = place.make(cfg["collection_seed"], seed, cfg["n"], cfg["dim"],
                      run.LEARN_QUERIES, 0)
    served, _ = run.build(cfg, coll.x, coll.learn, int(traffic["queue"]))
    rows = cfg["max_batch"]
    hlo = served.engine.lower(rows).compile().as_text()
    coll = place.with_pool(coll, seed, load.capacity(
        traffic, seconds, run.full_batch_s(served, coll.learn, rows), rows))
    inst = load.Instrumented(served.frontend)
    gc.collect()
    gc.freeze()
    log_dir = tempfile.mkdtemp(prefix="bench-split-")
    try:
        win = run.measure(served.frontend, coll.pool, traffic, seconds,
                          cfg["k"], log_dir)
    finally:
        gc.unfreeze()
    path = tr.find_xplane(log_dir)
    split = scopes.Split(tr.load(path), scopes.host_spans(path), hlo, rows,
                         **where)
    shutil.rmtree(log_dir, ignore_errors=True)
    # the trace's clock against the host's, by the traced batches' spans
    traced = split.trace.batches()
    offset = statistics.median(traced[b.index][0] / 1e9 - b.t0
                               for b in inst.batches if b.index in traced)
    lo_s = split.trace._lo / 1e9 - offset
    out = {"cell": cell_name, "seed": seed,
           "device": jax.devices()[0].device_kind, "rows": rows}
    out.update(split.summary())
    out.update(_rate_and_gaps(win, load.batches_in(inst, win), lo_s,
                              lo_s + split.trace.window_s))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import run
    spec = run.Spec()
    spec.cell(args.workload)
    run.use_checkout_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("split: needs a TPU; nothing run.")
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils import runtime
    runtime.configure()
    print(json.dumps(split_cell(spec, args.workload, args.seed,
                                args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
