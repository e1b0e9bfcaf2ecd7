"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit),
writes them to results/bench.csv, and writes one machine-readable
``BENCH_<name>.json`` per bench group (ops/s, HBM bytes moved, recall@10,
...) next to the CSV -- mirrored to the repo root -- so the perf
trajectory is diffable across PRs.

``--smoke`` shrinks the datasets and runs the search-path modules only
(table1 + kernel micros) so the perf harness itself is exercisable in CI;
the numbers it prints characterize the harness, not the hardware.
"""
import argparse
import os
import shutil
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny datasets, search-path modules only")
    ap.add_argument("--out", default=None,
                    help="CSV output path (default results/bench.csv)")
    args = ap.parse_args(argv)

    from benchmarks import (common, fig4_fig5_linear, fig6_cluster_structure,
                            fig7_tag_access, fig8_gleanvec, kernels_micro,
                            serving_stream, table1_search)
    saved = (common.BENCH_N, common.BENCH_QUERIES)
    try:
        if args.smoke:
            common.BENCH_N = 1500
            common.BENCH_QUERIES = 64
            common.dataset.cache_clear()
            common.ROWS.clear()
            common.RESULTS.clear()
            common.DECLARED.clear()
        print("name,us_per_call,derived")
        if args.smoke:
            table1_search.run()
            kernels_micro.run(n=4000, dim=128, d=48, c=8, m=8)
            serving_stream.run(cycles=2, batch=32)
        else:
            fig4_fig5_linear.run()
            fig6_cluster_structure.run()
            fig7_tag_access.run()
            fig8_gleanvec.run()
            table1_search.run()
            kernels_micro.run()
            serving_stream.run()
        out = args.out or os.path.join(os.path.dirname(__file__), "..",
                                       "results", "bench.csv")
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            f.write("name,us_per_call,derived\n")
            f.write("\n".join(common.ROWS) + "\n")
        print(f"# wrote {len(common.ROWS)} rows to {out}")
        # every BENCH_<name>.json also lands at the repo ROOT so the perf
        # trajectory is visible without digging into results/ -- but the
        # root copies are the COMMITTED full-size baselines, so the mirror
        # is guarded: a --smoke run never mirrors, and a run at any other
        # workload than the baseline's recorded one is refused (the rows
        # stay in results/, the baseline stays intact)
        repo_root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                 ".."))
        for p in common.write_json_results(os.path.dirname(
                os.path.abspath(out))):
            print(f"# wrote {p}")
            dst = os.path.join(repo_root, os.path.basename(p))
            if os.path.abspath(p) == dst:
                continue
            if args.smoke:
                print(f"# smoke workload: NOT mirrored to {dst}")
                continue
            have = common.workload_of(dst) if os.path.exists(dst) else None
            ran = {"bench_n": common.BENCH_N,
                   "bench_queries": common.BENCH_QUERIES}
            if have is not None and have != ran:
                print(f"# REFUSED to overwrite {dst}: baseline workload "
                      f"{have} != this run's {ran} (rows kept in {p})")
                continue
            shutil.copyfile(p, dst)
            print(f"# wrote {dst}")
    finally:
        if args.smoke:    # restore for in-process callers (tests)
            common.BENCH_N, common.BENCH_QUERIES = saved
            common.dataset.cache_clear()


if __name__ == '__main__':
    from repro.utils import runtime
    runtime.configure()
    main()
