"""Shared helpers of the benchmark's CPU tests: a checkout-like root that
holds the benchmark's files and one tiny cell per index kind."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY = {"n": 8192, "dim": 32, "metric": "inner_product", "queries": "ood",
        "mode": "gleanvec-int8-sorted", "d": 16, "clusters": 8,
        "layout_block": 256, "k": 10, "kappa": 40, "max_batch": 4,
        "collection_seed": 7, "source": "https://arxiv.org/abs/2410.22347"}
TINY_IVF = dict(TINY, index="ivf-aligned", nprobe=4, reduced_probe=True)
TINY_FLAT = dict(TINY, index="flat", nprobe=0, reduced_probe=False)
# twins whose f32 rerank store lives in host memory
TINY_HOST_IVF = dict(TINY_IVF, store="host")
TINY_HOST_FLAT = dict(TINY_FLAT, store="host")
TRAFFIC = {"loop": "closed", "clients": 8, "queue": 16}


def make_root(tmp: Path, limits: dict) -> Path:
    """A copy of the benchmark under ``tmp`` with the tiny cells
    ``tiny-ivf`` and ``tiny-flat`` and their host-store twins
    ``tiny-host-ivf`` and ``tiny-host-flat`` (closed loop of 8 clients)
    added, each reporting the metrics of the full-size cell of its index
    kind."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, cfg, like in (
            ("tiny-ivf", TINY_IVF, "t2i-8m-ivf-closed"),
            ("tiny-flat", TINY_FLAT, "rqa-4m-flat-closed"),
            ("tiny-host-ivf", TINY_HOST_IVF, "t2i-8m-ivf-closed"),
            ("tiny-host-flat", TINY_HOST_FLAT, "rqa-4m-flat-closed")):
        (tmp / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(dict(cfg, check_limits=limits)))
        spec["configs"].append({"name": name, "source": cfg["source"],
                                "file": f"bench/configs/{name}.json",
                                "reduced": ["n"], "why": "CPU test"})
        spec["workloads"].append({"name": name, "config": name,
                                  "traffic": "tiny-closed", "chips": 1,
                                  "why": "CPU test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", []):    # the metrics of its twin
                m["workloads"].append(name)
    (tmp / "bench" / "traffic" / "tiny-closed.json").write_text(
        json.dumps(TRAFFIC))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
