import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST come first: jax locks the platform and device count on first
# init. The dry-run compiles for a fake CPU mesh and never takes an
# accelerator, so neither this process nor its --all children (which
# inherit the environment) contend for a chip.
"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and extract the roofline inputs.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]

Each cell writes results/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis, cost_analysis, parsed collective bytes, trip-corrected
roofline terms, and the compile wall time. --all runs cells in subprocesses
(isolates XLA state; an OOM/crash in one cell cannot take down the sweep) and
skips cells whose JSON already exists (incremental; --force to redo).
"""
import argparse
import json
import subprocess
import sys
import time
import traceback

import jax

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def cell_path(arch: str, shape: str, mesh_kind: str) -> str:
    safe = f"{arch}__{shape}__{mesh_kind}".replace("/", "_")
    return os.path.abspath(os.path.join(RESULTS_DIR, safe + ".json"))


def run_cell(arch: str, shape: str, mesh_kind: str) -> dict:
    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_bundle
    from repro.utils import hlo_analysis, roofline

    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_chips = mesh.size
    t0 = time.time()
    bundle = build_bundle(arch, shape, mesh)

    def to_sharding(spec_tree, arg_tree):
        return jax.tree.map(
            lambda spec, _: NamedSharding(mesh, spec), spec_tree, arg_tree,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

    in_shardings = tuple(
        to_sharding(s, a) for s, a in zip(bundle.in_shardings, bundle.args))
    out_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), bundle.out_shardings,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

    from repro.utils.jax_compat import set_mesh
    with set_mesh(mesh):
        jitted = jax.jit(bundle.fn, in_shardings=in_shardings,
                         out_shardings=out_shardings)
        lowered = jitted.lower(*bundle.args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

    mem = compiled.memory_analysis()
    cost = hlo_analysis.normalize_cost(compiled.cost_analysis())
    hlo_text = compiled.as_text()

    # CPU-backend bf16 legalization: XLA CPU materializes f32 twins of large
    # bf16 buffers (hoisted converts around DUS/dots/collectives) that do
    # not exist in TPU modules (bf16 dots/updates are native there).
    # Estimate their footprint: f32 shapes >= 256 MB that have an
    # identically-dimensioned bf16 buffer, counted once per DISTINCT
    # defining instruction (buffer-assignment reuse makes this an upper
    # bound on liveness, so the subtraction is capped: the estimate never
    # drops below arguments + outputs + 10% of raw temps).
    import re as _re
    f32_defs, bf16_shapes = {}, set()
    for m in _re.finditer(
            r"%([\w.\-]+)\s*=\s*(f32|bf16)\[([\d,]+)\]", hlo_text):
        name, dt, dims = m.groups()
        if dt == "bf16":
            bf16_shapes.add(dims)
        else:
            f32_defs.setdefault(dims, set()).add(name)
    twin_bytes = 0
    for dims, names in f32_defs.items():
        if dims not in bf16_shapes:
            continue
        n = 1
        for d in dims.split(","):
            n *= int(d)
        if n * 4 >= 256e6:
            # liveness heuristic: at most 3 concurrent copies per shape
            twin_bytes += n * 4 * min(len(names), 3)
    stats = hlo_analysis.analyze_hlo(
        hlo_text, default_trips=bundle.trip_counts)

    corr = (stats["dot_flops"] / float(cost.get("flops", 1.0))
            if cost.get("flops") else 1.0)
    terms = roofline.compute_terms(cost, stats, bundle.model_flops, n_chips)

    record = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "n_chips": n_chips,
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes
                           + mem.temp_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes),
            "fits_v5e_16g": (mem.argument_size_in_bytes
                             + mem.temp_size_in_bytes
                             + mem.output_size_in_bytes
                             - mem.alias_size_in_bytes) < 16e9,
            "cpu_bf16_twin_bytes": twin_bytes,
            "peak_bytes_tpu_est": max(
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + 0.1 * mem.temp_size_in_bytes,
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes
                - twin_bytes),
            "fits_v5e_16g_tpu_est": max(
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + 0.1 * mem.temp_size_in_bytes,
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes
                - twin_bytes) < 16e9,
        },
        "cost": {k: v for k, v in cost.items()
                 if k in ("flops", "bytes accessed", "transcendentals")},
        "collectives": {
            "bytes": stats["collective_bytes"],
            "by_kind": stats["collective_by_kind"],
            "count": stats["n_collectives"],
            "while_trips": stats["while_trips"],
        },
        "flop_correction": corr,
        "roofline": terms.to_dict(),
        "notes": bundle.notes,
    }
    return record


def all_cells():
    from repro.configs.registry import ARCHS
    cells = []
    for arch, mod in ARCHS.items():
        for shape in mod.SHAPES:
            if shape in getattr(mod, "SKIPS", {}):
                continue
            cells.append((arch, shape))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        failures = []
        todo = [(a, s, m) for a, s in all_cells() for m in meshes]
        for i, (arch, shape, mk) in enumerate(todo):
            path = cell_path(arch, shape, mk)
            if os.path.exists(path) and not args.force:
                print(f"[{i+1}/{len(todo)}] SKIP (cached) {arch}:{shape}:{mk}")
                continue
            print(f"[{i+1}/{len(todo)}] RUN {arch}:{shape}:{mk}", flush=True)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mk]
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout)
            if r.returncode != 0:
                failures.append((arch, shape, mk))
                print(f"    FAILED:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
            else:
                print("    " + r.stdout.strip().splitlines()[-1])
        print(f"\ndone: {len(todo) - len(failures)}/{len(todo)} ok")
        if failures:
            print("failures:", failures)
            sys.exit(1)
        return

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    for mk in meshes:
        path = cell_path(args.arch, args.shape, mk)
        try:
            rec = run_cell(args.arch, args.shape, mk)
        except Exception as e:
            rec = {"arch": args.arch, "shape": args.shape, "mesh": mk,
                   "ok": False, "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"{args.arch}:{args.shape}:{mk} FAILED: {rec['error']}")
            sys.exit(1)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        r = rec["roofline"]
        print(f"{args.arch}:{args.shape}:{mk} ok "
              f"compile={rec['compile_s']}s "
              f"peak/dev={rec['memory']['peak_bytes']/1e9:.2f}GB "
              f"terms(c/m/n)={r['compute_s']:.2e}/{r['memory_s']:.2e}/"
              f"{r['collective_s']:.2e}s bottleneck={r['bottleneck']}")


if __name__ == "__main__":
    main()
