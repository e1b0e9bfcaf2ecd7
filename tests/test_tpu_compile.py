"""Ahead-of-time compiles of the hot Pallas kernels for a TPU v5e.

No chip is needed: libtpu describes a ``v5e:2x2`` slice, and each kernel
is lowered through Mosaic (``use_pallas=True``, not interpret mode) for
one of its devices at the served shapes' widths -- C=48 clusters, reduced
dims d in {160, 192} (RQA-10M and T2I-10M), u8 codes; ``ivf_scan_topk``
at the served T2I batch and layout (64 queries over 8,101,888 rows in
4096-row blocks, k=100: one call for the whole batch). Every compiled
program must call the kernel (``tpu_custom_call``) and must hold no gather
(:class:`NoGatherOnFusedPath`, which only TPU-compiled HLO can check).
The kernel's instruction is named after it (``pallas_call(name=...)``):
the profiler names its events by that instruction, and the benchmark finds
``ivf_scan_topk``'s events by it. Where libtpu cannot describe the
topology the tests skip.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo_rules import HLOProgram, NoGatherOnFusedPath
from repro.kernels.gleanvec_sq.ops import gleanvec_sq_topk
from repro.kernels.graph_scan.ops import graph_scan_beam_step
from repro.kernels.ip_topk.ops import ip_topk
from repro.kernels.ivf_scan.ops import ivf_scan_topk

C, N, M, K = 48, 1 << 16, 16, 10
LAYOUT_BLOCK = 512
# the ivf scan at the served T2I shape: a full 64-row batch, 12 of 48
# clusters probed per query, over the 8M-row layout in 4096-row blocks
IVF_M, IVF_N, IVF_BLOCK, NPROBE = 64, 8_101_888, 4096, 12
NEIGHBORS, BEAM = 4 * 36, 96   # graph hop: expand * R neighbors, beam

pytestmark = pytest.mark.tier1


@pytest.fixture(scope="module")
def v5e():
    """``ShapeDtypeStruct`` factory placed on one device of a described
    v5e 2x2 slice."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu, or it cannot describe a v5e
        pytest.skip(f"no TPU topology can be described here: {e}")
    device = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=device)


def _ivf(s, d, codes):
    return (lambda qs, ql, bt, rid, x, probe: ivf_scan_topk(
                qs, ql, bt, rid, x, probe, 100, IVF_BLOCK, use_pallas=True),
            s((IVF_M, C, d), jnp.float32), s((IVF_M, C), jnp.float32),
            s((IVF_N // IVF_BLOCK,), jnp.int32), s((IVF_N,), jnp.int32),
            s((IVF_N, d), codes), s((IVF_M, NPROBE), jnp.int32))


def _graph(s, d, codes):
    return (lambda qs, ql, bt, rid, x, nb, bv, bi: graph_scan_beam_step(
                qs, ql, bt, rid, x, nb, bv, bi, LAYOUT_BLOCK,
                use_pallas=True),
            s((M, C, d), jnp.float32), s((M, C), jnp.float32),
            s((N // LAYOUT_BLOCK,), jnp.int32), s((N,), jnp.int32),
            s((N, d), codes), s((M, NEIGHBORS), jnp.int32),
            s((M, BEAM), jnp.float32), s((M, BEAM), jnp.int32))


def _sq_sorted(s, d, codes):
    return (lambda qs, ql, bt, rid, x: gleanvec_sq_topk(
                qs, ql, bt, x, K, row_ids=rid, layout_block=LAYOUT_BLOCK,
                use_pallas=True),
            s((M, C, d), jnp.float32), s((M, C), jnp.float32),
            s((N // LAYOUT_BLOCK,), jnp.int32), s((N,), jnp.int32),
            s((N, d), codes))


def _sq_rows(s, d, codes):
    return (lambda qs, ql, tags, rid, x: gleanvec_sq_topk(
                qs, ql, tags, x, K, row_ids=rid, use_pallas=True),
            s((M, C, d), jnp.float32), s((M, C), jnp.float32),
            s((N,), jnp.int32), s((N,), jnp.int32), s((N, d), codes))


def _ip(s, d, codes):
    return (lambda q, x: ip_topk(q, x, K, use_pallas=True),
            s((M, d), jnp.float32), s((N, d), codes))


KERNELS = {"ivf_scan_topk": _ivf, "graph_scan_beam_step": _graph,
           "gleanvec_sq_topk-sorted": _sq_sorted,
           "gleanvec_sq_topk-rows": _sq_rows, "ip_topk": _ip}
CASES = [(name, "uint8") for name in KERNELS] \
    + [("ip_topk", "float32")]    # the device graph build's f32 self-join


@pytest.mark.parametrize("d", [160, 192])
@pytest.mark.parametrize("kernel,codes", CASES)
def test_kernel_compiles_for_v5e(v5e, kernel, codes, d):
    fn, *args = KERNELS[kernel](v5e, d, jnp.dtype(codes))
    program = HLOProgram.of(jax.jit(fn).lower(*args).compile(),
                            label=f"{kernel}/{codes}/d{d}")
    assert program.backend == "tpu"
    assert "tpu_custom_call" in program.text
    calls = [line.strip() for line in program.text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    name = kernel.split("-")[0]
    assert calls and all(re.match(rf"%?{name}(\.\d+)? = ", c)
                         for c in calls), calls[:1]
    result = NoGatherOnFusedPath().check(program)
    assert result.passed and not result.skipped, result.evidence
