"""Public ops: gather-free sorted-IVF range scan with Pallas kernel +
jnp fallback, plus the kernel's HBM-traffic model.

``ivf_scan_topk`` takes each query's probed cluster ids (-1-padded) and
streams the union of the batch's probed single-tag slabs once, scoring
every query against each -- Pallas on TPU (and in interpret mode), the
dense jnp oracle elsewhere. When the requested tile does not divide the
layout block, the dispatcher shrinks the tile to the layout block (every
slab is then one grid step) -- never wrong, only coarser.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.kernels._mosaic import lanes
from repro.kernels.ivf_scan.ivf_scan import (ivf_scan_topk
                                             as _pallas_ivf_scan_topk)
from repro.kernels.ivf_scan.ref import (ivf_scan_scores_ref,
                                        ivf_scan_topk_ref)

__all__ = ["ivf_scan_topk", "ivf_scan_topk_ref", "ivf_scan_scores_ref",
           "fine_step_bytes"]


def ivf_scan_topk(q_scaled: jax.Array, q_lo: jax.Array,
                  block_tags: jax.Array, row_ids: jax.Array,
                  codes: jax.Array, probe: jax.Array, k: int,
                  layout_block: int, tn: int = 512,
                  use_pallas: bool | None = None, interpret: bool = False):
    """``q_scaled (M, C, d)``, ``q_lo (M, C)``, ``block_tags (NB,)``,
    ``row_ids (N,)``, ``codes (N, d)`` u8/f32, ``probe (M, P)`` cluster
    ids (-1 = pad) -> (vals (M, k), ids (M, k)), ids ORIGINAL (-1 for
    -inf winners), ties to the lower sorted row."""
    if use_pallas is None:
        use_pallas = interpret or jax.default_backend() == "tpu"
    if not use_pallas:
        return ivf_scan_topk_ref(q_scaled, q_lo, block_tags, row_ids, codes,
                                 probe, k, layout_block)
    if layout_block % tn:
        tn = layout_block                  # shrink: one grid step per slab
    return _pallas_ivf_scan_topk(q_scaled, q_lo, block_tags, row_ids, codes,
                                 probe, k, layout_block=layout_block, tn=tn,
                                 interpret=interpret)


def fine_step_bytes(probe, block_tags, layout_block: int, d: int,
                    code_bytes: int = 1, k: int = 10) -> float:
    """HBM bytes the fused range-scan kernel moves for one query batch
    ``probe (M, P)`` over a layout with ``block_tags (NB,)``.

    Determined by the kernel's BlockSpecs (see ivf_scan.py): per union
    block (a block whose tag some query probes) ``layout_block`` rows of
    codes (``d * code_bytes`` each) and ids (4 each), plus its index and
    tag in SMEM; per run of equal tags along the union the ``(M, d)`` f32
    views, the ``(M, 1)`` offsets and the ``(M, 1)`` membership column;
    the ``(M, lanes(k))`` top-k values and ids written once. Steps past
    the union's end revisit its last tile and DMA nothing. This is the
    fused side of the >= 4x fine-step assertion; the gathered side comes
    from the compiled ``_probe_and_score``'s ``cost_analysis`` via
    ``normalize_cost``.
    """
    probe, block_tags = np.asarray(probe), np.asarray(block_tags)
    m = probe.shape[0]
    tags = block_tags[np.isin(block_tags, probe[probe >= 0])]
    runs = int(np.count_nonzero(np.diff(tags))) + 1 if tags.size else 0
    per_block = layout_block * (d * code_bytes + 4) + 8
    per_run = m * d * 4 + 2 * m * 4
    return float(tags.size * per_block + runs * per_run
                 + 2 * m * lanes(k) * 4 + 4)
