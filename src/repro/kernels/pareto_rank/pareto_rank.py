"""Pallas TPU kernel: tiled Pareto dominance counts.

NSGA environmental selection and every archive insertion rank a pool by
its dominance counts — the only O(n^2) step on the search path.  The jnp
reference materializes the fused (n, n, k) comparison tensor; this kernel
tiles it into (bi, bj) VMEM blocks and accumulates the dominator count
over the ``i`` (candidate-dominator) grid dimension, so peak memory is
O(block^2) however large the pool grows.

Layout (what Mosaic accepts): every value is a 2-D tile.  Dominators come
in row-major as (bi, k) with their validity mask as (bi, 1); the dominated
side comes in TRANSPOSED as (k, bj), so each objective ``c`` compares a
(bi, 1) column against a (1, bj) row into one (bi, bj) tile (a static
loop over ``k``).  The per-column count is a sublane reduction
(``keepdims``) into a lane-dense (1, bj) output block of a (1, n) array.

Grid layout: ``(n/bj, n/bi)`` with the reduction dimension LAST, so every
revisit of one output block is contiguous and the accumulator never
leaves VMEM between visits (init on ``i == 0`` via ``pl.when``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 128


def _rank_kernel(oi_ref, vi_ref, ojt_ref, c_ref):
    i = pl.program_id(1)                          # reduction position
    oi = oi_ref[...]                              # (bi, k) the dominators
    ojt = ojt_ref[...]                            # (k, bj) the dominated
    le = lt = None
    for c in range(oi.shape[1]):
        a = oi[:, c:c + 1]                        # (bi, 1)
        b = ojt[c:c + 1, :]                       # (1, bj)
        le = a <= b if le is None else le & (a <= b)
        lt = a < b if lt is None else lt | (a < b)
    dom = jnp.where(le & lt, vi_ref[...], 0.0)    # mask broadcasts (bi, 1)
    acc = jnp.sum(dom, axis=0, keepdims=True)     # (1, bj)

    @pl.when(i == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] += acc


def dominance_counts_pallas(objs, valid, block: int = BLOCK,
                            interpret: bool = False):
    """``objs``: (n, k) float32; ``valid``: (n,) — n must divide by
    ``block`` (the ops wrapper pads).  Returns (n,) int32 dominance
    counts, matching ``ref.dominance_counts_ref``.  Counts accumulate in
    float32, exact for any pool below 2^24 rows."""
    n, k = objs.shape
    b = min(block, n)
    assert n % b == 0
    o = objs.astype(jnp.float32)
    vf = valid.astype(jnp.float32).reshape(n, 1)
    counts = pl.pallas_call(
        _rank_kernel,
        grid=(n // b, n // b),
        in_specs=[
            pl.BlockSpec((b, k), lambda j, i: (i, 0)),
            pl.BlockSpec((b, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((k, b), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, b), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(o, vf, o.T)
    return counts[0].astype(jnp.int32)
