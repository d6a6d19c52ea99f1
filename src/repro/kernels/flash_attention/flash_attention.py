"""Pallas TPU flash-attention kernel (blockwise online softmax).

Canonical 3-D grid formulation: grid = (B * H, num_q_blocks, num_kv_blocks);
the kv-block dimension is innermost so the VMEM scratch accumulators
(running max m, running sum l, output accumulator acc) persist across it
(TPU executes the grid sequentially per core).  BlockSpecs tile Q/K/V into
MXU-aligned (block, head_dim) VMEM tiles; GQA is handled in the index maps
(query head h reads kv head h // (H // KV)) so KV tiles are never
materialized per-query-head in HBM.

Masking (causal / sliding window / cache-validity) is applied blockwise;
fully-masked kv blocks still execute but contribute zeros — block skipping
is a grid-shape optimization left to the caller.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = float(jnp.finfo(jnp.float32).min)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 block_q: int, block_k: int, sm_scale: float,
                 mask_kind: str, window: int, kv_valid_len, num_kv_blocks,
                 q_offset):
    """One (q_block, kv_block) step of online-softmax attention."""
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_ids = (pl.program_id(1) * block_q
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
             + q_offset)
    k_ids = (kv_i * block_k
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
    mask = jnp.ones((block_q, block_k), bool)
    if kv_valid_len is not None:
        mask &= k_ids < kv_valid_len
    if mask_kind in ("causal", "window"):
        mask &= k_ids <= q_ids
    if mask_kind == "window":
        mask &= (q_ids - k_ids) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (m == -inf) against NaNs
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.where(mask, jnp.exp(s - safe_m), 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - safe_m))
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_i == num_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, mask_kind: str = "causal",
                           window: int = 0,
                           kv_valid_len: Optional[int] = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False):
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D).  Returns (B, Sq, H, D).

    Requires Sq % block_q == 0 and Sk % block_k == 0 (the ops wrapper pads).
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]                  # may differ from D (MLA)
    rep = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq = Sq // bq
    nk = Sk // bk
    q_offset = (kv_valid_len - Sq) if kv_valid_len is not None else 0

    qt = q.transpose(0, 2, 1, 3)                         # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)                         # (B, KV, Sk, D)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _attn_kernel, block_q=bq, block_k=bk,
        sm_scale=1.0 / math.sqrt(D), mask_kind=mask_kind, window=window,
        kv_valid_len=kv_valid_len, num_kv_blocks=nk, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D),
                         lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda bh, qi, ki: (bh // H, (bh % H) // rep, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda bh, qi, ki: (bh // H, (bh % H) // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv),
                               lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, Dv), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
