"""Dispatching wrapper for flash attention.

* TPU backend -> the Pallas kernel (``flash_attention.py``).
* other backends (this CPU container, dry-runs) -> a *blocked* jnp
  implementation with the same online-softmax structure: ``lax.scan`` over
  KV blocks, O(S * block) live memory, identical FLOP count — so the
  compiled dry-run's cost/memory analysis reflects the kernelized program,
  not a naive O(S^2)-materialized one.
* on CPU only, ``REPRO_PALLAS_INTERPRET=1`` forces the Pallas kernel in
  interpret mode (kernel-correctness tests).
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_pallas

BLOCK_K = 512
NEG_INF = float(jnp.finfo(jnp.float32).min)


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _force_interpret() -> bool:
    return (jax.default_backend() == "cpu"
            and os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1")


def flash_attention_blocked(q, k, v, mask_kind: str = "causal",
                            window: int = 0,
                            kv_valid_len: Optional[int] = None,
                            block_k: int = BLOCK_K):
    """Online-softmax attention, scanning KV blocks (jnp reference path)."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]                  # may differ from D (MLA)
    rep = H // KV
    bk = min(block_k, Sk)
    pad = (-Sk) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = (Sk + pad) // bk

    qf = q.astype(jnp.float32) * (1.0 / math.sqrt(D))
    q_pos = (jnp.arange(Sq) if kv_valid_len is None
             else kv_valid_len - Sq + jnp.arange(Sq))
    valid_len = Sk if kv_valid_len is None else kv_valid_len

    kb = k.reshape(B, nk, bk, KV, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, bk, KV, Dv).transpose(1, 0, 2, 3, 4)

    def step(carry, xs):
        m, l, acc = carry
        ki, kblk, vblk = xs
        kf = jnp.repeat(kblk, rep, axis=2).astype(jnp.float32)
        vf = jnp.repeat(vblk, rep, axis=2).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
        k_ids = ki * bk + jnp.arange(bk)
        mask = k_ids[None, :] < valid_len
        if mask_kind in ("causal", "window"):
            mask = mask & (k_ids[None, :] <= q_pos[:, None])
        if mask_kind == "window":
            mask = mask & (q_pos[:, None] - k_ids[None, :] < window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(mask[None, None], jnp.exp(s - safe), 0.0)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - safe))
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha[..., 0][..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vf)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq, 1), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (jnp.arange(nk), kb, vb))
    out = acc / jnp.maximum(l, 1e-20)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# custom VJP: the flash-attention backward recomputes the per-block
# probabilities instead of letting scan stack them (without this, each
# attention op saves O(S^2) f32 residuals for autodiff — the whisper train
# cell hit 37 GB/device of stacked probabilities; see EXPERIMENTS.md §Perf).
# ---------------------------------------------------------------------------
def _fwd_with_lse(q, k, v, mask_kind, window, kv_valid_len, block_k):
    """Blocked forward that also returns the log-sum-exp per query row."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    rep = H // KV
    bk = min(block_k, Sk)
    pad = (-Sk) % bk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    nk = (Sk + pad) // bk
    qf = q.astype(jnp.float32) * (1.0 / math.sqrt(D))
    q_pos = (jnp.arange(Sq) if kv_valid_len is None
             else kv_valid_len - Sq + jnp.arange(Sq))
    valid_len = Sk if kv_valid_len is None else kv_valid_len
    kb = kp.reshape(B, nk, bk, KV, D).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nk, bk, KV, Dv).transpose(1, 0, 2, 3, 4)

    def blk_mask(ki):
        k_ids = ki * bk + jnp.arange(bk)
        m = k_ids[None, :] < valid_len
        if mask_kind in ("causal", "window"):
            m = m & (k_ids[None, :] <= q_pos[:, None])
        if mask_kind == "window":
            m = m & (q_pos[:, None] - k_ids[None, :] < window)
        return m

    def step(carry, xs):
        m, l, acc = carry
        ki, kblk, vblk = xs
        kf = jnp.repeat(kblk, rep, axis=2).astype(jnp.float32)
        vf = jnp.repeat(vblk, rep, axis=2).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
        mask = blk_mask(ki)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(mask[None, None], jnp.exp(s - safe), 0.0)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - safe))
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha[..., 0][..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vf)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq, 1), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (jnp.arange(nk), kb, vb))
    lse = (m + jnp.log(jnp.maximum(l, 1e-20)))[..., 0]       # (B,H,Sq)
    out = (acc / jnp.maximum(l, 1e-20)).transpose(0, 2, 1, 3)
    return out.astype(q.dtype), lse, blk_mask, (kb, vb, nk, bk, rep)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fa_diff(q, k, v, mask_kind, window, kv_valid_len, block_k):
    out, _, _, _ = _fwd_with_lse(q, k, v, mask_kind, window, kv_valid_len,
                                 block_k)
    return out


def _fa_diff_fwd(q, k, v, mask_kind, window, kv_valid_len, block_k):
    out, lse, _, _ = _fwd_with_lse(q, k, v, mask_kind, window, kv_valid_len,
                                   block_k)
    return out, (q, k, v, out, lse)


def _fa_diff_bwd(mask_kind, window, kv_valid_len, block_k, res, do):
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    rep = H // KV
    bk = min(block_k, Sk)
    pad = (-Sk) % bk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    nk = (Sk + pad) // bk
    scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32).transpose(0, 2, 1, 3)        # (B,H,Sq,Dv)
    outf = out.astype(jnp.float32).transpose(0, 2, 1, 3)
    delta = jnp.sum(dof * outf, axis=-1)                      # (B,H,Sq)
    q_pos = (jnp.arange(Sq) if kv_valid_len is None
             else kv_valid_len - Sq + jnp.arange(Sq))
    valid_len = Sk if kv_valid_len is None else kv_valid_len
    kb = kp.reshape(B, nk, bk, KV, D).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nk, bk, KV, Dv).transpose(1, 0, 2, 3, 4)

    def step(dq, xs):
        ki, kblk, vblk = xs
        kf = jnp.repeat(kblk, rep, axis=2).astype(jnp.float32)
        vf = jnp.repeat(vblk, rep, axis=2).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf * scale, kf)
        k_ids = ki * bk + jnp.arange(bk)
        mask = k_ids[None, :] < valid_len
        if mask_kind in ("causal", "window"):
            mask = mask & (k_ids[None, :] <= q_pos[:, None])
        if mask_kind == "window":
            mask = mask & (q_pos[:, None] - k_ids[None, :] < window)
        p = jnp.where(mask[None, None], jnp.exp(s - lse[..., None]), 0.0)
        dv_b = jnp.einsum("bhqk,bhqd->bkhd", p, dof)          # (B,bk,H,Dv)
        dp = jnp.einsum("bhqd,bkhd->bhqk", dof, vf)
        ds = p * (dp - delta[..., None])                      # (B,H,Sq,bk)
        dq = dq + scale * jnp.einsum("bhqk,bkhd->bqhd", ds, kf)
        dk_b = scale * jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        # GQA: fold query-head groups back onto their kv head
        dv_b = dv_b.reshape(B, bk, KV, rep, Dv).sum(3)
        dk_b = dk_b.reshape(B, bk, KV, rep, D).sum(3)
        return dq, (dk_b, dv_b)

    dq0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    dq, (dk_s, dv_s) = jax.lax.scan(step, dq0, (jnp.arange(nk), kb, vb))
    dk = dk_s.transpose(1, 0, 2, 3, 4).reshape(B, nk * bk, KV, D)[:, :Sk]
    dv = dv_s.transpose(1, 0, 2, 3, 4).reshape(B, nk * bk, KV, Dv)[:, :Sk]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_fa_diff.defvjp(_fa_diff_fwd, _fa_diff_bwd)


def flash_attention(q, k, v, mask_kind: str = "causal", window: int = 0,
                    kv_valid_len=None):
    """Public op.

    * static kv_valid_len (train / prefill): differentiable custom-VJP
      blocked path (backward recomputes probabilities per kv block);
    * traced kv_valid_len (decode): plain blocked path (never
      differentiated);
    * TPU backend / REPRO_PALLAS_INTERPRET: the Pallas kernel.
    """
    if _force_interpret():
        static_len = int(kv_valid_len) if kv_valid_len is not None else None
        return flash_attention_pallas(q, k, v, mask_kind, window,
                                      static_len, interpret=True)
    if _use_pallas() and (kv_valid_len is None
                          or isinstance(kv_valid_len, int)):
        return flash_attention_pallas(q, k, v, mask_kind, window,
                                      kv_valid_len)
    if kv_valid_len is None or isinstance(kv_valid_len, int):
        return _fa_diff(q, k, v, mask_kind, window, kv_valid_len, BLOCK_K)
    if q.shape[1] == 1:
        # single-token decode: dense (unscanned) attention so a
        # seq-sharded KV cache reduces via DISTRIBUTED partial softmax
        # (flash-decoding) instead of being all-gathered around the
        # sequential kv-block scan — see EXPERIMENTS.md §Perf
        from .ref import attention_ref
        return attention_ref(q, k, v, mask_kind, window, kv_valid_len)
    return flash_attention_blocked(q, k, v, mask_kind, window, kv_valid_len)
