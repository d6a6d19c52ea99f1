"""Pallas TPU kernel for the Mamba-1 selective scan (chunked).

Grid = (B, num_chunks); the chunk dimension is innermost/sequential on TPU,
so the running SSM state ``h`` lives in a VMEM scratch that persists across
chunks.  Each grid step loads a (chunk, Di) tile of u/delta and a
(chunk, Ds) tile of B/C into VMEM, then walks the chunk with a fori_loop of
fully-vectorized (Di, Ds) updates — sequential in time (the recurrence is
inherently sequential) but wide on the VPU lanes.

This is the TPU-native adaptation: instead of the GPU kernel's
warp-parallel prefix scan, we exploit the (Di x Ds) vector width per step
and the VMEM-resident state across the whole sequence (HBM traffic is
O(S*(Di+Ds)) for inputs + O(S*Di) outputs; the h state never leaves VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _scan_kernel(u_ref, d_ref, A_ref, b_ref, c_ref, h0_ref,
                 y_ref, hT_ref, h_ref, *, chunk: int, num_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    A = A_ref[...]                                   # (Di, Ds)

    def body(t, h):
        u_t = u_ref[0, t, :]                         # (Di,)
        d_t = d_ref[0, t, :]                         # (Di,)
        b_t = b_ref[0, t, :]                         # (Ds,)
        c_t = c_ref[0, t, :]                         # (Ds,)
        dA = jnp.exp(d_t[:, None] * A)               # (Di, Ds)
        h = dA * h + (d_t * u_t)[:, None] * b_t[None, :]
        y_ref[0, t, :] = jnp.sum(h * c_t[None, :], axis=1)
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == num_chunks - 1)
    def _finish():
        hT_ref[0] = h


def selective_scan_pallas(u, delta, A, Bc, Cc, h0=None,
                          chunk: int = DEFAULT_CHUNK,
                          interpret: bool = False):
    """u/delta: (B, S, Di); A: (Di, Ds); Bc/Cc: (B, S, Ds).
    Returns (y (B, S, Di), h_T (B, Di, Ds)), float32.  S % chunk == 0."""
    B, S, Di = u.shape
    Ds = A.shape[1]
    ch = min(chunk, S)
    nc = S // ch
    if h0 is None:
        h0 = jnp.zeros((B, Di, Ds), jnp.float32)

    kernel = functools.partial(_scan_kernel, chunk=ch, num_chunks=nc)
    y, hT = pl.pallas_call(
        kernel,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, ch, Di), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, ch, Di), lambda b, c: (b, c, 0)),
            pl.BlockSpec((Di, Ds), lambda b, c: (0, 0)),
            pl.BlockSpec((1, ch, Ds), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, ch, Ds), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Di, Ds), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, ch, Di), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Di, Ds), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Di), jnp.float32),
            jax.ShapeDtypeStruct((B, Di, Ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Di, Ds), jnp.float32)],
        interpret=interpret,
    )(u.astype(jnp.float32), delta.astype(jnp.float32),
      A.astype(jnp.float32), Bc.astype(jnp.float32), Cc.astype(jnp.float32),
      h0.astype(jnp.float32))
    return y, hT
