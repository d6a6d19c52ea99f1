"""Dispatching wrapper for the selective scan.

* TPU -> chunked Pallas kernel.
* elsewhere -> associative-scan jnp path: the linear recurrence
  h_t = a_t h_{t-1} + b_t composes associatively ((a1,b1)o(a2,b2) =
  (a1 a2, b1 a2 + b2)), so ``jax.lax.associative_scan`` gives an O(log S)
  depth program — the right lowering for CPU/dry-run and the second
  correctness reference against ``ref.py``.
* on CPU only, ``REPRO_PALLAS_INTERPRET=1`` forces the Pallas kernel in
  interpret mode.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .mamba_scan import selective_scan_pallas


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def selective_scan_assoc(u, delta, A, Bc, Cc, h0=None):
    """Associative-scan formulation (parallel prefix over S)."""
    B, S, Di = u.shape
    dA = jnp.exp(delta[..., None] * A[None, None])          # (B,S,Di,Ds)
    dBu = (delta * u)[..., None] * Bc[:, :, None, :]        # (B,S,Di,Ds)
    if h0 is not None:
        # fold h0 into the first element: h_1 = dA_1 h0 + dBu_1
        dBu = dBu.at[:, 0].add(dA[:, 0] * h0)

    def combine(l, r):
        a1, b1 = l
        a2, b2 = r
        return a1 * a2, b1 * a2 + b2

    _, hh = jax.lax.associative_scan(combine, (dA, dBu), axis=1)
    y = jnp.sum(hh * Cc[:, :, None, :], axis=-1)            # (B,S,Di)
    return y, hh[:, -1]


def selective_scan(u, delta, A, Bc, Cc, h0=None):
    if (jax.default_backend() == "cpu"
            and os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1"):
        return selective_scan_pallas(u, delta, A, Bc, Cc, h0, interpret=True)
    if _use_pallas():
        return selective_scan_pallas(u, delta, A, Bc, Cc, h0)
    return selective_scan_assoc(u, delta, A, Bc, Cc, h0)
