"""Dispatching wrapper for the GP covariance: the compiled Pallas kernel on
TPU (padding to the tile grid), the jnp reference elsewhere.  On CPU only,
REPRO_PALLAS_INTERPRET=1 runs the kernel in interpret mode; a TPU never
interprets and never falls back to the reference."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .gp_cov import matern52_pallas
from .ref import matern52_ref


def matern52(X1, X2, lengthscale: float = 0.3):
    backend = jax.default_backend()
    interpret = (backend == "cpu"
                 and os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1")
    if backend != "tpu" and not interpret:
        return matern52_ref(X1, X2, lengthscale)
    n, m = X1.shape[0], X2.shape[0]
    bn = 128 if n >= 128 else n
    bm = 128 if m >= 128 else m
    pn = (-n) % bn
    pm = (-m) % bm
    X1p = jnp.pad(X1, ((0, pn), (0, 0)))
    X2p = jnp.pad(X2, ((0, pm), (0, 0)))
    K = matern52_pallas(X1p, X2p, lengthscale, interpret=interpret)
    return K[:n, :m]
