"""Dispatching wrapper for the dominance-count kernel: the compiled Pallas
kernel on TPU (padding the pool to the tile grid; padded rows are invalid
dominators and their counts are sliced off), the jnp reference elsewhere.
On CPU only, REPRO_PALLAS_INTERPRET=1 runs the kernel in interpret mode —
how the CPU tests exercise the Pallas path.  A TPU never interprets and
never falls back to the reference."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .pareto_rank import dominance_counts_pallas
from .ref import dominance_counts_ref


def dominance_counts(objs, valid, block: int = 128):
    backend = jax.default_backend()
    interpret = (backend == "cpu"
                 and os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1")
    if backend != "tpu" and not interpret:
        return dominance_counts_ref(objs, valid)
    n = objs.shape[0]
    b = min(block, n)
    pn = (-n) % b
    objs_p = jnp.pad(objs, ((0, pn), (0, 0)))
    valid_p = jnp.pad(valid.astype(bool), (0, pn))      # padding rows can
    #                                                     never dominate
    counts = dominance_counts_pallas(objs_p, valid_p, block=b,
                                     interpret=interpret)
    return counts[:n]
