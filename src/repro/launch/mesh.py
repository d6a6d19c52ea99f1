"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init; the dry-run sets
XLA_FLAGS before any import).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — run under "
            "launch/dryrun.py (it forces 512 host devices)")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples on CPU)."""
    devs = jax.devices()
    mp = max(1, min(model_parallel, len(devs)))
    dp = len(devs) // mp
    arr = np.asarray(devs[: dp * mp]).reshape(dp, mp)
    return Mesh(arr, ("data", "model"))
