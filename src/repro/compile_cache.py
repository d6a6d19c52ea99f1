"""JAX's persistent compilation cache, kept at one fixed place.

A cold design query spends much of its time compiling the NSGA scan, so
every entry point (``chip_smoke.py``, ``benchmarks.run``, the
``repro.serve.worker`` and ``repro.calib.fit`` CLIs) calls ``enable()``
from its ``main``.  Importing this module turns nothing on, and tests never
call ``enable()``.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
directory is set in code.  Otherwise the cache lives at the fixed
``<checkout>/artifacts/jax_cache`` (``artifacts/`` is gitignored): the
directory is part of what a later process must find again, so it never
comes from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / "artifacts" / "jax_cache"


def cache_dir() -> Path:
    """The directory ``enable()`` uses: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else ``DEFAULT_DIR``.  Pure: touches neither JAX nor the disk."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else DEFAULT_DIR


def enable() -> Path:
    """Turn on the persistent compilation cache for this process and
    return its directory."""
    import jax

    d = cache_dir()
    d.mkdir(parents=True, exist_ok=True)
    if not os.environ.get(ENV_VAR):     # else JAX reads the variable itself
        jax.config.update("jax_compilation_cache_dir", str(d))
    return d
