"""Bring-up plumbing: where the persistent compilation cache goes, and
``chip_smoke.py`` refusing to run without a TPU."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_compile_cache_honours_env_var(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "jc"))
    assert compile_cache.cache_dir() == tmp_path / "jc"


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    d = compile_cache.cache_dir()
    assert d == ROOT / "artifacts" / "jax_cache"
    assert d == compile_cache.cache_dir()       # no pid, time or temp name


def _child_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [str(ROOT / "src"), env.get("PYTHONPATH")]))
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


@pytest.mark.parametrize("from_env", [True, False])
def test_enable_points_jax_at_the_cache_dir(tmp_path, from_env):
    """``enable()`` (run in a child, so this process never turns the cache
    on) leaves JAX's cache directory at ``cache_dir()``."""
    extra = {compile_cache.ENV_VAR: str(tmp_path / "jc")} if from_env \
        else {}
    prog = ("import jax; from repro.compile_cache import enable; "
            "d = enable(); print(d); "
            "print(jax.config.jax_compilation_cache_dir)")
    r = subprocess.run([sys.executable, "-c", prog], env=_child_env(**extra),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    got, jax_dir = r.stdout.split()[-2:]
    want = tmp_path / "jc" if from_env else compile_cache.DEFAULT_DIR
    assert Path(got) == want and Path(jax_dir) == want
    assert want.is_dir()


def test_chip_smoke_fails_without_a_tpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=_child_env(), cwd=ROOT, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout
