"""Device-saturating search benchmark: megabatched distinct-problem
throughput, island-sharded scan scaling, and the tiled dominance kernel.

Four arms, each with hard acceptance gates (asserted, not just
reported):

* ``megabatch`` — ``make_nsga_fused`` dispatches with every lane a
  DISTINCT problem vs every lane the SAME problem (identical statics,
  so identical compiled code — the only difference is the stacked spec
  arrays).  Gates on distinct-problem throughput >= 0.8x the
  same-problem fused batch: fusing different problems must not cost
  more than a whisker over the embarrassing case.
* ``islands`` — the 1-device island mesh vs the plain scan: gates on
  BIT-IDENTICAL outputs (the shard_map wrapper must be free when there
  is nothing to shard), and reports single-device evals/sec.
* ``islands_multi`` — the same search sharded over N islands; reports
  evals/sec vs the 1-device arm and names the platform.  Where several
  devices exist it runs in this process on them (a child could not
  reach an accelerator this process holds); otherwise a child pinned to
  ``JAX_PLATFORMS=cpu`` forces N host devices, which share the same
  cores, so the gate is sanity (the sharded dispatch completes and
  clears a floor), not linear speedup.
* ``pareto_kernel`` — the Pallas dominance-count kernel (compiled on a
  TPU, interpret mode on the CPU) vs the fused-jnp oracle on randomized
  populations with injected duplicate rows: gates on exact count
  equality.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

import repro.core as C
from repro.core.encoding import random_design
from repro.explore.nsga import (ISLAND_AXIS, NSGAConfig, make_nsga,
                                make_nsga_fused)
from repro.kernels.pareto_rank.ref import dominance_counts_ref

from .common import QUICK

OBJECTIVES = ("latency_ns", "cost_usd")
SPACE_KW = dict(max_shape=(16, 16, 4, 4, 1, 2))
ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")


def _problem(name):
    g = C.presets.bert_mms()[name]
    spec = C.SystemSpec.build(g, ch_max=2)
    return spec, C.DesignSpace(spec, **SPACE_KW)


def _pop0(space, pop, key):
    return jax.vmap(lambda k: random_design(k, space))(
        jax.random.split(key, pop))


def _time_dispatches(fn, repeat):
    """Min wall seconds per call over ``repeat`` post-warmup calls."""
    jax.block_until_ready(fn(0))            # compile
    best = float("inf")
    for i in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(i + 1))
        best = min(best, time.perf_counter() - t0)
    return best


def _megabatch_arm(cfg, lanes, repeat):
    names = ["att1", "att2", "att3", "att4"]
    probs = [_problem(names[i % len(names)]) for i in range(lanes)]
    spec0, space0 = probs[0]
    run = make_nsga_fused(spec0, space0, OBJECTIVES, cfg, lanes=lanes)
    pops = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[_pop0(space0, cfg.pop, jax.random.PRNGKey(100 + i))
          for i in range(lanes)])
    # keys built OUTSIDE the timed region: host-side PRNGKey construction
    # is identical for both arms and would only add noise to ms-scale
    # dispatches
    keys = [jax.random.PRNGKey(j) for j in range(lanes)]
    same = [spec0.arrays] * lanes
    distinct = [p[0].arrays for p in probs]
    repeat = max(repeat, 8)         # ms-scale dispatches: min-of-few is
    #                                 too noisy for a throughput gate
    t_same = _time_dispatches(lambda i: run(keys, pops, same), repeat)
    t_distinct = _time_dispatches(
        lambda i: run(keys, pops, distinct), repeat)
    evals = lanes * cfg.pop * cfg.generations
    thr_same, thr_distinct = evals / t_same, evals / t_distinct
    ratio = thr_distinct / thr_same
    assert ratio >= 0.8, (
        f"megabatched DISTINCT problems reached only {ratio:.2f}x the "
        f"fused same-problem batch throughput (gate: >= 0.8x)")
    return dict(thr_same=thr_same, thr_distinct=thr_distinct, ratio=ratio)


def _island_arm(cfg, repeat):
    spec, space = _problem("att2")
    key, pop0 = jax.random.PRNGKey(0), _pop0(space, cfg.pop,
                                             jax.random.PRNGKey(1))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), (ISLAND_AXIS,))
    plain = make_nsga(spec, space, OBJECTIVES, cfg)
    isl = make_nsga(spec, space, OBJECTIVES, cfg, mesh=mesh)
    a, b = plain(key, pop0), isl(key, pop0)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            "1-device island mesh is NOT bit-identical to the plain scan")
    t = _time_dispatches(
        lambda i: isl(jax.random.PRNGKey(i), pop0), repeat)
    return dict(evals_per_s=cfg.pop * cfg.generations / t)


def sharded_evals_per_s(cfg, devices, repeat):
    """Best-of-``repeat`` evals/sec of the scan sharded as one island per
    device of ``devices``."""
    spec, space = _problem("att2")
    mesh = jax.sharding.Mesh(np.array(devices), (ISLAND_AXIS,))
    cfg = dataclasses.replace(cfg, migration_interval=2)
    pop0 = _pop0(space, cfg.pop, jax.random.PRNGKey(1))
    run = make_nsga(spec, space, OBJECTIVES, cfg, mesh=mesh)
    t = _time_dispatches(lambda i: run(jax.random.PRNGKey(i), pop0), repeat)
    return cfg.pop * cfg.generations / t


def _multi_island_arm(cfg, n_dev, repeat):
    """Evals/sec of the scan sharded over several devices: in this process
    over the real devices when more than one exists (one process holds an
    accelerator), else in a child pinned to the CPU with ``n_dev`` forced
    host devices."""
    devs = jax.devices()
    if len(devs) > 1:
        n = min(n_dev, len(devs))
        return dict(evals_per_s=sharded_evals_per_s(cfg, devs[:n], repeat),
                    n_dev=n, platform=devs[0].platform)
    prog = textwrap.dedent(f"""
        import jax
        from benchmarks.bench_scale import sharded_evals_per_s
        from repro.explore.nsga import NSGAConfig
        assert len(jax.devices()) == {n_dev}
        cfg = NSGAConfig(pop={cfg.pop}, generations={cfg.generations})
        print("EVALS_PER_S",
              sharded_evals_per_s(cfg, jax.devices(), {repeat}))
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_dev}")
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [SRC, str(ROOT), env.get("PYTHONPATH")]))
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    eps = float(r.stdout.split("EVALS_PER_S")[1].strip().split()[0])
    assert eps > 0
    return dict(evals_per_s=eps, n_dev=n_dev, platform="cpu")


def _pareto_kernel_arm(n, k, repeat):
    """Kernel vs oracle parity and timing: compiled on a TPU, in
    interpret mode on the CPU."""
    interpret = jax.default_backend() == "cpu"
    if interpret:
        os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    try:
        from repro.kernels.pareto_rank.ops import dominance_counts
        ks = jax.random.split(jax.random.PRNGKey(17), 2)
        objs = jax.random.normal(ks[0], (n, k))
        objs = objs.at[n // 2:n // 2 + 8].set(objs[:8])     # exact ties
        valid = jax.random.bernoulli(ks[1], 0.8, (n,))
        got = dominance_counts(objs, valid)
        ref = dominance_counts_ref(objs, valid)
        assert np.array_equal(np.asarray(got), np.asarray(ref)), (
            "pareto_rank kernel counts diverge from the jnp oracle")
        best = float("inf")
        for _ in range(repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(dominance_counts(objs, valid))
            best = min(best, time.perf_counter() - t0)
        return dict(us=best * 1e6, n=n,
                    mode="interpret" if interpret else "compiled")
    finally:
        if interpret:
            os.environ.pop("REPRO_PALLAS_INTERPRET", None)


def run(quick: bool = QUICK):
    cfg = NSGAConfig(pop=8 if quick else 16,
                     generations=2 if quick else 4)
    lanes = 4 if quick else 8
    repeat = 2 if quick else 5
    n_dev = 2 if quick else 4

    mb = _megabatch_arm(cfg, lanes, repeat)
    one = _island_arm(cfg, repeat)
    multi = _multi_island_arm(cfg, n_dev, repeat)
    pk = _pareto_kernel_arm(256 if quick else 1024, 4, repeat)

    scaling = multi["evals_per_s"] / one["evals_per_s"]
    return [
        dict(name="scale_megabatch_distinct",
             us_per_call=1e6 * lanes * cfg.pop * cfg.generations
             / mb["thr_distinct"],
             derived=f"ratio_vs_same={mb['ratio']:.2f}"),
        dict(name="scale_islands_1dev",
             us_per_call=1e6 * cfg.pop * cfg.generations
             / one["evals_per_s"],
             derived="bit_identical=1"),
        dict(name=f"scale_islands_{multi['n_dev']}dev",
             us_per_call=1e6 * cfg.pop * cfg.generations
             / multi["evals_per_s"],
             derived=f"platform={multi['platform']};"
                     f"scaling_vs_1dev={scaling:.2f}"),
        dict(name="scale_pareto_kernel", us_per_call=pk["us"],
             derived=f"n={pk['n']};mode={pk['mode']};parity=1"),
    ]


if __name__ == "__main__":
    print("name,us_per_call,derived")
    from .common import emit
    emit(run())
