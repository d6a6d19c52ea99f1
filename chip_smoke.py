#!/usr/bin/env python3
"""Smoke test of the design-search path on a TPU.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the island-sharded search

One process drives the chip through the entry points a user calls
(``repro.api.Session``, ``repro.serve.Executor``) at a realistic size:
the ``attn_qwen2_72b`` graph at ``ch_max=4`` under a 4096-PE budget, a
1024-design population and a 32768-evaluation query.  One chip runs
these phases:

* device          — platform, kind and count; fails without a TPU
* golden          — the fixed designs of ``tests/test_golden_metrics.py``
                    match their frozen metric vectors
* kernel          — the compiled Pallas dominance kernel equals the jnp
                    reference exactly on pools with ties
* cold            — a served query from an empty cache, compile and
                    execute seconds apart; its front re-evaluated on the
                    TPU and on the CPU backend agrees
* warm / refine   — the same query is a cache hit; a larger budget
                    spends new evaluations
* executor        — one job through ``repro.serve.Executor`` reaches DONE
* bo_sa           — the paper's BO x SA engine at the Fig. 7 settings

``--chips 4`` runs the same cold query over a four-island mesh, checks
the island count, the population's sharding and the front's metrics, and
compares its hypervolume with a one-device mesh.  Every phase prints one
line naming the device.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failure exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
WORKLOAD = "attn_qwen2_72b"
EXECUTOR_WORKLOAD = "attn_qwen2_5_32b"     # equal padded dims: same scan
OBJECTIVES = ("latency_ns", "energy_pj", "cost_usd")
CH_MAX = 4
SPACE = dict(max_total_pes=4096)
POP = 1024
BUDGET = 32768
EXECUTOR_BUDGET = 8192
KERNEL_POOLS = ((2048, 3), (2500, 4))      # (rows, objectives)
BO_SA = dict(n_init=4, n_iter=4, sa_steps=100)
RTOL = 1e-4
CACHE = ROOT / "artifacts" / "chip_smoke"

# the CPU backend re-evaluates fronts beside the TPU in this process
if os.environ.get("JAX_PLATFORMS") and \
        "cpu" not in os.environ["JAX_PLATFORMS"].split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

TAG = ""


def log(phase: str, msg: str) -> None:
    print(f"[{TAG}] {phase}: {msg}", flush=True)


class CompileClock:
    """Seconds JAX spent compiling while active: the union of its own
    tracing, lowering and backend-compile (or compile-cache load)
    intervals, so nested jits count once."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.spans = []
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event in self.EVENTS:
            end = time.perf_counter()
            self.spans.append((end - duration, end))

    def run(self, fn):
        """``(fn(), wall_s, compile_s)``; ``fn`` must return host data."""
        self.spans, self.active = [], True
        t0 = time.perf_counter()
        try:
            out = jax.block_until_ready(fn())
        finally:
            self.active = False
        wall = time.perf_counter() - t0
        busy, edge = 0.0, t0
        for a, b in sorted(self.spans):
            a = max(a, edge)
            if b > a:
                busy, edge = busy + b - a, b
        return out, wall, busy


def device():
    """The first device, which must be a TPU."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — JAX's first device is "
                 f"{d.platform} ({d.device_kind}); run this on a TPU host")
    return d, len(devs)


def rel_err(got, want):
    """Largest relative error per column."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30),
                  axis=0)


def evaluate_on(dev, spec, designs):
    """``METRIC_KEYS`` rows of ``designs`` evaluated on device ``dev``."""
    from repro.core.evaluate import evaluate_system
    from repro.core.optimizer import metric_stack
    batch = jax.tree.map(lambda *xs: np.stack(xs), *designs)
    batch = jax.device_put(batch, dev)
    f = jax.jit(jax.vmap(lambda d: metric_stack(evaluate_system(spec, d))))
    out = f(batch)
    assert out.devices() == {dev}, out.devices()
    return np.asarray(out, np.float64)


def check_front(phase, r, spec, tpu, cpu):
    """Re-evaluate a served front on one TPU device and on the CPU
    backend: those two, and the metrics the front was served with, agree
    within ``RTOL`` on every metric."""
    from repro.core.optimizer import METRIC_KEYS
    assert len(r.front_designs) > 0, "empty front"
    on_tpu = evaluate_on(tpu, spec, r.front_designs)
    on_cpu = evaluate_on(cpu, spec, r.front_designs)
    assert np.all(np.isfinite(on_tpu)), "non-finite metrics"
    e_cpu = rel_err(on_tpu, on_cpu)
    e_srv = rel_err(r.front_metrics, on_tpu)
    log(phase, f"front of {len(on_tpu)} re-evaluated; max rel err tpu vs "
        "cpu " + ", ".join(f"{k}={v:.3g}" for k, v in zip(METRIC_KEYS, e_cpu))
        + f"; served vs tpu {e_srv.max():.3g} (limit {RTOL})")
    assert np.all(e_cpu <= RTOL), e_cpu
    assert np.all(e_srv <= RTOL), e_srv


def problem(name=WORKLOAD):
    from repro.api import Problem
    from repro.core.presets import workload_library
    return Problem(workload_library()[name], objectives=OBJECTIVES,
                   ch_max=CH_MAX, space_kwargs=dict(SPACE))


def session(cache_dir, mesh=None):
    from repro.api import Session
    from repro.explore.nsga import NSGAConfig
    from repro.explore.service import ExplorationService
    return Session(service=ExplorationService(
        cache_dir=cache_dir, nsga=NSGAConfig(pop=POP), mesh=mesh))


def submit_timed(clock, phase, sess, q):
    r, wall, comp = clock.run(
        lambda: sess.submit(q, key=jax.random.PRNGKey(SEED)))
    pv = r.provenance
    ex = wall - comp
    log(phase, f"from_cache={pv.from_cache} n_evals_run={pv.n_evals_run} "
        f"front={len(r.front_objs)} wall_s={wall:.3f} compile_s={comp:.3f} "
        f"execute_s={ex:.3f} evals_per_s="
        f"{pv.n_evals_run / ex if ex > 0 else float('nan'):.1f}")
    return r


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def phase_golden(tpu):
    sys.path.insert(0, str(ROOT / "tests"))
    from test_golden_metrics import GOLDEN, _fixed_design, _graph

    import repro.core as C
    for name in sorted(GOLDEN):
        spec = C.SystemSpec.build(_graph(name), ch_max=2)
        got = evaluate_on(tpu, spec, [_fixed_design(spec)])[0]
        err = rel_err(got[None], np.asarray(GOLDEN[name])[None]).max()
        log("golden", f"{name} max rel err {err:.3g} (limit {RTOL})")
        np.testing.assert_allclose(got, GOLDEN[name], rtol=RTOL)


def phase_kernel(tpu, cpu):
    import jax.numpy as jnp

    from repro.kernels.pareto_rank import ops
    from repro.kernels.pareto_rank.ref import dominance_counts_ref
    f = jax.jit(ops.dominance_counts)
    ref = jax.jit(dominance_counts_ref)
    for n, k in KERNEL_POOLS:
        k1, k2 = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(SEED), n))
        # a coarse grid gives exact per-objective ties; duplicated rows
        # give whole-row ties
        objs = jnp.round(jax.random.normal(k1, (n, k)) * 4) / 4
        objs = objs.at[n // 2:n // 2 + 64].set(objs[:64])
        valid = jax.random.bernoulli(k2, 0.9, (n,))
        objs, valid = jax.device_put((objs, valid), tpu)
        hlo = f.lower(objs, valid).compile().as_text()
        assert "tpu_custom_call" in hlo, "kernel did not compile to Mosaic"
        got = np.asarray(f(objs, valid))
        want = np.asarray(ref(*jax.device_put((objs, valid), cpu)))
        log("kernel", f"pool {n}x{k}: compiled Pallas kernel, "
            f"{int(np.sum(got == 0))} nondominated, "
            f"{int(np.sum(got != want))} mismatches vs jnp reference on cpu")
        assert np.array_equal(got, want)


def phase_served(clock, tpu, cpu):
    from repro.api import Query
    from repro.serve import Executor
    from repro.serve.jobs import DONE

    sess = session(CACHE / "explore_cache")
    p = problem()
    q = Query(p, budget=BUDGET)
    plan = sess.plan(q)
    log("cold", f"plan: cache_hit={plan.cache_hit} islands={plan.islands} "
        f"segments={len(plan.segments)}x{plan.segments[0].n_evals} evals")
    cold = submit_timed(clock, "cold", sess, q)
    assert not cold.provenance.from_cache
    assert cold.provenance.n_evals_run > 0
    check_front("cold", cold, p.spec, tpu, cpu)

    warm = submit_timed(clock, "warm", sess, q)
    assert warm.provenance.from_cache and warm.provenance.n_evals_run == 0
    assert np.array_equal(warm.front_objs, cold.front_objs)

    refined = submit_timed(clock, "refine", sess,
                           Query(p, budget=2 * BUDGET))
    assert not refined.provenance.from_cache
    assert refined.provenance.n_evals_run > 0

    ex = Executor(sess, max_workers=1)
    try:
        pe = problem(EXECUTOR_WORKLOAD)
        h, wall, comp = clock.run(lambda: _executor_job(ex, pe))
        r = h.result()
        log("executor", f"{EXECUTOR_WORKLOAD} job {h.job_id} "
            f"state={h.state()} n_evals_run={r.provenance.n_evals_run} "
            f"front={len(r.front_objs)} wall_s={wall:.3f} "
            f"compile_s={comp:.3f}")
        assert h.state() == DONE
        assert r.provenance.n_evals_run == EXECUTOR_BUDGET
        assert np.all(np.isfinite(r.front_metrics))
    finally:
        ex.shutdown()


def _executor_job(ex, p):
    from repro.api import Query
    h = ex.submit(Query(p, budget=EXECUTOR_BUDGET), key=SEED)
    h.result(timeout=900)
    return h


def phase_bo_sa(clock):
    import repro.core as C
    from repro.api import Problem, Query, Session
    from repro.core.optimizer import SAConfig
    spec = C.SystemSpec.build(C.presets.fig7_suite()["att2"], ch_max=36)
    bl = C.make_baseline("monad", spec, jax.random.PRNGKey(SEED),
                         pe_budget=4096)
    q = Query(Problem.from_spec(spec, bl.space), engine="bo_sa",
              weights=C.OBJ_EDP,
              engine_opts=dict(
                  bo_fields=bl.bo_fields, sa_fields=bl.sa_fields,
                  n_init=BO_SA["n_init"], n_iter=BO_SA["n_iter"],
                  sa=SAConfig(steps=BO_SA["sa_steps"], chains=4),
                  init_design=bl.init))
    r, wall, comp = clock.run(
        lambda: Session().submit(q, key=jax.random.PRNGKey(SEED)))
    log("bo_sa", f"fig7 att2 EDP objective={r.best_objective:.6g} "
        f"edp={float(r.best_metrics['edp']):.6g} "
        f"n_evals_run={r.provenance.n_evals_run} wall_s={wall:.3f} "
        f"compile_s={comp:.3f}")
    assert np.isfinite(r.best_objective)


def one_chip(clock, tpu, cpu):
    phase_golden(tpu)
    phase_kernel(tpu, cpu)
    phase_served(clock, tpu, cpu)
    phase_bo_sa(clock)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def four_chips(clock, devs, cpu):
    from jax.sharding import Mesh

    from repro.api import Query
    from repro.core.encoding import random_design
    from repro.explore import quantize
    from repro.explore.nsga import ISLAND_AXIS, make_nsga

    assert len(devs) >= 4, f"--chips 4 needs four devices, found {len(devs)}"
    mesh4 = Mesh(np.array(devs[:4]), (ISLAND_AXIS,))
    mesh1 = Mesh(np.array(devs[:1]), (ISLAND_AXIS,))
    p = problem()
    q = Query(p, budget=BUDGET)

    s4 = session(CACHE / "islands4", mesh=mesh4)
    plan = s4.plan(q)
    log("islands", f"plan: islands={plan.islands}")
    assert plan.islands == 4, plan.islands
    r4 = submit_timed(clock, "islands4", s4, q)
    assert not r4.provenance.from_cache

    # the scan variant the service ran for this query, once more from a
    # seeded population: its output population spans all four devices
    svc = s4.service
    sched = quantize.schedule(BUDGET, POP, svc.policy.chunk_generations)
    assert svc._mesh_for(sched.pop) is mesh4
    cfg = dataclasses.replace(svc.nsga, pop=sched.pop,
                              generations=sched.chunk)
    run = make_nsga(p.spec, p.space, OBJECTIVES, cfg, tech=svc.tech,
                    mesh=mesh4)
    key = jax.random.PRNGKey(SEED)
    pop0 = jax.vmap(lambda k: random_design(k, p.space))(
        jax.random.split(key, sched.pop))
    pop_out = run(key, pop0)[0]
    spans = {len(x.sharding.device_set) for x in jax.tree.leaves(pop_out)}
    log("islands", f"population leaves span {sorted(spans)} devices")
    assert spans == {4}, spans

    check_front("islands4", r4, p.spec, devs[0], cpu)

    r1 = submit_timed(clock, "islands1", session(CACHE / "islands1",
                                                 mesh=mesh1), q)
    hv4, hv1 = r4.trace.archive_hv[-1], r1.trace.archive_hv[-1]
    log("islands", "hypervolume ratio 4-island / 1-device per objective "
        "pair: " + ", ".join(f"{a / b:.4f}" for a, b in zip(hv4, hv1)))


def main(argv=None) -> int:
    global TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: only the "
                         "island-sharded search and its comparison")
    args = ap.parse_args(argv)

    tpu, count = device()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable as enable_compile_cache
    cache_dir = enable_compile_cache()
    TAG = f"{tpu.platform} {tpu.device_kind} x{count}"
    log("device", f"platform={tpu.platform} kind={tpu.device_kind} "
        f"count={count} compile_cache={cache_dir}")
    shutil.rmtree(CACHE, ignore_errors=True)     # every query starts cold
    clock = CompileClock()
    if args.chips == 4:
        four_chips(clock, jax.devices(), jax.devices("cpu")[0])
    else:
        one_chip(clock, tpu, jax.devices("cpu")[0])
    print(json.dumps({"ok": True, "device": {
        "platform": tpu.platform, "kind": tpu.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
