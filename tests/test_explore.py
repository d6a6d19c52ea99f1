"""Tests for the ``repro.explore`` subsystem: dominance/archive invariants
(no dominated point survives insertion, capacity pruning keeps boundary
points), NSGA-II front correctness against a brute-force dominance sweep,
and the service's cache round-trip (save -> load -> warm-start yields
identical fronts)."""

from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core as C
from repro.explore.archive import (HV_LOG_REF, ConvergenceTrace,
                                   ParetoArchive, hypervolume_2d,
                                   hypervolume_2d_jit, objective_pairs,
                                   pareto_front, spec_space_key)
from repro.explore.nsga import NSGAConfig, make_nsga, pmx
from repro.explore.service import (BudgetPolicy, ExplorationService,
                                   ExploreQuery)


def _brute_front(pts):
    """Reference O(n^2) double-loop dominance sweep."""
    pts = np.asarray(pts, np.float64)
    keep = []
    for i in range(len(pts)):
        dom = any(j != i and np.all(pts[j] <= pts[i])
                  and np.any(pts[j] < pts[i]) for j in range(len(pts)))
        if not dom:
            keep.append(i)
    return keep


# this module deliberately exercises the legacy explore/optimize entry
# points (now deprecation shims over repro.api) — expected warnings only
pytestmark = pytest.mark.filterwarnings("ignore:legacy entry point")

TINY_SPACE_KW = dict(max_shape=(16, 16, 4, 4, 1, 2))   # <= 2 chiplets =>
#                      every design satisfies the ch_max=2 node constraint


def _tiny_problem(ch_max=2):
    g = C.presets.bert_mms()["att2"]
    spec = C.SystemSpec.build(g, ch_max=ch_max)
    return g, spec, C.DesignSpace(spec, **TINY_SPACE_KW)


# ---------------------------------------------------------------------------
# canonical dominance math
# ---------------------------------------------------------------------------
def test_pareto_front_matches_bruteforce():
    pts = np.random.default_rng(0).random((64, 3))
    assert sorted(pareto_front(pts)) == sorted(_brute_front(pts))


def test_pareto_front_is_the_optimizer_impl():
    # one canonical implementation: the optimizer re-exports the archive's
    from repro.core.optimizer import pareto_front as pf_opt
    assert pf_opt is pareto_front
    assert sorted(pf_opt([[1, 2], [2, 1], [2, 2], [0.5, 3]])) == [0, 1, 3]
    assert pf_opt([[1, 1]]) == [0]


def test_hypervolume_2d():
    assert hypervolume_2d([(1, 5)], (10, 10)) == pytest.approx(45.0)
    # two staircase points: [1,10]x[5,10] + [2,10]x[3,5]
    assert hypervolume_2d([(1, 5), (2, 3)], (10, 10)) == pytest.approx(61.0)
    # dominated + non-finite points contribute nothing
    assert hypervolume_2d([(1, 5), (2, 6), (np.inf, 0)],
                          (10, 10)) == pytest.approx(45.0)
    assert hypervolume_2d(np.zeros((0, 2)), (1, 1)) == 0.0


def test_hypervolume_2d_jit_matches_host():
    rng = np.random.default_rng(7)
    for n in (1, 2, 17, 64):
        pts = rng.random((n, 2)) * 4
        pts[rng.random(n) < 0.2] = np.inf        # some filtered rows
        ref = (3.0, 3.5)
        assert float(hypervolume_2d_jit(pts, ref)) == pytest.approx(
            hypervolume_2d(pts, ref), rel=1e-5)
    # the validity mask drops points exactly like removing them
    pts = rng.random((8, 2))
    valid = rng.random(8) < 0.5
    assert float(hypervolume_2d_jit(pts, (2, 2), valid=valid)) \
        == pytest.approx(hypervolume_2d(pts[valid], (2, 2)), rel=1e-5)


def test_objective_pairs():
    assert objective_pairs(1) == ()
    assert objective_pairs(2) == ((0, 1),)
    assert objective_pairs(3) == ((0, 1), (0, 2), (1, 2))


def test_archive_projected_hypervolume():
    arc = ParetoArchive(8, {"tag": np.zeros((), np.int32)}, n_obj=2)
    assert arc.projected_hypervolume((0, 1)) == 0.0   # empty archive
    arc.insert({"tag": np.zeros(1, np.int32)}, np.array([[np.e, np.e]]))
    # single point at log-coords (1, 1) against (ref, ref)
    assert arc.projected_hypervolume((0, 1)) == pytest.approx(
        (HV_LOG_REF - 1.0) ** 2, rel=1e-5)
    # inserting a dominating point can only grow the projected hv
    hv0 = arc.projected_hypervolume((0, 1))
    arc.insert({"tag": np.zeros(1, np.int32)}, np.array([[1.0, 1.0]]))
    assert arc.projected_hypervolume((0, 1)) >= hv0


def test_convergence_trace_extend_and_summary():
    tr = lambda hv, best, n0: ConvergenceTrace(
        objectives=("latency_ns", "cost_usd"),
        pairs=(("latency_ns", "cost_usd"),),
        front_size=np.array([2, 3]), hypervolume=np.asarray(hv, float),
        best=np.asarray(best, float), feasible_frac=np.ones(2),
        n_evals=np.array([n0, 2 * n0]))
    a = tr([[1.0], [2.0]], [5.0, 4.0], 8)
    b = tr([[1.5], [2.5]], [4.5, 3.0], 8)   # dips below a's running max
    c = a.extend(b)
    assert c.generations == 4
    np.testing.assert_array_equal(c.n_evals, [8, 16, 24, 32])
    # the seam stays monotone: hv never drops, best never rises
    np.testing.assert_allclose(c.hypervolume.ravel(), [1, 2, 2, 2.5])
    np.testing.assert_allclose(c.best, [5, 4, 4, 3])
    s = c.summary()
    assert s["generations"] == 4 and s["n_evals"] == 32
    assert s["hypervolume_final"] == [2.5] and s["best_final"] == 3.0
    with pytest.raises(ValueError):
        a.extend(ConvergenceTrace.from_history([(0, 1.0)]))


def test_convergence_trace_from_history():
    t = ConvergenceTrace.from_history(
        [(0, 3.0), (1, 5.0), (2, 1.0), ("pareto_kept", 2)],
        evals_per_step=10)
    np.testing.assert_allclose(t.best, [3.0, 3.0, 1.0])   # running best
    np.testing.assert_array_equal(t.n_evals, [10, 20, 30])
    assert t.pairs == () and t.hypervolume.shape == (3, 0)


# ---------------------------------------------------------------------------
# archive invariants
# ---------------------------------------------------------------------------
def _point_archive(capacity, n=0, seed=0):
    arc = ParetoArchive(capacity, {"tag": np.zeros((), np.int32)}, n_obj=2)
    if n:
        pts = np.random.default_rng(seed).random((n, 2))
        arc.insert({"tag": np.arange(n, dtype=np.int32)}, pts)
    return arc


def test_archive_no_dominated_point_survives():
    arc = _point_archive(64)
    rng = np.random.default_rng(1)
    seen = []
    for batch in range(4):                       # incremental insertions
        pts = rng.random((20, 2))
        seen.append(pts)
        arc.insert({"tag": np.arange(20, dtype=np.int32)}, pts)
        _, objs = arc.front()
        # every archived point is mutually nondominated ...
        assert len(pareto_front(objs)) == len(objs)
    # ... and the archive front equals the brute-force front of all inserts
    allpts = np.concatenate(seen)
    expect = np.sort(allpts[_brute_front(allpts)], axis=0)
    np.testing.assert_allclose(np.sort(objs, axis=0), expect, rtol=1e-6)
    assert arc.n_evals == 80


def test_archive_capacity_pruning_keeps_boundary_points():
    x = np.linspace(0.0, 1.0, 50)
    pts = np.stack([x, 1.0 - x], axis=1)         # 50 mutually nondominated
    arc = _point_archive(8)
    arc.insert({"tag": np.arange(50, dtype=np.int32)}, pts)
    _, objs = arc.front()
    assert len(objs) == 8                        # pruned to capacity
    # crowding pruning must preserve the per-objective extremes
    assert objs[:, 0].min() == pytest.approx(0.0)
    assert objs[:, 1].min() == pytest.approx(0.0)


def test_archive_drops_nonfinite_rows():
    arc = _point_archive(8)
    pts = np.array([[0.5, 0.5], [np.nan, 0.1], [0.1, np.inf]])
    arc.insert({"tag": np.zeros(3, np.int32)}, pts)
    _, objs = arc.front()
    np.testing.assert_allclose(objs, [[0.5, 0.5]])


def test_archive_save_load_roundtrip(tmp_path):
    arc = _point_archive(16, n=30)
    arc.searched = ("latency_ns", "cost_usd")
    p = arc.save(tmp_path / "a.npz")
    back = ParetoArchive.load(p)
    assert back.searched == ("latency_ns", "cost_usd")
    np.testing.assert_array_equal(back.objs, arc.objs)
    np.testing.assert_array_equal(back.valid, arc.valid)
    np.testing.assert_array_equal(back.designs["tag"], arc.designs["tag"])
    assert back.n_evals == arc.n_evals == 30
    assert back.capacity == 16 and back.n_obj == 2


def test_spec_space_key_canonical():
    g1, spec1, space1 = _tiny_problem()
    g2, spec2, space2 = _tiny_problem()          # equal content, new objects
    assert spec_space_key(spec1, space1) == spec_space_key(spec2, space2)
    # any DesignSpace bound change => different archive
    assert spec_space_key(spec1, C.DesignSpace(spec1, max_logB=2)) \
        != spec_space_key(spec1, space1)
    # different ch_max changes the padded dims => different archive
    _, spec3, space3 = _tiny_problem(ch_max=3)
    assert spec_space_key(spec3, space3) != spec_space_key(spec1, space1)
    # extra cache-identity (the service folds its TechConstants in here)
    assert spec_space_key(spec1, space1, extra="t") \
        != spec_space_key(spec1, space1)


def test_service_cache_is_tech_keyed(tmp_path):
    from repro.core.constants import DEFAULT_TECH
    import dataclasses as dc
    _, spec, space = _tiny_problem()
    a = ExplorationService(cache_dir=tmp_path)
    b = ExplorationService(cache_dir=tmp_path, tech=DEFAULT_TECH)
    # None normalizes to DEFAULT_TECH: same archive
    assert a.problem_key(spec, space) == b.problem_key(spec, space)
    other = dc.replace(DEFAULT_TECH,
                       dram_bw=DEFAULT_TECH.dram_bw * 2)
    c = ExplorationService(cache_dir=tmp_path, tech=other)
    # different tech constants must never share an archive
    assert c.problem_key(spec, space) != a.problem_key(spec, space)


# ---------------------------------------------------------------------------
# NSGA-II explorer
# ---------------------------------------------------------------------------
def test_nsga_front_correct_vs_bruteforce_sweep():
    _, spec, space = _tiny_problem()
    cfg = NSGAConfig(pop=8, generations=3)
    run = make_nsga(spec, space, ("latency_ns", "cost_usd"), cfg)
    pop0 = jax.vmap(lambda k: C.random_design(k, space))(
        jax.random.split(jax.random.PRNGKey(0), cfg.pop))
    pop, raw, sel, ev_designs, ev_raw, ev_feas, trace = run(
        jax.random.PRNGKey(1), pop0)

    raw = np.asarray(raw, np.float64)
    assert raw.shape == (cfg.pop, 4) and np.all(np.isfinite(raw))
    assert np.asarray(ev_raw).shape == (cfg.generations, cfg.pop, 4)
    assert np.asarray(ev_feas).shape == (cfg.generations, cfg.pop)
    assert np.asarray(ev_feas).dtype == bool
    # final population's latency-cost front == brute-force dominance sweep
    cols = raw[:, [0, 2]]
    assert sorted(pareto_front(cols)) == sorted(_brute_front(cols))
    # elitism: the front is nonempty and every design evaluable
    assert len(pareto_front(cols)) >= 1
    # every returned design stays inside the encoding bounds
    sh = np.asarray(jax.tree.map(np.asarray, pop)["shape"])
    assert sh.min() >= 1 and np.all(sh <= np.asarray(space.max_shape))


def test_nsga_scans_out_convergence_trace():
    """The scan emits per-generation telemetry with zero extra evals: the
    running hypervolume is monotone non-decreasing, the running best is
    monotone non-increasing, and the hv matches the host recomputation."""
    _, spec, space = _tiny_problem()
    cfg = NSGAConfig(pop=8, generations=4)
    objectives = ("latency_ns", "cost_usd")
    run = make_nsga(spec, space, objectives, cfg)
    pop0 = jax.vmap(lambda k: C.random_design(k, space))(
        jax.random.split(jax.random.PRNGKey(0), cfg.pop))
    pop, raw, sel, _d, ev_raw, ev_feas, tr = run(jax.random.PRNGKey(1), pop0)

    t = ConvergenceTrace.from_scan(objectives, tr, cfg.pop)
    assert t.generations == cfg.generations
    assert t.pairs == (("latency_ns", "cost_usd"),)
    assert t.hypervolume.shape == (cfg.generations, 1)
    assert np.all(np.diff(t.hypervolume, axis=0) >= 0)       # monotone
    # the instantaneous per-generation hv is traced alongside: its running
    # max IS the monotone hypervolume column
    assert t.hv_gen is not None and t.hv_gen.shape == t.hypervolume.shape
    np.testing.assert_allclose(np.maximum.accumulate(t.hv_gen, axis=0),
                               t.hypervolume, rtol=1e-6)
    assert np.all(np.diff(t.best) <= 1e-6)
    assert np.all((0 <= t.feasible_frac) & (t.feasible_frac <= 1))
    assert np.all(t.front_size >= 0) and np.all(t.front_size <= cfg.pop)
    np.testing.assert_array_equal(
        t.n_evals, cfg.pop * (np.arange(cfg.generations) + 1))
    # final-generation running hv >= hv of the final population's feasible
    # log-front recomputed on the host (running max can only exceed it)
    logs = np.log(np.maximum(np.asarray(raw, np.float64)[:, [0, 2]], 1e-3))
    hv_host = hypervolume_2d(logs, (HV_LOG_REF, HV_LOG_REF))
    assert t.hypervolume[-1, 0] >= hv_host * (1 - 1e-4)


def test_scan_program_records_its_node_bound(monkeypatch):
    """Building a scan program records the node bound its network is
    sized by; programs below the full table are counted, cache hits
    build nothing."""
    import types
    from repro import obs
    from repro.core.network import N_TOT
    from repro.explore import nsga
    monkeypatch.setattr(nsga, "_NSGA_CACHE", {})
    obs.enable()
    g = C.presets.attention_block(types.SimpleNamespace(
        d_model=64, head_dim=8, n_heads=4, n_kv_heads=2), seq=32)
    spec = C.SystemSpec.build(g, ch_max=1)
    c1 = C.DesignSpace(spec, max_shape=(16, 16, 4, 4, 1, 1))
    cfg = NSGAConfig(pop=8, generations=1)

    def gauge():
        return obs.REGISTRY.gauge("network.node_bound").value

    def bounded():
        return obs.REGISTRY.counter("network.bounded_programs").value

    n0 = bounded()
    make_nsga(spec, c1, cfg=cfg)
    assert gauge() == 5 and bounded() - n0 == 1
    make_nsga(spec, c1, cfg=cfg)
    assert bounded() - n0 == 1
    make_nsga(spec, C.DesignSpace(spec), cfg=cfg)
    assert gauge() == N_TOT and bounded() - n0 == 1


def test_pmx_always_yields_valid_permutations():
    """The placement crossover must keep children valid permutations for
    every cut-point draw and any parent pair."""
    rng = np.random.default_rng(0)
    for t in range(32):
        n = int(rng.integers(2, 24))
        a = jnp.asarray(rng.permutation(n).astype(np.int32))
        b = jnp.asarray(rng.permutation(n).astype(np.int32))
        c = np.asarray(pmx(jax.random.PRNGKey(t), a, b))
        assert sorted(c.tolist()) == list(range(n))


def test_pmx_mixes_both_parents():
    """Unlike whole-field take (child == one parent), PMX produces children
    carrying genes of BOTH parents for some cut points."""
    a = jnp.arange(10, dtype=jnp.int32)
    b = jnp.asarray(np.arange(10)[::-1].copy().astype(np.int32))
    mixed = 0
    for t in range(40):
        c = np.asarray(pmx(jax.random.PRNGKey(t), a, b))
        if not (np.array_equal(c, np.asarray(a))
                or np.array_equal(c, np.asarray(b))):
            mixed += 1
    assert mixed > 0


def test_nsga_pmx_placement_flag():
    """With ``pmx_placement`` on, the run completes and every evaluated
    design's placement is still a valid permutation."""
    _, spec, space = _tiny_problem()
    cfg = NSGAConfig(pop=8, generations=2, pmx_placement=True,
                     crossover_rate=1.0)     # force crossover every field
    run = make_nsga(spec, space, ("latency_ns", "cost_usd"), cfg)
    pop0 = jax.vmap(lambda k: C.random_design(k, space))(
        jax.random.split(jax.random.PRNGKey(0), cfg.pop))
    pop, raw, sel, ev_designs, ev_raw, ev_feas, trace = run(
        jax.random.PRNGKey(1), pop0)
    n = space.W * space.CH
    places = np.asarray(ev_designs["placement"]).reshape(-1, n)
    for row in places:
        assert sorted(row.tolist()) == list(range(n))
    assert np.all(np.isfinite(np.asarray(raw)))


# ---------------------------------------------------------------------------
# the exploration service: batching + cache
# ---------------------------------------------------------------------------
def test_service_cache_roundtrip_and_warm_start(tmp_path):
    g, spec, space = _tiny_problem()
    mk = lambda: ExplorationService(cache_dir=tmp_path,
                                    nsga=NSGAConfig(pop=8, generations=2))
    svc = mk()
    r1 = svc.explore(g, ("latency_ns", "cost_usd"), budget=16, ch_max=2,
                     space_kwargs=TINY_SPACE_KW)
    assert not r1.from_cache and r1.n_evals_run >= 16
    assert len(r1.front_objs) >= 1
    # the front the service returns is nondominated
    assert len(pareto_front(r1.front_objs)) == len(r1.front_objs)

    # identical query on the warm service: served from the archive
    r2 = svc.explore(g, ("latency_ns", "cost_usd"), budget=16, ch_max=2,
                     space_kwargs=TINY_SPACE_KW)
    assert r2.from_cache and r2.n_evals_run == 0
    np.testing.assert_allclose(r2.front_objs, r1.front_objs)
    assert r2.elapsed_s < r1.elapsed_s

    # fresh service, same cache dir: disk round-trip, identical front
    r3 = mk().explore(g, ("latency_ns", "cost_usd"), budget=16, ch_max=2,
                     space_kwargs=TINY_SPACE_KW)
    assert r3.from_cache and r3.cache_key == r1.cache_key
    np.testing.assert_allclose(r3.front_objs, r1.front_objs)

    # bigger budget invalidates the cache and warm-starts instead
    r4 = svc.explore(g, ("latency_ns", "cost_usd"), budget=48, ch_max=2,
                     space_kwargs=TINY_SPACE_KW)
    assert not r4.from_cache and r4.n_evals_run >= 32

    # objectives never searched for must spend compute, however warm the
    # archive is on other axes
    r5 = svc.explore(g, ("energy_pj", "area_mm2"), budget=16, ch_max=2,
                     space_kwargs=TINY_SPACE_KW)
    assert not r5.from_cache and r5.n_evals_run >= 16


def test_service_batches_same_spec_queries(tmp_path):
    from repro.explore.service import ExploreQuery
    g, _, _ = _tiny_problem()
    svc = ExplorationService(cache_dir=tmp_path,
                             nsga=NSGAConfig(pop=8, generations=2))
    qs = [ExploreQuery(g, ("latency_ns", "cost_usd"), budget=16, ch_max=2,
                       space_kwargs=TINY_SPACE_KW),
          ExploreQuery(g, ("energy_pj", "area_mm2"), budget=16, ch_max=2,
                       space_kwargs=TINY_SPACE_KW)]
    ra, rb = svc.explore_batch(qs)
    # one shared run answered both ...
    assert ra.cache_key == rb.cache_key
    assert not ra.from_cache and not rb.from_cache
    # ... each projected onto its own objectives, each nondominated
    for r in (ra, rb):
        assert r.front_objs.shape[1] == 2
        assert len(pareto_front(r.front_objs)) == len(r.front_objs)
    # both served from one archive: total evals booked once
    assert svc.archive_for(
        *_tiny_problem()[1:], key=ra.cache_key).n_evals == ra.n_evals_run


def test_service_front_contains_only_feasible_designs(tmp_path):
    """NSGA may keep constraint-violating designs in its gene pool (the
    penalty steers them out), but none may be archived or served."""
    g, _, _ = _tiny_problem()
    kw = dict(TINY_SPACE_KW, max_total_pes=2048)   # binding PE budget
    svc = ExplorationService(cache_dir=tmp_path,
                             nsga=NSGAConfig(pop=16, generations=2))
    r = svc.explore(g, ("latency_ns", "cost_usd"), budget=48, ch_max=2,
                    space_kwargs=kw)
    for d in r.front_designs:
        assert int(np.prod(d["shape"], axis=1).sum()) <= 2048
    # the archive itself holds no infeasible point either
    _, spec, _ = _tiny_problem()
    space = C.DesignSpace(spec, **kw)
    designs, _objs = svc.archive_for(spec, space).front()
    for i in range(len(_objs)):
        assert int(np.prod(designs["shape"][i], axis=1).sum()) <= 2048


def test_optimize_records_into_archive():
    """The scalarized BO x SA engine feeds the same Pareto cache the
    service serves from: optimize(archive=...) batch-inserts every
    SA-refined design with its raw metric vector."""
    from repro.core.optimizer import SAConfig, optimize
    g, spec, space = _tiny_problem()
    arc = ParetoArchive(
        32, jax.tree.map(np.asarray,
                         C.random_design(jax.random.PRNGKey(0), space)),
        n_obj=4, obj_keys=C.METRIC_KEYS)
    r = optimize(spec, space, jax.random.PRNGKey(0), bo_fields=(),
                 n_init=3, sa=SAConfig(steps=20, chains=2), archive=arc)
    designs, objs = arc.front()
    assert len(arc) >= 1 and arc.n_evals == 3
    assert objs.shape[1] == 4 and np.all(np.isfinite(objs))
    # archived rows are mutually nondominated designs within bounds
    assert len(pareto_front(objs)) == len(objs)
    assert np.asarray(designs["shape"]).min() >= 1


def test_service_rejects_unknown_objective():
    g, _, _ = _tiny_problem()
    with pytest.raises(ValueError):
        ExploreQuery(g, objectives=("latency_ns", "nope"))


# ---------------------------------------------------------------------------
# convergence-aware exploration: telemetry, plateau stopping, budget ledger
# ---------------------------------------------------------------------------
def test_default_cache_dir_is_repo_anchored(tmp_path, monkeypatch):
    """Regression: the default cache must not fragment across working
    directories — it is anchored to the repo root unless overridden."""
    from repro.explore import service as service_mod
    monkeypatch.delenv("REPRO_EXPLORE_CACHE", raising=False)
    monkeypatch.chdir(tmp_path)                  # CWD must be irrelevant
    svc = ExplorationService()
    assert svc.cache_dir.is_absolute()
    root = Path(service_mod.__file__).resolve().parents[3]
    assert svc.cache_dir == root / "artifacts" / "explore_cache"
    assert svc.cache_dir == Path(service_mod.DEFAULT_CACHE_DIR)
    # the env var and the explicit argument still override, in that order
    monkeypatch.setenv("REPRO_EXPLORE_CACHE", str(tmp_path / "env"))
    assert ExplorationService().cache_dir == tmp_path / "env"
    assert ExplorationService(cache_dir=tmp_path / "arg").cache_dir \
        == tmp_path / "arg"


def test_explore_result_carries_convergence_trace(tmp_path):
    g, _, _ = _tiny_problem()
    svc = ExplorationService(cache_dir=tmp_path, nsga=NSGAConfig(pop=8),
                             policy=BudgetPolicy(chunk_generations=2))
    r = svc.explore(g, ("latency_ns", "cost_usd"), budget=32, ch_max=2,
                    space_kwargs=TINY_SPACE_KW)
    t = r.trace
    assert isinstance(t, ConvergenceTrace)
    assert t.objectives == ("latency_ns", "cost_usd")
    # one generation of telemetry per pop-wide evaluation actually spent
    assert t.n_evals[-1] == r.n_evals_run
    assert len(t.front_size) == t.generations
    # the acceptance gate: per-generation front size + hypervolume, the hv
    # monotone non-decreasing for the archive-backed front
    assert np.all(t.front_size >= 0)
    assert np.all(np.diff(t.hypervolume, axis=0) >= 0)
    assert np.all(np.diff(t.archive_hv, axis=0) >= -1e-6)
    assert t.archive_hv.shape[1] == len(t.pairs)
    # the trace summary is persisted with the archive npz
    back = ParetoArchive.load(svc._path(r.cache_key))
    assert back.trace_summary == t.summary()
    assert back.budget_covered >= 32
    # a warm (cache-served) answer spends nothing and carries no new trace
    r2 = svc.explore(g, ("latency_ns", "cost_usd"), budget=32, ch_max=2,
                     space_kwargs=TINY_SPACE_KW)
    assert r2.from_cache and r2.trace is None


@pytest.mark.slow
def test_explore_deterministic_given_key(tmp_path):
    """Same PRNG key + cold cache => identical fronts AND identical
    convergence traces, bit for bit."""
    g, _, _ = _tiny_problem()
    results = []
    for sub in ("a", "b"):
        svc = ExplorationService(cache_dir=tmp_path / sub,
                                 nsga=NSGAConfig(pop=8),
                                 policy=BudgetPolicy(chunk_generations=2))
        results.append(svc.explore(
            g, ("latency_ns", "cost_usd"), budget=32, ch_max=2,
            space_kwargs=TINY_SPACE_KW, key=jax.random.PRNGKey(7)))
    ra, rb = results
    np.testing.assert_array_equal(ra.front_objs, rb.front_objs)
    np.testing.assert_array_equal(ra.front_metrics, rb.front_metrics)
    assert ra.n_evals_run == rb.n_evals_run
    np.testing.assert_array_equal(ra.trace.front_size, rb.trace.front_size)
    np.testing.assert_array_equal(ra.trace.hypervolume,
                                  rb.trace.hypervolume)
    np.testing.assert_array_equal(ra.trace.best, rb.trace.best)
    np.testing.assert_array_equal(ra.trace.feasible_frac,
                                  rb.trace.feasible_frac)
    np.testing.assert_array_equal(ra.trace.archive_hv, rb.trace.archive_hv)


def test_plateau_early_stop_banks_budget(tmp_path):
    """With an always-satisfied plateau threshold the service stops after
    patience+1 segments and banks the rest of the budget in the ledger."""
    g, _, _ = _tiny_problem()
    svc = ExplorationService(
        cache_dir=tmp_path, nsga=NSGAConfig(pop=8),
        policy=BudgetPolicy(chunk_generations=1, plateau_rel=10.0,
                            patience=1, reallocate=False))
    r = svc.explore(g, ("latency_ns", "cost_usd"), budget=64, ch_max=2,
                    space_kwargs=TINY_SPACE_KW)
    # 8 generations planned (pop 8), stopped after segment 2 of 8
    assert r.plateaued
    assert r.n_evals_run == 16 and r.n_evals_banked == 48
    assert svc.ledger == {r.cache_key: 48}
    # early-stopped or not, the query's budget counts as covered: the
    # identical query is served warm
    r2 = svc.explore(g, ("latency_ns", "cost_usd"), budget=64, ch_max=2,
                     space_kwargs=TINY_SPACE_KW)
    assert r2.from_cache
    # ... and budget coverage survives the disk round-trip
    svc2 = ExplorationService(
        cache_dir=tmp_path, nsga=NSGAConfig(pop=8),
        policy=BudgetPolicy(chunk_generations=1, plateau_rel=10.0,
                            patience=1, reallocate=False))
    r3 = svc2.explore(g, ("latency_ns", "cost_usd"), budget=64, ch_max=2,
                      space_kwargs=TINY_SPACE_KW)
    assert r3.from_cache


def test_plateau_disabled_spends_full_budget(tmp_path):
    g, _, _ = _tiny_problem()
    svc = ExplorationService(
        cache_dir=tmp_path, nsga=NSGAConfig(pop=8),
        policy=BudgetPolicy(chunk_generations=1, plateau_rel=10.0,
                            patience=1, adaptive=False))
    r = svc.explore(g, ("latency_ns", "cost_usd"), budget=64, ch_max=2,
                    space_kwargs=TINY_SPACE_KW)
    assert not r.plateaued and r.n_evals_run == 64
    assert r.n_evals_banked == 0 and svc.ledger == {}
    # chunked and single-scan spending agree on the accounting
    assert r.trace.generations == 8 and r.trace.n_evals[-1] == 64


@pytest.mark.slow
def test_batch_reallocates_banked_budget(tmp_path):
    """A plateaued problem's banked evaluations flow to the batch's
    under-explored, still-improving problem."""
    g1, _, _ = _tiny_problem()
    g2 = C.presets.bert_mms()["att3"]
    svc = ExplorationService(
        cache_dir=tmp_path, nsga=NSGAConfig(pop=8),
        policy=BudgetPolicy(chunk_generations=1, plateau_rel=10.0,
                            patience=1))
    qs = [ExploreQuery(g1, ("latency_ns", "cost_usd"), budget=64, ch_max=2,
                       space_kwargs=TINY_SPACE_KW),
          ExploreQuery(g2, ("latency_ns", "cost_usd"), budget=8, ch_max=2,
                       space_kwargs=TINY_SPACE_KW)]
    ra, rb = svc.explore_batch(qs)
    assert ra.cache_key != rb.cache_key
    # g1 plateaued and banked; g2 ran its whole (1-segment) budget without
    # a plateau verdict, so it is the reallocation taker
    assert ra.plateaued and ra.n_evals_banked > 0
    assert rb.n_evals_realloc > 0
    assert rb.n_evals_run == 8 + rb.n_evals_realloc
    # the taker's archive really recorded the extra evaluations ...
    spec2 = C.SystemSpec.build(g2, ch_max=2)
    space2 = C.DesignSpace(spec2, **TINY_SPACE_KW)
    assert svc.archive_for(spec2, space2).n_evals == rb.n_evals_run
    # ... its trace covers them ...
    assert rb.trace.n_evals[-1] == rb.n_evals_run
    # ... and the spent credit was drained from the ledger
    assert sum(svc.ledger.values()) \
        == ra.n_evals_banked - rb.n_evals_realloc
