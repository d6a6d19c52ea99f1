"""The compiled design sampler (``nsga.sample_designs``) and the archive
template (``nsga.design_template``): the same designs, bit for bit, as an
eager ``vmap(random_design)`` over the same keys; one program per set of
space statics, whatever the workload; a cold archive laid out as the one
built from a drawn template."""

import types

import numpy as np
import pytest

import jax

import repro.core as C
from repro import obs
from repro.api import Problem, Query, Session
from repro.core.encoding import random_design
from repro.core.workload import MAX_LOOPS
from repro.explore import nsga, service
from repro.explore.archive import ParetoArchive
from repro.explore.nsga import NSGAConfig, design_template, sample_designs
from repro.explore.service import BudgetPolicy, ExplorationService

# InternLM2-1.8B attention widths at ch_max 1, as the benchmark's c1 cell
INTERNLM2 = types.SimpleNamespace(d_model=2048, head_dim=128, n_heads=16,
                                  n_kv_heads=8)
C1_SPACE_KW = dict(max_shape=(16, 16, 4, 4, 1, 1), max_total_pes=4096)
SPACES = {
    "c1_bench": (1, C1_SPACE_KW),
    "ch_max4": (4, {}),
    "pinned_package_and_family": (2, dict(fixed_packaging=1,
                                          fixed_family=2)),
    "no_pipeline": (2, dict(allow_pipeline=False)),
}


def _space(name, seq=256):
    ch_max, kw = SPACES[name]
    g = C.presets.attention_block(INTERNLM2, seq=seq)
    return C.DesignSpace(C.SystemSpec.build(g, ch_max=ch_max), **kw)


def _eager(key, space, n):
    """The eager draw the compiled sampler replaces."""
    return jax.vmap(lambda k: random_design(k, space))(
        jax.random.split(key, n))


def _counter(name):
    return obs.REGISTRY.counter(name).value


def _assert_same_designs(a, b):
    assert set(a) == set(b)
    for f in a:
        x, y = np.asarray(a[f]), np.asarray(b[f])
        assert x.dtype == y.dtype and x.shape == y.shape, f
        assert x.tobytes() == y.tobytes(), f


@pytest.mark.parametrize("name", sorted(SPACES))
def test_sampler_matches_eager_random_design(name):
    space = _space(name)
    key = jax.random.PRNGKey(3100000011)
    _assert_same_designs(sample_designs(key, space, 256),
                         _eager(key, space, 256))
    # the immigrants' (generations, n) form: the same keys, split once
    # over the product, laid out generation-major
    kk = jax.random.split(key, 4 * 32).reshape(4, 32, -1)
    want = jax.vmap(jax.vmap(lambda k: random_design(k, space)))(kk)
    _assert_same_designs(sample_designs(key, space, (4, 32)), want)


def test_sampler_compiles_once_per_statics(monkeypatch):
    """Two problems with equal statics but different loop counts and
    bounds share one program, and each draw keeps to its own bounds."""
    monkeypatch.setattr(nsga, "_SAMPLERS", {})
    mm = C.WorkloadGraph([C.matmul("a", 512, 512, 64),
                          C.matmul("b", 64, 4096, 512)], [])
    cv = C.WorkloadGraph([C.conv2d("c", 1, 64, 32, 14, 14, 3, 3),
                          C.matmul("d", 3000, 96, 32)], [])
    spaces = [C.DesignSpace(C.SystemSpec.build(g, ch_max=2),
                            **C1_SPACE_KW) for g in (mm, cv)]
    assert nsga._sampler_key(spaces[0]) == nsga._sampler_key(spaces[1])
    assert not np.array_equal(spaces[0].n_loops, spaces[1].n_loops)
    calls0 = _counter("explore.sampler.calls")
    compiles0 = _counter("explore.sampler.compiles")
    for i, space in enumerate(spaces):
        key = jax.random.PRNGKey(i)
        d = sample_designs(key, space, 512)
        _assert_same_designs(d, _eager(key, space, 512))
        nl = np.maximum(space.n_loops, 1)
        tmax = np.maximum(space.bounds, 1)
        assert (np.asarray(d["spatial"]) < nl[None, :, None]).all()
        tiling = np.asarray(d["tiling"])
        assert (tiling >= 1).all()
        assert (tiling <= tmax[None, :, None, :]).all()
        pipe = np.asarray(d["pipe"])
        assert ((pipe < nl[None, :]) | (pipe == MAX_LOOPS)).all()
    assert _counter("explore.sampler.calls") - calls0 == 2
    assert _counter("explore.sampler.compiles") - compiles0 == 1


@pytest.mark.parametrize("name", sorted(SPACES))
def test_design_template_matches_a_drawn_design(name, monkeypatch):
    monkeypatch.setattr(nsga, "_TEMPLATES", {})
    space = _space(name)
    drawn = jax.tree.map(np.asarray,
                         random_design(jax.random.PRNGKey(0), space))
    t = design_template(space)
    assert set(t) == set(drawn)
    for f, v in drawn.items():
        assert t[f].shape == v.shape and t[f].dtype == v.dtype, f
        assert not t[f].any()
    # one trace per set of statics: another sequence length reuses it
    other = _space(name, seq=4096)
    assert design_template(other).keys() == t.keys()
    assert len(nsga._TEMPLATES) == 1


def test_cold_archive_layout_and_roundtrip(tmp_path):
    space = _space("c1_bench")
    svc = ExplorationService(cache_dir=tmp_path / "cache", capacity=32)
    arc = svc.archive_for(space.spec, space)
    old = ParetoArchive(32, jax.tree.map(
        np.asarray, random_design(jax.random.PRNGKey(0), space)))
    assert arc.designs.keys() == old.designs.keys()
    for f, v in old.designs.items():
        assert arc.designs[f].shape == v.shape, f
        assert arc.designs[f].dtype == v.dtype, f
    back = ParetoArchive.load(arc.save(tmp_path / "cold.npz"))
    _assert_same_designs(back.designs, arc.designs)
    np.testing.assert_array_equal(back.objs, arc.objs)
    np.testing.assert_array_equal(back.valid, arc.valid)


def test_cold_submit_front_matches_eager_population(tmp_path, monkeypatch):
    """A cold query served with the compiled first population and the
    traced template gives the front, bit for bit, that the eager draw
    and the drawn template give; a second cold problem with equal
    statics builds no new sampler program."""
    obs.enable()
    q = Query(Problem(C.presets.attention_block(INTERNLM2, seq=256),
                      objectives=("latency_ns", "energy_pj", "cost_usd"),
                      ch_max=1, space_kwargs=C1_SPACE_KW), budget=64)

    def session(sub):
        return Session(cache_dir=tmp_path / sub,
                       nsga=NSGAConfig(pop=16, generations=2),
                       policy=BudgetPolicy(chunk_generations=2,
                                           adaptive=False))

    key = jax.random.PRNGKey(7)
    r_new = session("new").submit(q, key=key)
    compiles = _counter("explore.sampler.compiles")
    q2 = Query(Problem(C.presets.attention_block(INTERNLM2, seq=1024),
                       objectives=q.problem.objectives, ch_max=1,
                       space_kwargs=C1_SPACE_KW), budget=64)
    session("new2").submit(q2, key=key)
    assert _counter("explore.sampler.compiles") == compiles

    monkeypatch.setattr(service, "sample_designs",
                        lambda k, space, n: _eager(k, space, n))
    monkeypatch.setattr(service, "design_template",
                        lambda space: jax.tree.map(np.asarray, random_design(
                            jax.random.PRNGKey(0), space)))
    r_old = session("old").submit(q, key=key)
    assert r_new.front_metrics.tobytes() == r_old.front_metrics.tobytes()
    assert r_new.front_objs.tobytes() == r_old.front_objs.tobytes()
    assert len(r_new.front_designs) == len(r_old.front_designs)
    for a, b in zip(r_new.front_designs, r_old.front_designs):
        _assert_same_designs(a, b)
