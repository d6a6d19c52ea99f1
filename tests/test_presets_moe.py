"""``presets.moe_layer``: one expert-parallel rank of a DeepSeek-V3 expert
layer.  Its routing is the ``noaux_tc`` rule token by token; the routing
stream is a prefix-stable draw, kept once per widths and seed; the ranks'
packages add up to the uncut layer; and the build is traced."""

import types

import numpy as np
import pytest

import jax

from repro import obs
from repro.api import Problem, Query, Session
from repro.core import presets
from repro.explore.nsga import NSGAConfig
from repro.explore.service import ExplorationService

SMALL = dict(hidden_size=16, moe_intermediate_size=8, n_routed_experts=16,
             num_experts_per_tok=4, n_shared_experts=1, n_group=4,
             topk_group=2, topk_method="noaux_tc", scoring_func="sigmoid",
             ep_size=4, ep_rank=0, route_seed=11)


def _cfg(**kw):
    return types.SimpleNamespace(**dict(SMALL, **kw))


@pytest.fixture
def fresh_streams(monkeypatch):
    monkeypatch.setattr(presets, "_ROUTE_STREAMS", {})


def _route_loop(logits, top_k, n_group, topk_group):
    """The ``noaux_tc`` rule one token at a time, ties to the lower index."""
    n_exp = logits.shape[1]
    size = n_exp // n_group
    picks = np.zeros(logits.shape, bool)
    for t, row in enumerate(logits):
        s = [1.0 / (1.0 + np.exp(-x)) for x in row]
        group = []
        for g in range(n_group):
            a, b = sorted(s[g * size:(g + 1) * size])[-2:]
            group.append(a + b)
        kept = sorted(range(n_group), key=lambda g: (-group[g], g))
        kept = kept[:topk_group]
        cand = [e for e in range(n_exp) if e // size in kept]
        for e in sorted(cand, key=lambda e: (-s[e], e))[:top_k]:
            picks[t, e] = True
    return picks


@pytest.mark.parametrize("top_k,topk_group,ties", [
    (8, 2, False), (6, 3, False), (8, 2, True)])
def test_routing_is_the_per_token_rule(top_k, topk_group, ties):
    logits = np.random.default_rng(5).standard_normal((64, 64))
    if ties:        # coarse logits: equal scores within and across groups
        logits = np.round(logits, 0)
    got = presets.noaux_tc_route(logits, top_k, 4, topk_group)
    want = _route_loop(logits, top_k, 4, topk_group)
    np.testing.assert_array_equal(got, want)
    assert np.all(got.sum(axis=1) == top_k)


def test_counts_are_the_plain_routing_of_the_seeded_stream(fresh_streams):
    cfg = _cfg(ep_size=1, n_routed_experts=64, num_experts_per_tok=8,
               topk_group=2)
    logits = np.random.default_rng(cfg.route_seed).standard_normal((64, 64))
    want = _route_loop(logits, 8, 4, 2).sum(axis=0)
    np.testing.assert_array_equal(presets.routed_tokens(cfg, 64), want)


def test_stream_prefix_drawn_alone_equals_the_longer_cached_one(
        fresh_streams):
    cfg = _cfg()
    held = range(0, cfg.n_routed_experts)
    draws0 = obs.REGISTRY.counter("presets.moe_layer.stream_draws").value
    big = presets.ROUTE_BLOCK * 2 + 1       # one draw, then one extension
    for seq in (1, 100, presets.ROUTE_BLOCK, big, 300):
        presets.routed_tokens(cfg, seq)
    stream = presets._ROUTE_STREAMS[(11, 16, 4, 4, 2)]
    assert stream.n == cfg.ep_size * presets.ROUTE_BLOCK * 4
    assert obs.REGISTRY.counter("presets.moe_layer.stream_draws").value \
        - draws0 == 2
    for seq in (7, 300, big):
        n = cfg.ep_size * seq
        alone = presets.noaux_tc_route(
            np.random.default_rng(cfg.route_seed).standard_normal((n, 16)),
            4, 4, 2)
        np.testing.assert_array_equal(stream.counts(held, n),
                                      alone.sum(axis=0))


def test_packages_add_up_to_the_uncut_layer(fresh_streams):
    seq, ep = 40, SMALL["ep_size"]
    ranks = [presets.moe_layer(_cfg(ep_rank=r), seq) for r in range(ep)]
    uncut = presets.moe_layer(_cfg(ep_size=1), ep * seq)
    head = [w.loops for w in ranks[0].workloads[:4]]
    assert all([w.loops for w in g.workloads[:4]] == head for g in ranks)
    # router and shared expert: each rank's over its own tokens
    for w, u in zip(ranks[0].workloads[:4], uncut.workloads[:4]):
        assert w.macs * ep == u.macs
    routed = [w for g in ranks for w in g.workloads[4:]]
    tokens = [w.bounds["i"] for w in routed[::3]]
    assert min(tokens) >= 2          # no expert padded up to one token
    assert sum(tokens) == SMALL["num_experts_per_tok"] * ep * seq
    assert sum(w.macs for w in routed) == \
        sum(w.macs for w in uncut.workloads[4:])
    assert [w.name for w in routed] == [w.name for w in uncut.workloads[4:]]


def test_graph_shape_and_edges(fresh_streams):
    g = presets.moe_layer(_cfg(ep_rank=2), 50)
    k = SMALL["n_routed_experts"] // SMALL["ep_size"]
    assert len(g.workloads) == 4 + 3 * k and len(g.edges) == 2 + 4 * k
    assert g.workloads[4].name == "expert8_gate"
    d, ff = SMALL["hidden_size"], SMALL["moe_intermediate_size"]
    assert g.workloads[0].loops == (("i", 50), ("j", 16), ("k", d))
    assert g.workloads[3].loops == (("i", 50), ("j", d), ("k", ff))
    dispatch = [(e.src, e.dst, e.tensor_src, e.tensor_dst)
                for e in g.edges if e.src == 0]
    assert dispatch == [(0, w, "C", "A") for w in range(4, 4 + 3 * k)
                        if (w - 4) % 3 < 2]


def test_an_expert_no_token_picks_runs_over_one(fresh_streams):
    # one token, 4 picks of the 16 experts held
    counts = presets.routed_tokens(_cfg(ep_size=1), 1)
    assert sorted(counts)[-5:] == [0, 1, 1, 1, 1]
    g = presets.moe_layer(_cfg(ep_size=1), 1)
    assert [w.bounds["i"] for w in g.workloads[4::3]] == \
        [max(int(t), 1) for t in counts]


def test_other_routing_rules_are_refused():
    with pytest.raises(ValueError, match="noaux_tc"):
        presets.moe_layer(_cfg(topk_method="group_limited_greedy"), 8)
    with pytest.raises(ValueError, match="sigmoid"):
        presets.moe_layer(_cfg(scoring_func="softmax"), 8)


def test_build_is_traced_and_draws_the_stream_once(fresh_streams,
                                                   tmp_path):
    def counter(name):
        return obs.REGISTRY.counter(name).value

    records = []
    calls0 = counter("presets.moe_layer.calls")
    draws0 = counter("presets.moe_layer.stream_draws")
    session = Session(service=ExplorationService(
        cache_dir=tmp_path, capacity=16, nsga=NSGAConfig(pop=8)))
    seqs = (24, 8, 40)
    with obs.sink_attached(records.append):
        for i, seq in enumerate(seqs):
            p = Problem(presets.moe_layer(_cfg(), seq),
                        objectives=("latency_ns", "energy_pj"), ch_max=1,
                        space_kwargs=dict(max_shape=(4, 4, 2, 2, 1, 1),
                                          max_total_pes=256))
            r = session.submit(Query(p, budget=16),
                               key=jax.random.PRNGKey(i))
            assert r.provenance.n_evals_run == 16
    spans = [(r["name"], r["parent"]) for r in records
             if r.get("type") == "span" and r["name"].startswith("presets.")]
    assert spans == [("presets.moe_route_draw", "presets.moe_layer"),
                     ("presets.moe_layer", None),
                     ("presets.moe_layer", None),
                     ("presets.moe_layer", None)]
    built = [r["attrs"] for r in records if r.get("name") ==
             "presets.moe_layer"]
    assert [a["seq"] for a in built] == list(seqs)
    assert all(a["workloads"] == 16 and a["edges"] == 18 for a in built)
    assert built[0]["tokens_here"] == sum(
        presets.routed_tokens(_cfg(), 24))
    assert counter("presets.moe_layer.calls") - calls0 == len(seqs)
    assert counter("presets.moe_layer.stream_draws") - draws0 == 1


def test_random_designs_are_drawn_within_the_pe_budget(fresh_streams):
    """At 28 workloads nearly every uniform draw exceeds a 4096-PE budget,
    so the search's first population would hold no feasible design: a
    draw over the budget is scaled down to it, one within it is kept."""
    from repro.core import SystemSpec
    from repro.core.encoding import DesignSpace, random_design
    cfg = _cfg(n_routed_experts=32, ep_size=4)        # 8 experts held
    spec = SystemSpec.build(presets.moe_layer(cfg, 64), ch_max=1)
    shape = (16, 16, 4, 4, 1, 1)
    free = DesignSpace(spec, max_shape=shape)
    held = DesignSpace(spec, max_shape=shape, max_total_pes=4096)
    keys = jax.random.split(jax.random.PRNGKey(8), 64)
    a = jax.vmap(lambda k: random_design(k, free))(keys)
    b = jax.vmap(lambda k: random_design(k, held))(keys)
    for f in a:
        if f != "shape":
            np.testing.assert_array_equal(a[f], b[f])
    pes_a = np.prod(np.asarray(a["shape"]), axis=-1).sum(-1)
    pes_b = np.prod(np.asarray(b["shape"]), axis=-1).sum(-1)
    assert np.mean(pes_a > 4096) > 0.9 and np.mean(pes_b <= 4096) > 0.9
    assert np.all(np.asarray(b["shape"]) <= np.asarray(a["shape"]))
    assert np.all(np.asarray(b["shape"]) >= 1)
    # c1's attention block: a draw within the budget is the uniform one
    c1 = SystemSpec.build(presets.attention_block(types.SimpleNamespace(
        d_model=64, head_dim=8, n_heads=4, n_kv_heads=2), seq=32), ch_max=1)
    a = jax.vmap(lambda k: random_design(
        k, DesignSpace(c1, max_shape=shape)))(keys)["shape"]
    b = jax.vmap(lambda k: random_design(
        k, DesignSpace(c1, max_shape=shape, max_total_pes=4096)))(keys)
    within = np.prod(np.asarray(a), axis=-1).sum(-1) <= 4096
    assert within.mean() > 0.8
    np.testing.assert_array_equal(np.asarray(b["shape"])[within],
                                  np.asarray(a)[within])
