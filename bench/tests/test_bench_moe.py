"""The DeepSeek-V3 expert-layer configuration: its reference graph
(``bench/graphs/moe_layer.py``) is the library's ``presets.moe_layer``
graph at small and at published widths, the system's evaluations of its
designs agree with the reference, and its cell reads the graph-build
span."""

import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import reference as R  # noqa: E402
from harness.layout import Layout  # noqa: E402
from harness.system import problem, program_graph  # noqa: E402

LAYOUT = Layout(BENCH.parent)
CELL = "deepseek_v3_moe.ep32.sweep"
CONFIG = LAYOUT.config("deepseek_v3_moe.ep32")
SMALL = dict(builder="moe_layer", hidden_size=24, moe_intermediate_size=16,
             n_routed_experts=32, num_experts_per_tok=4, n_shared_experts=2,
             n_group=4, topk_group=2, topk_method="noaux_tc",
             scoring_func="sigmoid", ep_size=4, ep_rank=1, route_seed=7)


def _same_graph(graph, seq):
    nests, edges = R.build_graph(graph, seq)
    g = program_graph({"graph": graph}, seq)
    assert [n.loops for n in nests] == [w.loops for w in g.workloads]
    assert edges == [(e.src, e.dst, e.tensor_src, e.tensor_dst)
                     for e in g.edges]
    spec = R.build_spec(nests, edges, ch_max=1)
    return spec, g


@pytest.mark.parametrize("seq", [1, 37, 500])
def test_reference_graph_is_the_library_graph_small(seq):
    spec, g = _same_graph(SMALL, seq)
    assert (spec.W, spec.CH, spec.E) == (4 + 3 * 8, 1, 2 + 4 * 8)


@pytest.mark.parametrize("seq", [256, 4096])
def test_reference_graph_is_the_library_graph_published(seq):
    spec, g = _same_graph(CONFIG["graph"], seq)
    assert (spec.W, spec.CH, spec.E) == (28, 1, 34)
    assert spec.W * spec.CH + 1 <= R.MAX_NODES
    routed = [w.bounds["i"] for w in g.workloads[4::3]]
    # each routed expert sees about seq tokens (32 * seq * 8 / 256)
    assert 0.8 * seq < min(routed) and max(routed) < 1.2 * seq


def test_configuration_states_its_cuts():
    top = {k: CONFIG[k] for k in ("hidden_size", "moe_intermediate_size",
                                  "num_experts_per_tok", "n_shared_experts",
                                  "n_group", "topk_group", "topk_method",
                                  "scoring_func", "ep_size")}
    assert {k: CONFIG["graph"][k] for k in top} == top
    held = CONFIG["graph"]["n_routed_experts"] // CONFIG["ep_size"]
    assert CONFIG["n_routed_experts"] == held == 8
    assert CONFIG["published"]["n_routed_experts"] == \
        CONFIG["graph"]["n_routed_experts"] == 256
    spec = next(c for c in LAYOUT.spec["configs"]
                if c["name"] == "deepseek_v3_moe.ep32")
    assert sorted(spec["reduced"]) == sorted(CONFIG["reduced"]) == \
        sorted(CONFIG["published"]) == sorted(CONFIG["cuts"])


def test_system_agrees_with_the_reference_small():
    import jax
    from repro.core.encoding import random_design
    from repro.core.evaluate import evaluate_system
    from repro.core.optimizer import metric_stack
    config = dict(graph=SMALL, ch_max=1, max_shape=[8, 8, 2, 2, 1, 1],
                  max_total_pes=0, objectives=["latency_ns"])
    p = problem(config, 90)
    spec = R.build_spec(*R.build_graph(SMALL, 90), ch_max=1)
    keys = jax.random.split(jax.random.PRNGKey(4), 16)
    ds = jax.vmap(lambda k: random_design(k, p.space))(keys)
    got = np.asarray(jax.jit(jax.vmap(lambda d: metric_stack(
        evaluate_system(p.spec, d))))(ds), np.float64)
    gaps = []
    for i in range(len(keys)):
        d = {k: np.asarray(v[i]) for k, v in ds.items()}
        ref = R.Model().evaluate(spec, d)
        gaps.append(float(np.max(np.abs(got[i] - ref) / np.abs(ref))))
    assert max(gaps) < 1e-5, gaps


def test_cell_reports_the_graph_build_metric():
    assert [m["name"] for m in LAYOUT.end_to_end(CELL)] == \
        ["setup_s", "evals_per_s", "front_p50_s"]
    assert [m["name"] for m in LAYOUT.per_layer(CELL)] == \
        ["graph_build_ms_per_query"]
    assert "graph_build_ms_per_query" not in [
        m["name"] for m in LAYOUT.per_layer("internlm2_attn.c1.sweep")]


def test_graph_build_reader(monkeypatch):
    from harness import scopes
    read = LAYOUT.reader("graph_build_ms_per_query")
    host = ("/host:CPU", "python#0")
    rows = [host + ("presets.moe_layer", 0, 3_000_000, ""),
            host + ("presets.moe_route_draw", 1_000_000, 1_000_000, ""),
            host + ("presets.moe_layer", 9_000_000, 1_000_000, ""),
            host + ("explore.dispatch", 20_000_000, 5_000_000, "")]
    run = types.SimpleNamespace(trace={"gaps": []}, queries=2, evals=0)
    monkeypatch.setattr(scopes, "_CACHE", {})
    monkeypatch.setattr(scopes, "rows_of", lambda r: rows)
    assert read(run) == pytest.approx(2.0)      # 4 ms over 2 queries
    # a program without the span (the parent of this configuration)
    monkeypatch.setattr(scopes, "rows_of", lambda r: rows[3:])
    assert read(run) is None
    monkeypatch.setattr(scopes, "rows_of", lambda r: [])
    assert read(run) is None
