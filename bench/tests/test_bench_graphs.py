"""A configuration's reference graph is a file of its own,
``bench/graphs/<builder>.py``, found by the configuration's builder
name: the builders there build the graphs written out below; at the
c1 configuration's widths their loop-nest encoding and the reference's
evaluations equal ``data/graph_specs.npz``, recorded when the builders
still sat in ``reference.py``; a new builder file is found with no
harness file touched, and an unknown builder names the file it missed;
the system side builds the library's own graph."""

import json
import re
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import reference as R  # noqa: E402
from harness.layout import Layout  # noqa: E402
from harness.reference import Loopnest, Tensor  # noqa: E402
from harness.system import program_graph  # noqa: E402

C1 = Layout(BENCH.parent).config("internlm2_attn.c1")
FIXTURE = BENCH / "tests/data/graph_specs.npz"
NEST_FIELDS = ("bounds", "loopmask", "A", "tmask", "dmask", "is_out")
EDGE_FIELDS = ("esrc", "edst", "edst_tensor", "emask", "ext_in", "fin_out")
# every nest is C[i,j] += A[i,k] * B[k,j]
MM = (Tensor("A", (("i",), ("k",))), Tensor("B", (("k",), ("j",))),
      Tensor("C", (("i",), ("j",)), True))


def draw_design(rng, spec, max_shape, max_logB=3):
    """A design of ``spec``'s space drawn from ``rng`` field by field, as
    the library's uniform random design is drawn."""
    W, L = spec.W, R.MAX_LOOPS
    nl = np.asarray([int(w["loopmask"].sum()) for w in spec.wl])
    bounds = np.stack([w["bounds"] for w in spec.wl])
    tiling = np.maximum(1, np.floor(np.maximum(bounds, 1)[:, None, :]
                                    ** rng.random((W, 2, L))))
    return dict(shape=rng.integers(1, np.asarray(max_shape) + 1, (W, 6)),
                spatial=rng.integers(0, nl[:, None], (W, 6)),
                order=np.argsort(rng.random((W, 3, L)), axis=-1),
                tiling=tiling.astype(np.int64),
                pipe=np.where(rng.random(W) < 0.5, rng.integers(0, nl), L),
                logB=np.int64(rng.integers(0, max_logB + 1)),
                packaging=np.int64(rng.integers(0, 3)),
                family=np.int64(rng.integers(0, R.N_FAMILIES)),
                placement=rng.permutation(W * spec.CH))


@pytest.mark.parametrize("graph,nests,edges", [
    ({"builder": "attention_block", "d_model": 8, "head_dim": 2,
      "n_heads": 2, "n_kv_heads": 1},
     [Loopnest((("i", 5), ("j", 8), ("k", 8)), MM),
      Loopnest((("i", 5), ("j", 5), ("k", 2)), MM),
      Loopnest((("i", 5), ("j", 2), ("k", 5)), MM),
      Loopnest((("i", 5), ("j", 8), ("k", 4)), MM)],
     [(0, 1, "C", "A"), (1, 2, "C", "A"), (2, 3, "C", "A")]),
    ({"builder": "mlp_stack", "d_model": 6, "d_ff": 12},
     [Loopnest((("i", 5), ("j", 12), ("k", 6)), MM),
      Loopnest((("i", 5), ("j", 12), ("k", 6)), MM),
      Loopnest((("i", 5), ("j", 6), ("k", 12)), MM)],
     [(0, 2, "C", "A"), (1, 2, "C", "B")]),
], ids=["attention_block", "mlp_stack"])
def test_builder_files_build_the_written_graphs(graph, nests, edges):
    got_nests, got_edges = R.build_graph(graph, 5)
    assert list(got_nests) == nests
    assert list(got_edges) == edges


CASES = {f"c1_{s}": (C1["graph"], s, int(C1["ch_max"]), C1["max_shape"])
         for s in (256, 1000, 4096)}
CASES["mlp_c4_700"] = ({"builder": "mlp_stack", "d_model": 5120,
                        "d_ff": 1536}, 700, 4, [16, 16, 4, 4, 2, 2])


@pytest.mark.parametrize("case", list(CASES))
def test_spec_and_evaluations_match_the_recording(case):
    graph, seq, ch_max, max_shape = CASES[case]
    spec = R.build_spec(*R.build_graph(graph, seq), ch_max=ch_max)
    with np.load(FIXTURE) as rec:
        assert [spec.W, spec.CH, spec.E] == list(rec[f"{case}/WCHE"])
        for f in NEST_FIELDS:
            np.testing.assert_array_equal(
                np.stack([w[f] for w in spec.wl]), rec[f"{case}/{f}"])
        for f in EDGE_FIELDS:
            np.testing.assert_array_equal(getattr(spec, f),
                                          rec[f"{case}/{f}"])
        rng = np.random.default_rng(seq)
        got = np.stack([R.Model().evaluate(spec, draw_design(
            rng, spec, max_shape)) for _ in range(8)])
        # same code on the same data; the tolerance only admits another
        # CPU's rounding of pow and sqrt
        np.testing.assert_allclose(got, rec[f"{case}/evaluate"],
                                   rtol=1e-12, atol=0)


def test_new_builder_file_is_found_with_no_harness_edit(tmp_path):
    before = {p: p.read_bytes() for p in BENCH.rglob("*.py")}
    graphs = tmp_path / "graphs"
    graphs.mkdir()
    (graphs / "toy_chain.py").write_text(
        "from harness.reference import matmul\n\n\n"
        "def build(d, depth, seq):\n"
        "    return ([matmul(seq, d, d) for _ in range(depth)],\n"
        "            [(i, i + 1, 'C', 'A') for i in range(depth - 1)])\n")
    graph = {"builder": "toy_chain", "d": 16, "depth": 3}
    nests, edges = R.build_graph(graph, 8, graphs_dir=graphs)
    assert nests == [Loopnest((("i", 8), ("j", 16), ("k", 16)), MM)] * 3
    assert edges == [(0, 1, "C", "A"), (1, 2, "C", "A")]
    spec = R.build_spec(nests, edges, ch_max=1)
    d = draw_design(np.random.default_rng(0), spec, [4, 4, 2, 2, 1, 1])
    assert np.all(np.isfinite(R.Model().evaluate(spec, d)))
    assert R.graph_module("toy_chain", graphs) is \
        R.graph_module("toy_chain", graphs)          # loaded once
    assert not (R.GRAPHS_DIR / "toy_chain.py").exists()
    assert {p: p.read_bytes() for p in BENCH.rglob("*.py")} == before


def test_unknown_builder_names_the_missing_file(tmp_path):
    missing = tmp_path / "graphs" / "no_such_block.py"
    with pytest.raises(KeyError, match=re.escape(str(missing))):
        R.build_graph({"builder": "no_such_block", "d": 4}, 8,
                      graphs_dir=tmp_path / "graphs")
    with pytest.raises(KeyError, match="no_such_block.py"):
        R.build_graph({"builder": "no_such_block"}, 8)


@pytest.mark.parametrize("graph,cfg", [
    (C1["graph"], {k: v for k, v in C1["graph"].items() if k != "builder"}),
    ({"builder": "mlp_stack", "d_model": 5120, "d_ff": 1536},
     {"d_model": 5120, "d_ff": 1536, "n_experts": 0, "expert_ff": 1536}),
], ids=["attention_block", "mlp_stack"])
def test_program_graph_is_the_library_builder(graph, cfg):
    from repro.core import presets
    want = getattr(presets, graph["builder"])(types.SimpleNamespace(**cfg),
                                              seq=300)
    assert program_graph({"graph": graph}, 300) == want


def test_expert_package_graph_fits_the_network():
    """A test graph of one DeepSeek-V2 expert-parallel package (34
    matmuls at ch_max 1: 35 network nodes with DRAM) builds, encodes
    and evaluates through a ``graphs_dir`` of its own."""
    widths = json.loads((BENCH / "tests/graphs/moe_package.json")
                        .read_text())
    nests, edges = R.build_graph(widths, 1000,
                                 graphs_dir=BENCH / "tests/graphs")
    spec = R.build_spec(nests, edges, ch_max=1)
    assert (spec.W, spec.CH, spec.E) == (34, 1, 42)
    assert spec.W * spec.CH + 1 <= R.MAX_NODES
    routed = [n.loops[0][1] for n in nests[4:][::3]]
    assert len(routed) == 10 and min(routed) >= 1
    mod = R.graph_module("moe_package", BENCH / "tests/graphs")
    tokens = mod.routed_tokens(1000, 160, 6, 8, 3, seed=0)
    assert tokens.sum() == 6 * 1000 and list(tokens[:10]) == routed
    d = draw_design(np.random.default_rng(1), spec, [8, 8, 2, 2, 1, 1])
    assert np.all(np.isfinite(R.Model().evaluate(spec, d)))
