"""Model-level tests: dataflow analysis invariants, network routing and
contention, cost model (Fig.-3 qualitative behavior), pipeline model
(Fig.-5 example), and property tests over random design points."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

import repro.core as C
from repro.core import network
from repro.core.dataflow import analyze_chiplet
from repro.core.perf_model import StageGraph, Stage, build_stage_graph
from repro.core.simulator import SystolicConfig, simulate_matmul
from repro.core.workload import matmul, conv2d, WorkloadGraph


def _design_mm(shape, spatial, order=None, t1=(64, 64, 64), t2=(256, 256, 512)):
    order = order if order is not None else [0, 1, 2, 3, 4, 5, 6, 7]
    return (jnp.asarray(shape, jnp.int32), jnp.asarray(spatial, jnp.int32),
            jnp.asarray([order] * 3, jnp.int32),
            jnp.asarray([list(t1) + [1] * 5, list(t2) + [1] * 5], jnp.int32))


def test_dataflow_mac_conservation():
    wl = matmul("mm", 256, 256, 256).to_arrays()
    sh, sp, od, ti = _design_mm([8, 8, 2, 2, 2, 2], [0, 1, 0, 1, 0, 1])
    an = analyze_chiplet(wl, sh, sp, od, ti)
    assert float(an["total_macs"]) == 256 ** 3
    assert float(an["mac_count"]) == pytest.approx(256 ** 3, rel=1e-6)
    assert 0 < float(an["utilization"]) <= 1.0


def test_dataflow_min_traffic_bound():
    """External traffic must be at least the compulsory (cold) volume of each
    tensor's per-chiplet share."""
    w = matmul("mm", 256, 256, 256)
    wl = w.to_arrays()
    sh, sp, od, ti = _design_mm([8, 8, 2, 2, 1, 1], [0, 1, 0, 1, 0, 1])
    an = analyze_chiplet(wl, sh, sp, od, ti)
    cold = (w.tensor_size("A") + w.tensor_size("B") + w.tensor_size("C")) * 2
    assert float(an["ext_bytes"]) >= cold * 0.99


def test_dataflow_order_changes_traffic():
    """Output-inner vs reduction-inner loop orders must differ in external
    traffic (reuse is order-dependent) — the core of dataflow exploration."""
    wl = matmul("mm", 512, 512, 512).to_arrays()
    sh = [16, 16, 2, 2, 1, 1]
    sp = [0, 1, 0, 1, 0, 1]
    _, _, od_k_inner, ti = _design_mm(sh, sp, [0, 1, 2, 3, 4, 5, 6, 7],
                                      t2=(64, 64, 64))
    _, _, od_k_outer, _ = _design_mm(sh, sp, [2, 0, 1, 3, 4, 5, 6, 7],
                                     t2=(64, 64, 64))
    a1 = analyze_chiplet(wl, *_design_mm(sh, sp, [0, 1, 2, 3, 4, 5, 6, 7],
                                         t2=(64, 64, 64))[0:4])
    a2 = analyze_chiplet(wl, *_design_mm(sh, sp, [2, 0, 1, 3, 4, 5, 6, 7],
                                         t2=(64, 64, 64))[0:4])
    assert float(a1["ext_bytes"]) != float(a2["ext_bytes"])


def test_dataflow_bigger_tile_less_refill():
    wl = matmul("mm", 512, 512, 512).to_arrays()
    sh, sp = [16, 16, 2, 2, 1, 1], [0, 1, 0, 1, 0, 1]
    small = analyze_chiplet(wl, *_design_mm(sh, sp, t2=(64, 64, 64)))
    big = analyze_chiplet(wl, *_design_mm(sh, sp, t2=(256, 256, 512)))
    assert float(big["ext_bytes"]) <= float(small["ext_bytes"])
    assert float(big["chip_buf_bytes"]) > float(small["chip_buf_bytes"])


# ---------------------------------------------------------------------------
# network
# ---------------------------------------------------------------------------
def test_routing_tables_reach_destination():
    nh_all = network.next_hop_tables()
    for fam in range(network.N_FAMILIES):
        for n in (2, 5, 9, 16, 36):
            nh = nh_all[network.topo_code(fam, n)]
            for s in range(n):
                for d in list(range(n)) + [n]:       # incl. DRAM node
                    cur, hops = s, 0
                    while cur != d and hops < network.N_TOT:
                        cur = int(nh[cur, d])
                        hops += 1
                    assert cur == d, (fam, n, s, d)


@pytest.mark.parametrize("fam", range(network.N_FAMILIES))
def test_routing_paths_bounded_by_node_count(fam):
    """The hop walk's length rests on this: on the table of n chiplet
    nodes plus DRAM, every path between any two ids of the full table
    (unused slots included) arrives within n hops."""
    nh_all = network.next_hop_tables()
    ids = np.arange(network.N_TOT)
    src, dst = np.meshgrid(ids, ids, indexing="ij")
    for n in range(1, network.MAX_NODES + 1):
        nh = nh_all[network.topo_code(fam, n)].astype(np.int64)
        cur = src
        for _ in range(n):
            cur = nh[cur, dst]
        assert (cur == dst).all(), (fam, n)
        # and the table cut to any bound K > n never leaves [0, K)
        for k in range(n + 1, network.N_TOT + 1):
            assert nh[:k, :k].max() < k, (fam, n, k)


def _bounded_space(name):
    """Spaces whose designs sit on fewer than ``N_TOT`` nodes: the bench's
    c1 (4 workloads, one 1x1 chiplet array each), a 28-workload expert
    layer of the same kind, and ch_max 4 with 2x2 arrays (unused
    placement slots, a design-dependent DRAM id)."""
    import types
    one = (16, 16, 4, 4, 1, 1)
    if name == "c1":
        g = C.presets.attention_block(types.SimpleNamespace(
            d_model=64, head_dim=8, n_heads=4, n_kv_heads=2), seq=32)
        return C.DesignSpace(C.SystemSpec.build(g, ch_max=1), max_shape=one,
                             max_total_pes=4096)
    if name == "moe_w28":
        g = C.presets.moe_layer(types.SimpleNamespace(
            hidden_size=16, moe_intermediate_size=8, n_routed_experts=32,
            num_experts_per_tok=4, n_shared_experts=1, n_group=4,
            topk_group=2, topk_method="noaux_tc", scoring_func="sigmoid",
            ep_size=4, ep_rank=0, route_seed=11), 64)
        return C.DesignSpace(C.SystemSpec.build(g, ch_max=1), max_shape=one,
                             max_total_pes=4096)
    g = C.presets.attention_block(types.SimpleNamespace(
        d_model=64, head_dim=8, n_heads=4, n_kv_heads=2), seq=32)
    return C.DesignSpace(C.SystemSpec.build(g, ch_max=4),
                         max_shape=(8, 8, 2, 2, 2, 2))


def test_node_bound_of_a_space():
    assert _bounded_space("c1").node_bound == 5
    assert _bounded_space("moe_w28").W == 28
    assert _bounded_space("moe_w28").node_bound == 29
    assert _bounded_space("ch_max4_2x2").node_bound == 17
    # the default 6x6 chiplet arrays reach the full table
    space = _bounded_space("c1")
    assert C.DesignSpace(space.spec).node_bound == network.N_TOT


@pytest.mark.parametrize("name", ["c1", "moe_w28", "ch_max4_2x2"])
def test_node_bound_leaves_metrics_bit_identical(name):
    """256 seeded random designs over all four families: the network
    sized by the space's node bound gives the 37-node model's metrics
    bit for bit (the bound drops only hops after arrival and links no
    flow can use)."""
    from repro.core.evaluate import evaluate_arrays
    from repro.explore.nsga import sample_designs
    space = _bounded_space(name)
    k = space.node_bound
    assert k < network.N_TOT
    designs = sample_designs(jax.random.PRNGKey(1700000017), space, 256)
    assert set(np.asarray(designs["family"]).tolist()) == set(
        range(network.N_FAMILIES))
    spec = space.spec
    dims = (spec.W, spec.CH, spec.E)

    def metrics(bound):
        f = jax.jit(jax.vmap(lambda d, arr: evaluate_arrays(
            arr, d, dims, node_bound=bound), in_axes=(0, None)))
        return jax.device_get(f(designs, spec.arrays))

    got, want = metrics(k), metrics(network.N_TOT)
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)


@pytest.mark.parametrize("fam,n,src,dst", [
    (network.FAM_MESH, 9, [0], [8]),
    (network.FAM_CHAIN, 3, [0, 1], [2, 2])])
def test_network_on_bounded_table_matches_full(fam, n, src, dst):
    """``route_links`` and ``evaluate_network`` on the table cut to K =
    n + 1 nodes walk K - 1 hops and give the full table's delays, hops
    and hotspot."""
    nh = network.next_hop_tables()[network.topo_code(fam, n)]
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    k = n + 1
    links, hops = network.route_links(jnp.asarray(nh[:k, :k]), src, dst)
    links_full, hops_full = network.route_links(jnp.asarray(nh), src, dst)
    assert links.shape[0] == k - 1
    np.testing.assert_array_equal(hops, hops_full)
    np.testing.assert_array_equal(links, links_full[:k - 1])
    f = len(src)
    args = (src, dst, jnp.full((f,), 32.0), jnp.full((f,), 3.2e4),
            jnp.ones((f,), bool), 32.0, 128.0, 20.0, n)
    cut = network.evaluate_network(jnp.asarray(nh[:k, :k]), *args)
    full = network.evaluate_network(jnp.asarray(nh), *args)
    assert set(cut) == set(full)
    for key in full:
        np.testing.assert_array_equal(cut[key], full[key], err_msg=key)


def test_mesh_xy_hop_count():
    nh = network.next_hop_tables()[network.topo_code(network.FAM_MESH, 9)]
    # 3x3 mesh: node 0 -> node 8 = 2 + 2 hops
    links, hops = network.route_links(
        jnp.asarray(nh), jnp.asarray([0]), jnp.asarray([8]))
    assert int(hops[0]) == 4


def test_contention_throttles_proportionally():
    """Paper Fig. 5b: two flows sharing a link each get bandwidth pro-rata."""
    nh = jnp.asarray(network.next_hop_tables()[
        network.topo_code(network.FAM_CHAIN, 3)])
    src = jnp.asarray([0, 1])
    dst = jnp.asarray([2, 2])
    bwr = jnp.asarray([32.0, 32.0])
    vol = jnp.asarray([3.2e4, 3.2e4])
    out = network.evaluate_network(nh, src, dst, bwr, vol,
                                   jnp.asarray([True, True]),
                                   32.0, 128.0, 20.0, 3)
    # link 1->2 carries both flows: each gets 16 GB/s; flow0 has 2 hops
    assert float(out["delay_ns"][0]) == pytest.approx(2 * 20 + 3.2e4 / 16.0,
                                                      rel=1e-3)
    assert float(out["delay_ns"][1]) == pytest.approx(1 * 20 + 3.2e4 / 16.0,
                                                      rel=1e-3)


def test_no_contention_full_bandwidth():
    nh = jnp.asarray(network.next_hop_tables()[
        network.topo_code(network.FAM_CHAIN, 3)])
    out = network.evaluate_network(
        nh, jnp.asarray([0]), jnp.asarray([1]), jnp.asarray([16.0]),
        jnp.asarray([1.6e4]), jnp.asarray([True]), 32.0, 128.0, 20.0, 3)
    assert float(out["delay_ns"][0]) == pytest.approx(20 + 1.6e4 / 16.0,
                                                      rel=1e-3)


# ---------------------------------------------------------------------------
# cost model (Fig. 3 qualitative)
# ---------------------------------------------------------------------------
def test_yield_decreases_with_area():
    y1 = float(C.die_yield(100.0, 0.0009, 4.0))
    y2 = float(C.die_yield(600.0, 0.0009, 4.0))
    assert 0 < y2 < y1 < 1


def test_fig3_large_die_chipletization_wins():
    """TPU-class (331mm^2) dies: 3 chiplets on organic substrate must be
    cheaper than the 3x-area monolithic die (paper Fig. 3)."""
    mono = float(C.monolithic_cost(3 * 331.0))
    chl = float(C.package_cost(jnp.asarray([331.0] * 3), C.PKG_ORGANIC))
    assert chl < mono


def test_fig3_small_die_chipletization_no_win():
    """Gemmini-class (1.1mm^2) dies: negligible die-cost reduction, bonding
    overhead dominates -> chipletization does NOT pay off (paper Fig. 3)."""
    mono = float(C.monolithic_cost(3 * 1.1))
    chl = float(C.package_cost(jnp.asarray([1.1] * 3), C.PKG_ORGANIC))
    assert chl > mono


def test_fig3_interposer_costs_more():
    areas = jnp.asarray([331.0] * 3)
    organic = float(C.package_cost(areas, C.PKG_ORGANIC))
    passive = float(C.package_cost(areas, C.PKG_PASSIVE))
    active = float(C.package_cost(areas, C.PKG_ACTIVE))
    assert organic < passive < active


# ---------------------------------------------------------------------------
# pipeline model (paper Fig. 5a example)
# ---------------------------------------------------------------------------
def test_fig5_stage_graph():
    """v0, v1 in parallel; e01: v0->v2 ; e12: v1->v2."""
    sg = build_stage_graph(
        compute_delays={0: 10.0, 1: 8.0, 2: 6.0},
        binding={0: 0, 1: 1, 2: 2},
        deps=[(0, 2, 3.0), (1, 2, 5.0)])
    # longest path: v1(8) + e(5) + v2(6) = 19
    assert sg.latency() == pytest.approx(19.0)
    assert sg.throughput() == pytest.approx(1 / 10.0)
    assert sg.total_time(ticks=4) == pytest.approx(19.0 + 3 * 10.0)


def test_shared_chiplet_merges_stages():
    sg = build_stage_graph(
        compute_delays={0: 10.0, 1: 8.0, 2: 6.0},
        binding={0: 0, 1: 0, 2: 1},                 # wl 0,1 share chiplet 0
        deps=[(0, 2, 3.0), (1, 2, 3.0)])
    # merged stage = 18, then transfer 3, then 6
    assert sg.latency() == pytest.approx(27.0)


# ---------------------------------------------------------------------------
# full-evaluator properties
# ---------------------------------------------------------------------------
@given(seed=st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_random_designs_yield_finite_positive_metrics(seed):
    g = WorkloadGraph([matmul("mm", 128, 128, 128)], [])
    spec = C.SystemSpec.build(g, ch_max=36)
    space = C.DesignSpace(spec)
    d = C.random_design(jax.random.PRNGKey(seed), space)
    m = C.evaluate_system(spec, d)
    for k in ("latency_ns", "energy_pj", "cost_usd", "area_mm2", "edp"):
        v = float(m[k])
        assert np.isfinite(v) and v > 0, (k, v)
    assert 0 <= float(m["utilization"]) <= 1.0 + 1e-6


def test_analytical_vs_systolic_simulator():
    """Sec. V-A: analytical latency within ~10% of the cycle-approximate
    systolic simulation for compute-bound matmuls on an 8x8 array."""
    errs = []
    for (M, N, K) in [(128, 128, 128), (256, 256, 256), (512, 512, 128)]:
        sim = simulate_matmul(M, N, K, SystolicConfig(8, 8))
        wl = matmul("mm", M, N, K).to_arrays()
        sh = jnp.asarray([8, 8, 1, 1, 1, 1], jnp.int32)
        sp = jnp.asarray([0, 1, 0, 1, 0, 1], jnp.int32)
        od = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7]] * 3, jnp.int32)
        ti = jnp.asarray([[8, 8, K] + [1] * 5, [M, N, K] + [1] * 5], jnp.int32)
        an = analyze_chiplet(wl, sh, sp, od, ti, ext_bw_gbps=128.0)
        err = abs(float(an["delay_ns"]) - sim["latency_ns"]) / sim["latency_ns"]
        errs.append(err)
    assert np.mean(errs) < 0.12, errs
