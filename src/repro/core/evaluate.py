"""System-level evaluation of one chiplet-accelerator design point.

Composes the per-chiplet dataflow analysis, the contention-aware network
model, the energy/area models and the Eq.-1 cost model into the paper's
pipeline performance model (Sec. III-C):

    Lat = max_path sum D(stage),   Thr = 1 / max_stage D,
    T_total = Lat + (B - 1) / Thr          (B = pipeline ticks)

Everything below is pure jnp on fixed-shape arrays so that `jax.vmap`
evaluates whole populations of design points in one `jit` — the TPU-native
re-think of the paper's one-candidate-at-a-time DSE loop.

A ``SystemSpec`` (static, per workload graph) fixes the padded dims:
W workloads x CH chiplets-per-cluster x E edges.  A *design* is a pytree of
arrays (see ``encoding.py``):

    shape   (W, 6)  raw dims [x0,y0,x1,y1,x2,y2]
    spatial (W, 6)  loop ids
    order   (W, 3, L)
    tiling  (W, 2, L)
    pipe    (W,)    pipelined loop id (L => none)
    logB    ()      log2 pipeline ticks
    packaging ()    0..2
    family  ()      network family 0..3
    placement (W*CH,) global chiplet -> node id
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import network as netmod
from .constants import TechConstants, DEFAULT_TECH
from .cost import package_cost
from .dataflow import analyze_chiplet
from .energy import chiplet_energy_pj, chiplet_area_mm2, system_network_energy_pj
from .network import MAX_NODES, N_TOT, evaluate_network, next_hop_tables
from .workload import MAX_LOOPS, MAX_TENSORS, WorkloadGraph

F = jnp.float32
BIG = F(1e18)


@dataclasses.dataclass(frozen=True)
class SystemSpec:
    """Static (non-traced) description of a workload graph, padded."""
    W: int                       # max workloads
    CH: int                      # max chiplets per cluster
    E: int                       # max edges
    arrays: Dict[str, np.ndarray]
    graph: WorkloadGraph

    @staticmethod
    def build(graph: WorkloadGraph, ch_max: int = 8) -> "SystemSpec":
        W = len(graph.workloads)
        E = max(len(graph.edges), 1)
        wl = [w.to_arrays() for w in graph.workloads]
        arr = {k: np.stack([d[k] for d in wl]) for k in wl[0]}
        arr["wmask"] = np.ones(W, bool)

        tname_idx = [
            {t.name: i for i, t in enumerate(w.tensors)}
            for w in graph.workloads
        ]
        esrc = np.zeros(E, np.int32)
        edst = np.zeros(E, np.int32)
        edst_tensor = np.zeros(E, np.int32)
        emask = np.zeros(E, bool)
        for i, e in enumerate(graph.edges):
            esrc[i], edst[i] = e.src, e.dst
            edst_tensor[i] = tname_idx[e.dst][e.tensor_dst]
            emask[i] = True
        arr.update(esrc=esrc, edst=edst, edst_tensor=edst_tensor, emask=emask)

        ext_in = np.zeros((W, MAX_TENSORS), bool)
        for wi, tn in graph.external_inputs():
            ext_in[wi, tname_idx[wi][tn]] = True
        fin_out = np.zeros((W, MAX_TENSORS), bool)
        for wi, tn in graph.final_outputs():
            fin_out[wi, tname_idx[wi][tn]] = True
        arr.update(ext_in=ext_in, fin_out=fin_out)
        return SystemSpec(W=W, CH=ch_max, E=E, arrays=arr, graph=graph)


def _tick_bounds(bounds, loopmask, pipe_loop, B):
    """Divide the pipelined loop's bound by B (the per-tick sub-problem)."""
    l = jnp.arange(MAX_LOOPS)
    hit = (l == pipe_loop) & loopmask
    return jnp.where(hit, jnp.maximum((bounds + B - 1) // B, 1), bounds)


def evaluate_system(spec: SystemSpec, design: Dict,
                    tech: TechConstants = DEFAULT_TECH) -> Dict:
    """Full PPA + cost evaluation of one design point (jit/vmap-able)."""
    return evaluate_arrays(spec.arrays, design, (spec.W, spec.CH, spec.E),
                           tech)


def evaluate_arrays(arrays: Dict, design: Dict, dims: Tuple[int, int, int],
                    tech: TechConstants = DEFAULT_TECH) -> Dict:
    """Same as ``evaluate_system`` but over raw (traced) workload arrays, so
    one jit compilation is shared by every workload graph with equal padded
    dims (W, CH, E) — the whole Fig.-7 suite compiles once."""
    arr = {k: jnp.asarray(v) for k, v in arrays.items()}
    W, CH, E = dims
    L = MAX_LOOPS

    pkg = design["packaging"]
    cap = jnp.asarray(tech.link_bw_cap, F)[pkg]
    B = (2 ** design["logB"]).astype(F)

    # ---- per-workload chiplet analysis (per pipeline tick) -----------------
    def analyze_one(wi, ext_bw):
        with jax.named_scope("dataflow"):
            wl = {k: arr[k][wi] for k in
                  ("bounds", "loopmask", "A", "tmask", "dmask", "is_out")}
            wl = dict(wl)
            wl["bounds"] = _tick_bounds(wl["bounds"], wl["loopmask"],
                                        design["pipe"][wi],
                                        (2 ** design["logB"]).astype(
                                            jnp.int32))
            return analyze_chiplet(wl, design["shape"][wi],
                                   design["spatial"][wi], design["order"][wi],
                                   design["tiling"][wi], tech=tech,
                                   ext_bw_gbps=ext_bw)

    an0 = jax.vmap(lambda wi: analyze_one(wi, cap))(jnp.arange(W))
    d_stage0 = an0["delay_ns"]                                  # (W,)

    with jax.named_scope("network"):
        n_chips = an0["n_chiplets"].astype(jnp.int32)               # (W,)
        base = jnp.cumsum(n_chips) - n_chips        # global chiplet base
        n_nodes = jnp.sum(n_chips)
        placement = design["placement"]                             # (W*CH,)

        # ---- communication graph (flows) ------------------------------------
        # block A: DRAM->chiplet external-input streams  (W*CH flows)
        # block B: chiplet->DRAM final-output writebacks (W*CH flows)
        # block C: producer->consumer intermediate flows (E*CH flows)
        ch_ids = jnp.arange(CH)

        def wl_chip_node(wi, j):
            g = jnp.clip(base[wi] + j, 0, W * CH - 1)
            return placement[g]

        wgrid = jnp.repeat(jnp.arange(W), CH)                       # (W*CH,)
        jgrid = jnp.tile(ch_ids, W)
        chip_valid = jgrid < n_chips[wgrid]
        node_of = jax.vmap(wl_chip_node)(wgrid, jgrid)              # (W*CH,)

        ein = an0["ext_in_bytes_t"]             # (W, T) per chiplet
        eout = an0["ext_out_bytes_t"]
        dram_in_vol = jnp.sum(ein * arr["ext_in"], axis=1)[wgrid]   # (W*CH,)
        dram_out_vol = jnp.sum(eout * arr["fin_out"], axis=1)[wgrid]

        dram_node = n_nodes
        srcA = jnp.full((W * CH,), 0, jnp.int32) + dram_node
        dstA = node_of
        volA, mA = dram_in_vol, chip_valid & (dram_in_vol > 0)
        srcB, dstB = node_of, jnp.full((W * CH,), 0, jnp.int32) + dram_node
        volB, mB = dram_out_vol, chip_valid & (dram_out_vol > 0)

        egrid = jnp.repeat(jnp.arange(E), CH)                       # (E*CH,)
        jg = jnp.tile(ch_ids, E)
        w1, w2 = arr["esrc"][egrid], arr["edst"][egrid]
        mC = arr["emask"][egrid] & (jg < n_chips[w2])
        volC = ein[w2, arr["edst_tensor"][egrid]]   # per consumer chiplet
        srcC = jax.vmap(wl_chip_node)(w1, jg % jnp.maximum(n_chips[w1], 1))
        dstC = jax.vmap(wl_chip_node)(w2, jg)

        src = jnp.concatenate([srcA, srcB, srcC]).astype(jnp.int32)
        dst = jnp.concatenate([dstA, dstB, dstC]).astype(jnp.int32)
        vol = jnp.concatenate([volA, volB, volC])
        fmask = jnp.concatenate([mA, mB, mC])
        fw_src = jnp.concatenate([wgrid, wgrid, w1])    # stage of src
        fw_dst = jnp.concatenate([wgrid, wgrid, w2])
        is_dram_f = jnp.concatenate([jnp.ones_like(mA), jnp.ones_like(mB),
                                     jnp.zeros_like(mC)])

        # bwr_{i,j} = |Omega| / min(D(v_i), D(v_j))
        # (DRAM side: consumer delay)
        d_src = jnp.where(is_dram_f > 0, BIG, d_stage0[fw_src])
        d_min = jnp.minimum(d_src, d_stage0[fw_dst])
        bwr = vol / jnp.maximum(d_min, 1.0)

        # ---- network: provision at hotspot, cap by packaging ----------------
        nh_all = jnp.asarray(next_hop_tables())
        tcode = (design["family"] * (MAX_NODES + 1)
                 + jnp.clip(n_nodes, 1, MAX_NODES))
        nh = nh_all[tcode]
        pre = evaluate_network(nh, src, dst, bwr, vol, fmask,
                               cap, tech.dram_bw, tech.router_delay_ns,
                               n_nodes)
        link_bw = jnp.minimum(jnp.maximum(pre["hotspot"], 1.0), cap)
        net = evaluate_network(nh, src, dst, bwr, vol, fmask,
                               link_bw, tech.dram_bw, tech.router_delay_ns,
                               n_nodes)

        # ---- fixed-point pass: refine stage delays with achieved inbound bw
        # DRAM streaming overlaps compute INSIDE the stage
        # (max(D_C, D_B, D_A), Sec III-C); each workload's effective
        # external bandwidth per chiplet is what its block-A flows
        # achieved under contention.
        ebw_f = jnp.where(fmask, vol / jnp.maximum(net["delay_ns"], 1.0), 0.0)
        ebw_A = ebw_f[: W * CH]
        inbound = jnp.zeros((W,), F).at[wgrid].add(jnp.where(mA, ebw_A, 0.0))
        per_chip_bw = inbound / jnp.maximum(an0["n_chiplets"], 1.0)
        per_chip_bw = jnp.where(per_chip_bw > 0, per_chip_bw, cap)
    an = jax.vmap(lambda wi, bw: analyze_one(wi, bw))(
        jnp.arange(W), jnp.minimum(per_chip_bw, cap))
    d_stage = an["delay_ns"]                                    # (W,)

    with jax.named_scope("network"):
        # ---- transfer-stage delays ------------------------------------------
        # DRAM in/out contributes only the FIRST/LAST tile fill to the
        # path (the bulk is overlapped inside the compute stage);
        # producer->consumer edges are full pipeline transfer stages
        # D(e) = max over the edge's flows.
        fdel = jnp.where(fmask, net["delay_ns"], 0.0)
        hop_lat = net["hops"] * F(tech.router_delay_ns)
        tiles_w = jnp.maximum(an["ext_tiles"], 1.0)                 # (W,)
        first_fill = hop_lat + (fdel - hop_lat) / tiles_w[
            jnp.concatenate([wgrid, wgrid, w1])]
        d_in = jnp.zeros((W,), F).at[wgrid].max(
            jnp.where(mA, first_fill[: W * CH], 0.0))
        d_out = jnp.zeros((W,), F).at[wgrid].max(
            jnp.where(mB, first_fill[W * CH: 2 * W * CH], 0.0))
        eflow = fdel[2 * W * CH:]
        d_edge = jnp.zeros((E,), F).at[egrid].max(jnp.where(mC, eflow, 0.0))

        # ---- DAG longest path (max-plus relaxation over edges) --------------
        dist = d_in + d_stage                                       # (W,)
        def relax(dist, _):
            upd = dist[arr["esrc"]] + d_edge + d_stage[arr["edst"]]
            upd = jnp.where(arr["emask"], upd, -BIG)
            return dist.at[arr["edst"]].max(upd), None
        dist, _ = jax.lax.scan(relax, dist, None, length=W)
        lat_tick = jnp.max(dist + d_out)

        max_stage = jnp.maximum(
            jnp.max(d_stage),
            jnp.maximum(jnp.max(jnp.where(arr["emask"], d_edge, 0.0)),
                        jnp.maximum(jnp.max(d_in), jnp.max(d_out))))
        latency = lat_tick + (B - 1.0) * max_stage
        throughput = 1.0 / jnp.maximum(max_stage, 1e-9)

    with jax.named_scope("energy_cost"):
        # ---- energy ---------------------------------------------------------
        e_compute = jnp.sum(jax.vmap(
            lambda i: chiplet_energy_pj({k: v[i] for k, v in an.items()},
                                        tech))(jnp.arange(W))) * B
        e_net = system_network_energy_pj(net, pkg, tech) * B
        energy = e_compute + e_net

        # ---- area / cost ----------------------------------------------------
        area_w = jax.vmap(
            lambda i: chiplet_area_mm2({k: v[i] for k, v in an.items()},
                                       link_bw, pkg, tech))(
                jnp.arange(W))                                  # (W,)
        die_areas = jnp.where(chip_valid, area_w[wgrid], 0.0)       # (W*CH,)
        cost = package_cost(die_areas, pkg, tech)
        area = jnp.sum(die_areas)

        # ---- calibration corrections ----------------------------------------
        # Per-metric multiplicative factors fitted by repro.calib; all
        # default to 1.0 (exact multiplicative identity), so the
        # uncalibrated model returns bit-identical numbers to a build
        # without this block.
        cl, ce = F(tech.corr_latency), F(tech.corr_energy)
        ca, cc = F(tech.corr_area), F(tech.corr_cost)
        latency, lat_tick = latency * cl, lat_tick * cl
        throughput = throughput / cl
        d_stage, d_edge = d_stage * cl, d_edge * cl
        e_compute, e_net = e_compute * ce, e_net * ce
        energy = energy * ce
        cost, area = cost * cc, area * ca

        return dict(
            latency_ns=latency, lat_tick_ns=lat_tick,
            throughput_per_ns=throughput,
            energy_pj=energy, edp=energy * 1e-12 * latency * 1e-9,
            cost_usd=cost, area_mm2=area,
            utilization=jnp.sum(an["utilization"] * an["n_chiplets"])
            / jnp.maximum(jnp.sum(an["n_chiplets"]), 1.0),
            hotspot_gbps=pre["hotspot"], link_bw_gbps=link_bw,
            n_nodes=n_nodes, stage_delays_ns=d_stage, edge_delays_ns=d_edge,
            energy_compute_pj=e_compute, energy_network_pj=e_net,
            dram_bytes=net["dram_bytes"] * B,
            d2d_byte_hops=net["d2d_byte_hops"] * B,
        )


def make_batch_evaluator(spec: SystemSpec, tech: TechConstants = DEFAULT_TECH):
    """vmapped + jitted population evaluator: designs (stacked pytree) -> metrics."""
    def one(design):
        return evaluate_system(spec, design, tech)
    return jax.jit(jax.vmap(one))
