"""Plain reference of the design model the benchmark checks served fronts
against: Monad's analytical performance, energy, area and cost model
(arXiv:2302.11256, Sec. III-IV) for one design point, in straightforward
numpy, one design at a time.

It imports nothing of the system under test.  Workload graphs are built
from a configuration's published widths by ``bench/graphs/<builder>.py``
(found by the configuration's builder name) out of the ``Tensor``,
``Loopnest`` and ``matmul`` defined here; the padded loop-nest encoding
and the routing tables are derived here, and the technology constants
are the documented defaults, copied.  ``dtype`` selects the
floating type of every real-valued quantity: float64 for the reference,
and a lower precision (``ml_dtypes.bfloat16``) for the control that a
sound comparison has to reject.

Placement follows the paper (Sec. IV-B): a design of n chiplets sits
on an n-node network (n <= 36) plus the DRAM node n.  Workload w owns
``shape[w, 4] * shape[w, 5]`` chiplets, at most ``ch_max``; the chiplets
are numbered workload by workload, and chiplet g's node is the rank of
``placement[g]`` among the n live chiplets' entries, so every chiplet
has a node of its own.  A design outside that space (a workload over
``ch_max`` chiplets, or over 36 chiplets in all) has no evaluation here:
``evaluate`` raises ``OutsideSpace``.
"""

from __future__ import annotations

import dataclasses
import math
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Sequence, Tuple

import numpy as np

from .layout import ROOT, load_module

MAX_LOOPS, MAX_TENSORS, MAX_DIMS = 8, 4, 4
MAX_NODES = 36
N_TOT = MAX_NODES + 1
MAX_HOPS = 40
N_FAMILIES = 4
FAM_CHAIN, FAM_RING, FAM_MESH, FAM_STAR = 0, 1, 2, 3
BIG = 1e18
METRICS = ("latency_ns", "energy_pj", "cost_usd", "area_mm2")


class OutsideSpace(ValueError):
    """A design the modelled design space does not hold."""

# Technology constants (conventions: pJ, mm^2, USD, GB/s = bytes/ns, ns).
TECH = dict(
    clock_ghz=1.0, router_delay_ns=20.0, t_tile_overhead_ns=0.0,
    bytes_per_elem=2,
    e_mac_pj=1.0, e_reg_pj_bit=0.03, e_core_sram_pj_bit=0.30,
    e_chip_sram_pj_bit=0.81, e_dram_pj_bit=8.0,
    e_d2d_pj_bit=(0.50, 0.25, 0.25), e_router_pj_bit=0.10,
    a_pe=0.0015, a_sram_per_mb=2.0, a_router=0.25, a_core_overhead=0.05,
    a_chiplet_overhead=1.0,
    bw_density=(30.0, 180.0, 180.0), link_bw_cap=(32.0, 256.0, 256.0),
    n_link_io=(1.0, 1.0, 0.5), dram_bw=128.0, core_buf_bw=64.0,
    chip_buf_bw=256.0, chip_noc_bw=128.0,
    wafer_diameter_mm=300.0, wafer_cost=3500.0, defect_density_mm2=0.0009,
    yield_alpha=4.0, scribe_mm=0.2, c_bond=(1.0, 2.0, 2.0), bond_yield=0.99,
    c_substrate_mm2=0.01, int_wafer_cost=(0.0, 900.0, 1500.0),
    int_defect_mm2=(0.0, 0.0002, 0.0005), c_process=5.0,
    interposer_margin=1.15)
PKG_ORGANIC = 0


# ---------------------------------------------------------------------------
# workload graphs at published widths
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Tensor:
    name: str
    dims: Tuple[Tuple[str, ...], ...]
    is_output: bool = False


@dataclasses.dataclass(frozen=True)
class Loopnest:
    loops: Tuple[Tuple[str, int], ...]
    tensors: Tuple[Tensor, ...]


def matmul(m: int, n: int, k: int) -> Loopnest:
    """C[i,j] += A[i,k] * B[k,j]"""
    return Loopnest((("i", m), ("j", n), ("k", k)),
                    (Tensor("A", (("i",), ("k",))),
                     Tensor("B", (("k",), ("j",))),
                     Tensor("C", (("i",), ("j",)), True)))


GRAPHS_DIR = ROOT / "bench" / "graphs"
_GRAPH_MODULES: Dict[Path, ModuleType] = {}


def graph_module(builder: str, graphs_dir: Path = GRAPHS_DIR) -> ModuleType:
    """The module of ``<graphs_dir>/<builder>.py``, loaded once: its
    ``build(seq, **widths) -> (nests, edges)`` and, where the library's
    builder reads other attributes than the widths, ``program_cfg``."""
    path = Path(graphs_dir) / f"{builder}.py"
    if path not in _GRAPH_MODULES:
        if not path.is_file():
            raise KeyError(f"no reference graph for builder {builder!r}: "
                           f"{path} does not exist")
        _GRAPH_MODULES[path] = load_module(path, f"bench_graph_{builder}")
    return _GRAPH_MODULES[path]


def build_graph(graph: Dict, seq: int, graphs_dir: Path = GRAPHS_DIR):
    """(nests, edges) of a configuration's ``graph`` entry at ``seq``."""
    kw = {k: v for k, v in graph.items() if k != "builder"}
    return graph_module(graph["builder"], graphs_dir).build(seq=seq, **kw)


def _nest_arrays(w: Loopnest) -> Dict[str, np.ndarray]:
    idx = {n: i for i, (n, _) in enumerate(w.loops)}
    bounds = np.ones(MAX_LOOPS, np.int64)
    loopmask = np.zeros(MAX_LOOPS, bool)
    for i, (_, b) in enumerate(w.loops):
        bounds[i], loopmask[i] = b, True
    A = np.zeros((MAX_TENSORS, MAX_DIMS, MAX_LOOPS), np.int64)
    tmask = np.zeros(MAX_TENSORS, bool)
    dmask = np.zeros((MAX_TENSORS, MAX_DIMS), bool)
    is_out = np.zeros(MAX_TENSORS, bool)
    for ti, t in enumerate(w.tensors):
        tmask[ti], is_out[ti] = True, t.is_output
        for di, grp in enumerate(t.dims):
            dmask[ti, di] = True
            for name in grp:
                A[ti, di, idx[name]] = 1
    return dict(bounds=bounds, loopmask=loopmask, A=A, tmask=tmask,
                dmask=dmask, is_out=is_out)


@dataclasses.dataclass
class Spec:
    W: int
    CH: int
    E: int
    wl: List[Dict[str, np.ndarray]]
    esrc: np.ndarray
    edst: np.ndarray
    edst_tensor: np.ndarray
    emask: np.ndarray
    ext_in: np.ndarray
    fin_out: np.ndarray


def build_spec(nests: Sequence[Loopnest], edges, ch_max: int) -> Spec:
    W, E = len(nests), max(len(edges), 1)
    tidx = [{t.name: i for i, t in enumerate(w.tensors)} for w in nests]
    esrc, edst, etn = (np.zeros(E, np.int64) for _ in range(3))
    emask = np.zeros(E, bool)
    for i, (s, d, _ts, td) in enumerate(edges):
        esrc[i], edst[i], etn[i], emask[i] = s, d, tidx[d][td], True
    produced = {(d, td) for _s, d, _ts, td in edges}
    consumed = {(s, ts) for s, _d, ts, _td in edges}
    ext_in = np.zeros((W, MAX_TENSORS), bool)
    fin_out = np.zeros((W, MAX_TENSORS), bool)
    for wi, w in enumerate(nests):
        for t in w.tensors:
            if not t.is_output and (wi, t.name) not in produced:
                ext_in[wi, tidx[wi][t.name]] = True
            if t.is_output and (wi, t.name) not in consumed:
                fin_out[wi, tidx[wi][t.name]] = True
    return Spec(W, ch_max, E, [_nest_arrays(w) for w in nests], esrc, edst,
                etn, emask, ext_in, fin_out)


# ---------------------------------------------------------------------------
# routing tables
# ---------------------------------------------------------------------------
def _mesh_dims(n):
    r = int(math.isqrt(n))
    while r > 1 and n % r:
        r -= 1
    return r, n // r


def _next_hop(family: int, n: int) -> np.ndarray:
    """Deterministic next hop NH[s, d] over n chiplet nodes plus the DRAM
    node n; entries not on any route lead straight to their target."""
    nh = np.tile(np.arange(N_TOT), (N_TOT, 1))
    rows, cols = _mesh_dims(n)
    for s in range(n):
        for d in range(n):
            if s == d:
                continue
            if family == FAM_CHAIN:
                nh[s, d] = s + 1 if d > s else s - 1
            elif family == FAM_RING:
                nh[s, d] = (s + 1) % n if (d - s) % n <= (s - d) % n \
                    else (s - 1) % n
            elif family == FAM_MESH:
                sr, sc = divmod(s, cols)
                dr, dc = divmod(d, cols)
                nh[s, d] = (sr * cols + sc + (1 if dc > sc else -1)
                            if sc != dc else
                            (sr + (1 if dr > sr else -1)) * cols + sc)
            else:
                nh[s, d] = d if s == 0 else 0
    if family == FAM_MESH:
        for d in range(n):
            nh[n, d] = (d // cols) * cols
        for s in range(n):
            sr, sc = divmod(s, cols)
            nh[s, n] = n if sc == 0 else sr * cols + sc - 1
    else:
        for d in range(n):
            nh[n, d] = 0
        for s in range(n):
            nh[s, n] = n if s == 0 else nh[s, 0]
    return nh


_NH: Dict[Tuple[int, int], np.ndarray] = {}


def next_hop(family: int, n: int) -> np.ndarray:
    if (family, n) not in _NH:
        _NH[(family, n)] = _next_hop(family, n)
    return _NH[(family, n)]


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _cdiv(a, b):
    return (a + b - 1) // b


class Model:
    """The model at one floating type ``dt``."""

    def __init__(self, dt=np.float64):
        self.dt = dt

    def c(self, x):
        return np.asarray(x, self.dt)

    def _split(self, sx, sy, X, Y):
        l = np.arange(MAX_LOOPS)
        return np.where(l == sx, X, 1) * np.where(l == sy, Y, 1)

    def _footprint(self, A, dmask, tile):
        c = self.c
        span = np.sum(c(A) * c(tile), axis=-1)
        nnz = c(np.sum(A != 0, axis=-1))
        fd = np.where(dmask, span - np.maximum(nnz - c(1), c(0)), c(1))
        return np.prod(np.maximum(fd, c(1)), axis=-1)

    def _refills(self, rel, pos, trips, loopmask):
        posb = np.broadcast_to(pos, rel.shape)
        pstar = np.max(np.where(rel & loopmask, posb, -1), axis=-1)
        count = (posb <= pstar[:, None]) & loopmask
        return np.prod(np.where(count, self.c(trips), self.c(1)), axis=-1)

    def _distinct(self, rel, trips, loopmask):
        return np.prod(np.where(rel & loopmask, self.c(trips), self.c(1)),
                       axis=-1)

    def _multicast(self, rel, sx, sy, X, Y):
        mx = np.where(rel[:, sx], 1, X)
        my = np.where(rel[:, sy], 1, Y)
        return self.c(mx if sx == sy else mx * my)

    def chiplet(self, wl, shape, spatial, order, tiling, ext_bw):
        """One workload on one chiplet design; per-tick bounds in ``wl``."""
        c, T = self.c, TECH
        bounds, loopmask = wl["bounds"], wl["loopmask"]
        A, tmask, dmask, is_out = wl["A"], wl["tmask"], wl["dmask"], \
            wl["is_out"]
        rel = np.any(A != 0, axis=1) & tmask[:, None]
        x0, y0, x1, y1, x2, y2 = (max(int(v), 1) for v in shape)
        n_pe, n_core, n_chip = x0 * y0, x1 * y1, x2 * y2
        bpe = c(T["bytes_per_elem"])

        N2 = _cdiv(bounds, self._split(spatial[4], spatial[5], x2, y2))
        t2 = np.minimum(np.maximum(tiling[1], 1), N2)
        n2 = np.where(loopmask, _cdiv(N2, t2), 1)
        share1 = _cdiv(t2, self._split(spatial[2], spatial[3], x1, y1))
        t1 = np.minimum(np.maximum(tiling[0], 1), share1)
        n1 = np.where(loopmask, _cdiv(share1, t1), 1)
        p = np.where(loopmask,
                     _cdiv(t1, self._split(spatial[0], spatial[1], x0, y0)),
                     1)
        pos0, pos1, pos2 = (np.argsort(order[i], kind="stable")
                            for i in range(3))

        pe_pass = np.prod(c(p))
        n1_tot, n2_tot = np.prod(c(n1)), np.prod(c(n2))
        total_macs = np.prod(c(np.where(loopmask, bounds, 1)))
        macs_per_chip = total_macs / c(n_chip)
        tm = c(tmask)
        f1 = self._footprint(A, dmask, t1) * tm
        f2 = self._footprint(A, dmask, t2) * tm
        core_buf = np.sum(f1) * bpe
        chip_buf = np.sum(f2) * bpe

        r0 = self._refills(rel, pos0, p, loopmask)
        d0 = self._distinct(rel, p, loopmask)
        rd0 = np.where(is_out, r0 + np.maximum(r0 - d0, c(0)), r0)
        m0 = self._multicast(rel, spatial[0], spatial[1], x0, y0)
        core_acc_pass = np.sum(rd0 * tm / m0 * c(n_pe)) * bpe
        core_acc = core_acc_pass * n1_tot * n2_tot * c(n_core)

        r1 = self._refills(rel, pos1, n1, loopmask)
        d1 = self._distinct(rel, n1, loopmask)
        rw1 = np.where(is_out, c(2) * r1 - d1, r1)
        m1 = self._multicast(rel, spatial[2], spatial[3], x1, y1)
        chipbuf_pass = np.sum(rw1 * f1 * tm / m1) * bpe * c(n_core)
        chipbuf_acc = chipbuf_pass * n2_tot

        r2 = self._refills(rel, pos2, n2, loopmask)
        d2 = self._distinct(rel, n2, loopmask)
        rw2 = np.where(is_out, c(2) * r2 - d2, r2)
        ext_bytes = np.sum(rw2 * f2 * tm) * bpe
        ext_in = np.where(is_out, c(0), r2 * f2 * tm) * bpe
        ext_out = np.where(is_out, rw2 * f2 * tm, c(0)) * bpe

        clk = c(T["clock_ghz"])
        d_pe = (pe_pass + c(2 * x0 + y0 - 2)) / clk
        core_pass_d = np.maximum(d_pe, core_acc_pass / c(T["core_buf_bw"]))
        chip_pass_d = np.maximum(
            n1_tot * core_pass_d,
            np.maximum(chipbuf_pass / c(T["chip_noc_bw"]),
                       chipbuf_pass / c(T["chip_buf_bw"])))
        d_ext = (ext_bytes / n2_tot) / np.maximum(c(ext_bw), c(1e-6))
        delay = n2_tot * (np.maximum(chip_pass_d, d_ext)
                          + c(T["t_tile_overhead_ns"]))
        return dict(
            delay=delay, ext_tiles=n2_tot, n_chip=c(n_chip),
            n_core=c(n_core), n_pe=c(n_pe), core_buf=core_buf,
            chip_buf=chip_buf, core_acc=core_acc, chipbuf_acc=chipbuf_acc,
            ext_bytes=ext_bytes, ext_in=ext_in, ext_out=ext_out,
            reg_acc=(np.sum(rd0 * tm) * bpe * c(n_pe) * n1_tot * n2_tot
                     * c(n_core)),
            macs=macs_per_chip * c(n_chip))

    def network(self, nh, src, dst, bwr, vol, fmask, link_bw, n_nodes):
        """Per-flow delay under the proportional throttling of overloaded
        links (Sec. III-C)."""
        c, T = self.c, TECH
        cur = src.copy()
        us, vs = [], []
        for _ in range(MAX_HOPS):
            nxt = nh[cur, dst]
            us.append(cur)
            vs.append(nxt)
            cur = nxt
        u, v = np.stack(us), np.stack(vs)                  # (H, F)
        hops = c(np.sum(u != v, axis=0))
        active = (u != v) & fmask[None, :]
        lid = u * N_TOT + v
        load = np.zeros(N_TOT * N_TOT, self.dt)
        np.add.at(load, lid[active], np.broadcast_to(bwr, lid.shape)[active])
        hotspot = np.max(load)
        is_dram = (u == n_nodes) | (v == n_nodes)
        cap = np.where(is_dram, c(T["dram_bw"]), c(link_bw))
        link_load = load[lid]
        ratio = np.where(active, np.minimum(
            c(1), cap / np.maximum(link_load, c(1e-9))), c(1))
        ebw = np.maximum(bwr * np.min(ratio, axis=0), c(1e-9))
        delay = np.where(fmask, hops * c(T["router_delay_ns"]) + vol / ebw,
                         c(0))
        fm = c(fmask)
        d2d = c(np.sum(active & ~is_dram, axis=0))
        dram = c(np.sum(active & is_dram, axis=0))
        return dict(delay=delay, hops=hops, hotspot=hotspot,
                    d2d_byte_hops=np.sum(vol * d2d * fm),
                    dram_bytes=np.sum(vol * np.minimum(dram, c(1)) * fm),
                    router_byte_hops=np.sum(vol * hops * fm))

    def _die_cost(self, area, wafer_cost, d0):
        c, T = self.c, TECH
        return (wafer_cost / self._dies_per_wafer(area)) \
            / self._yield(area, d0)

    def _yield(self, area, d0):
        a = self.c(TECH["yield_alpha"])
        return (self.c(1) + area * d0 / a) ** (-a)

    def _dies_per_wafer(self, area):
        c, T = self.c, TECH
        d = c(T["wafer_diameter_mm"])
        a = area + c(T["scribe_mm"]) * np.sqrt(np.maximum(area, c(1e-6)))
        return np.maximum(c(np.pi) * (d / c(2)) ** c(2) / a
                          - c(np.pi) * d / np.sqrt(c(2) * a), c(1))

    def package_cost(self, areas, pkg):
        c, T = self.c, TECH
        used = areas > 0
        dies = np.where(used, self._die_cost(
            np.maximum(areas, c(1e-3)), c(T["wafer_cost"]),
            c(T["defect_density_mm2"])), c(0))
        bond = c(T["c_bond"][pkg]) / c(T["bond_yield"])
        c_dies = np.sum(dies) + c(np.sum(used)) * bond
        pkg_area = np.sum(areas) * c(T["interposer_margin"])
        c_sub = pkg_area * c(T["c_substrate_mm2"])
        c_int = c(0)
        if pkg != PKG_ORGANIC:
            raw = c(T["int_wafer_cost"][pkg]) / self._dies_per_wafer(
                np.maximum(pkg_area, c(1)))
            y = self._yield(pkg_area, c(T["int_defect_mm2"][pkg]))
            c_int = raw / np.maximum(y, c(1e-3))
        return c_dies + c_sub + c_int + c(T["c_process"])

    def evaluate(self, spec: Spec, d: Dict[str, np.ndarray]) -> np.ndarray:
        """``METRICS`` of one design (a dict of numpy arrays)."""
        c, T = self.c, TECH
        W, CH, E = spec.W, spec.CH, spec.E
        pkg = int(d["packaging"])
        cap = c(T["link_bw_cap"][pkg])
        Bi = 2 ** int(d["logB"])
        B = c(Bi)

        def analyze(wi, ext_bw):
            wl = dict(spec.wl[wi])
            pipe = int(d["pipe"][wi])
            b = wl["bounds"].copy()
            hit = (np.arange(MAX_LOOPS) == pipe) & wl["loopmask"]
            b[hit] = np.maximum(_cdiv(b[hit], Bi), 1)
            wl["bounds"] = b
            return self.chiplet(wl, d["shape"][wi], d["spatial"][wi],
                                d["order"][wi], d["tiling"][wi], ext_bw)

        an0 = [analyze(wi, cap) for wi in range(W)]
        d_stage0 = np.stack([a["delay"] for a in an0])
        n_chips = chiplets(d)
        if np.any(n_chips > CH) or np.sum(n_chips) > MAX_NODES:
            raise OutsideSpace(f"chiplets per workload {n_chips.tolist()}"
                               f" with ch_max {CH}")
        base = np.cumsum(n_chips) - n_chips
        n_nodes = int(np.sum(n_chips))
        live = np.asarray(d["placement"], np.int64)[:n_nodes]
        rank = np.argsort(np.argsort(live, kind="stable"), kind="stable")
        rank = np.concatenate([rank, np.zeros(W * CH - n_nodes, np.int64)])

        def node(wi, j):
            return rank[base[wi] + np.minimum(j, n_chips[wi] - 1)]

        wgrid = np.repeat(np.arange(W), CH)
        jgrid = np.tile(np.arange(CH), W)
        chip_valid = jgrid < n_chips[wgrid]
        node_of = node(wgrid, jgrid)
        ein = np.stack([a["ext_in"] for a in an0])
        eout = np.stack([a["ext_out"] for a in an0])
        vin = np.sum(ein * c(spec.ext_in), axis=1)[wgrid]
        vout = np.sum(eout * c(spec.fin_out), axis=1)[wgrid]
        dram = np.full(W * CH, n_nodes)
        mA = chip_valid & (vin > 0)
        mB = chip_valid & (vout > 0)
        egrid = np.repeat(np.arange(E), CH)
        jg = np.tile(np.arange(CH), E)
        w1, w2 = spec.esrc[egrid], spec.edst[egrid]
        mC = spec.emask[egrid] & (jg < n_chips[w2])
        volC = ein[w2, spec.edst_tensor[egrid]]
        srcC = node(w1, jg % np.maximum(n_chips[w1], 1))
        dstC = node(w2, jg)

        src = np.concatenate([dram, node_of, srcC])
        dst = np.concatenate([node_of, dram, dstC])
        vol = np.concatenate([vin, vout, volC])
        fmask = np.concatenate([mA, mB, mC])
        fw_src = np.concatenate([wgrid, wgrid, w1])
        fw_dst = np.concatenate([wgrid, wgrid, w2])
        is_dram_f = np.concatenate([np.ones(2 * W * CH, bool),
                                    np.zeros(E * CH, bool)])
        d_src = np.where(is_dram_f, c(BIG), d_stage0[fw_src])
        d_min = np.minimum(d_src, d_stage0[fw_dst])
        bwr = vol / np.maximum(d_min, c(1))

        nh = next_hop(int(d["family"]), min(max(n_nodes, 1), MAX_NODES))
        pre = self.network(nh, src, dst, bwr, vol, fmask, cap, n_nodes)
        link_bw = np.minimum(np.maximum(pre["hotspot"], c(1)), cap)
        net = self.network(nh, src, dst, bwr, vol, fmask, link_bw, n_nodes)

        ebw_f = np.where(fmask, vol / np.maximum(net["delay"], c(1)), c(0))
        inbound = np.zeros(W, self.dt)
        np.add.at(inbound, wgrid, np.where(mA, ebw_f[:W * CH], c(0)))
        n_chip_f = np.stack([a["n_chip"] for a in an0])
        per_chip = inbound / np.maximum(n_chip_f, c(1))
        per_chip = np.where(per_chip > 0, per_chip, cap)
        an = [analyze(wi, np.minimum(per_chip[wi], cap)) for wi in range(W)]
        d_stage = np.stack([a["delay"] for a in an])

        fdel = np.where(fmask, net["delay"], c(0))
        hop_lat = net["hops"] * c(T["router_delay_ns"])
        tiles = np.maximum(np.stack([a["ext_tiles"] for a in an]), c(1))
        first = hop_lat + (fdel - hop_lat) / tiles[fw_src]
        d_in = np.zeros(W, self.dt)
        np.maximum.at(d_in, wgrid, np.where(mA, first[:W * CH], c(0)))
        d_out = np.zeros(W, self.dt)
        np.maximum.at(d_out, wgrid,
                      np.where(mB, first[W * CH:2 * W * CH], c(0)))
        d_edge = np.zeros(E, self.dt)
        np.maximum.at(d_edge, egrid, np.where(mC, fdel[2 * W * CH:], c(0)))

        dist = d_in + d_stage
        for _ in range(W):
            upd = np.where(spec.emask, dist[spec.esrc] + d_edge
                           + d_stage[spec.edst], c(-BIG))
            np.maximum.at(dist, spec.edst, upd)
        lat_tick = np.max(dist + d_out)
        max_stage = max(np.max(d_stage),
                        np.max(np.where(spec.emask, d_edge, c(0))),
                        np.max(d_in), np.max(d_out))
        latency = lat_tick + (B - c(1)) * max_stage

        e_compute = c(0)
        for a in an:
            nch = a["n_chip"]
            e_compute = e_compute + (
                a["macs"] * c(T["e_mac_pj"])
                + a["reg_acc"] * nch * c(8) * c(T["e_reg_pj_bit"])
                + a["core_acc"] * nch * c(8) * c(T["e_core_sram_pj_bit"])
                + (a["chipbuf_acc"] + a["ext_bytes"]) * nch * c(8)
                * c(T["e_chip_sram_pj_bit"]))
        e_net = (net["d2d_byte_hops"] * c(8) * c(T["e_d2d_pj_bit"][pkg])
                 + net["router_byte_hops"] * c(8) * c(T["e_router_pj_bit"])
                 + net["dram_bytes"] * c(8) * c(T["e_dram_pj_bit"]))
        energy = e_compute * B + e_net * B

        io = link_bw / c(T["bw_density"][pkg]) * c(4) * c(T["n_link_io"][pkg])
        area_w = np.stack([
            a["n_core"] * (a["n_pe"] * c(T["a_pe"])
                           + a["core_buf"] / c(2 ** 20)
                           * c(T["a_sram_per_mb"])
                           + c(T["a_core_overhead"]))
            + a["chip_buf"] / c(2 ** 20) * c(T["a_sram_per_mb"])
            + c(T["a_router"]) + c(T["a_chiplet_overhead"]) + io
            for a in an])
        dies = np.where(chip_valid, area_w[wgrid], c(0))
        cost = self.package_cost(dies, pkg)
        return np.asarray([latency, energy, cost, np.sum(dies)], np.float64)


def feasible(spec: Spec, d: Dict, max_total_pes: int) -> bool:
    """The design's constraints: no workload has more than ``ch_max``
    chiplets, all chiplets fit the placeable nodes, and the PE total fits
    the budget (when one is set)."""
    s = np.asarray(d["shape"], np.int64)
    n_chips = chiplets(d)
    pes = int(np.sum(np.prod(s[:, :6], axis=1)))
    return (bool(np.all(n_chips <= spec.CH))
            and int(np.sum(n_chips)) <= min(MAX_NODES, spec.W * spec.CH)
            and (max_total_pes <= 0 or pes <= max_total_pes))


def chiplets(d: Dict) -> np.ndarray:
    """Chiplets of each workload: its chiplet array's two dims."""
    s = np.maximum(np.asarray(d["shape"], np.int64), 1)
    return s[:, 4] * s[:, 5]


def nondominated(points: np.ndarray) -> np.ndarray:
    """Mask of the rows of ``points`` (all minimized) that no row
    dominates."""
    p = np.asarray(points, np.float64)
    if len(p) == 0:
        return np.zeros(0, bool)
    le = np.all(p[:, None, :] <= p[None, :, :], axis=-1)
    lt = np.any(p[:, None, :] < p[None, :, :], axis=-1)
    return ~np.any(le & lt, axis=0)
