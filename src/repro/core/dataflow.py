"""Per-chiplet dataflow analysis (paper Sec. III-B/III-C, TENET-style).

Given one workload (padded arrays from ``Workload.to_arrays``) and one chiplet
design point, compute — entirely in jnp so the whole thing vmaps over design
populations — the quantities the performance/energy/cost models consume:

* temporal trip counts and spatial splits per hierarchy level,
* buffer footprints (core / chiplet) from the tile sizes,
* access counts at every level of the memory hierarchy with *order-dependent
  reuse* (innermost-irrelevant-suffix stationarity) and multicast discounts,
* compute cycles and utilization,
* the pipelined per-level delay  D = trips x max(D_C, D_B, D_A)  (Sec III-C).

Hierarchy and loop structure modeled per chiplet (paper Fig. 1):

    for n2-loops over t2-tiles          # chiplet buffer refilled from ext
      spatial over (X1 x Y1) cores
      for n1-loops over t1-tiles        # core buffer refilled from chiplet buf
        spatial over (X0 x Y0) PEs
        for p-loops over elements       # PE: 1 MAC/cycle, register reuse

Design-point encoding (all int32):
    shape   (6,)   [x0, y0, x1, y1, x2, y2]       raw array dims (>= 1)
    spatial (6,)   [sx0, sy0, sx1, sy1, sx2, sy2] loop ids per level
    order   (3,L)  loop id by position, 0 = outermost   (PE, core, chiplet)
    tiling  (2,L)  [t1; t2] raw tile sizes (clamped internally)
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .workload import MAX_LOOPS
from .constants import TechConstants, DEFAULT_TECH

F = jnp.float32


def _cdiv(a, b):
    return (a + b - 1) // b


def _split_of(spatial_x, spatial_y, X, Y, L=MAX_LOOPS):
    """Per-loop spatial split factor at one level."""
    l = jnp.arange(L)
    sx = jnp.where(l == spatial_x, X, 1)
    sy = jnp.where(l == spatial_y, Y, 1)
    return sx * sy                       # if sx==sy loop: X*Y on that loop


def _positions(order):
    """order: (L,) loop id by position -> pos[loop] = position (0=outermost)."""
    return jnp.argsort(order)


def _footprint(A, dmask, tile):
    """Tile footprint (elements) per tensor.  A: (T,D,L) int, tile: (L,)."""
    # an elementwise product and sum, not a dot: a TPU runs an f32 dot at
    # default precision in bf16, which rounds tile sizes above 256
    span = jnp.sum(A.astype(F) * tile.astype(F), axis=-1)     # (T,D)
    nnz = jnp.sum(A != 0, axis=-1).astype(F)                  # (T,D)
    fd = jnp.where(dmask, span - jnp.maximum(nnz - 1.0, 0.0), 1.0)
    fd = jnp.maximum(fd, 1.0)
    return jnp.prod(fd, axis=-1)                              # (T,)


def _refills(rel, pos, trips, loopmask):
    """Order-aware refill count per tensor.

    rel: (T,L) bool — loop relevant to tensor; pos: (L,) position of loop;
    trips: (L,) trip counts at this level.  A tensor tile is reused across the
    innermost contiguous run of irrelevant loops; every loop at or outside the
    innermost *relevant* position multiplies refills.
    """
    posb = jnp.broadcast_to(pos, rel.shape)                   # (T,L)
    pstar = jnp.max(jnp.where(rel & loopmask, posb, -1), axis=-1)  # (T,)
    count = (posb <= pstar[:, None]) & loopmask
    return jnp.prod(jnp.where(count, trips.astype(F), 1.0), axis=-1)  # (T,)


def _distinct(rel, trips, loopmask):
    return jnp.prod(
        jnp.where(rel & loopmask, trips.astype(F), 1.0), axis=-1)


def _multicast(rel, spatial_x, spatial_y, X, Y):
    """Multicast fan-out for tensors *not* split by a spatial loop."""
    rx = rel[:, spatial_x] if rel.ndim == 2 else rel[spatial_x]
    ry = rel[:, spatial_y]
    mx = jnp.where(rx, 1, X)
    my = jnp.where(ry, 1, Y)
    same = spatial_x == spatial_y
    return jnp.where(same, mx, mx * my).astype(F)


def analyze_chiplet(wl: Dict, shape, spatial, order, tiling,
                    tech: TechConstants = DEFAULT_TECH,
                    ext_bw_gbps=None) -> Dict:
    """Analyze one workload mapped on one chiplet design (pure jnp).

    wl: dict from Workload.to_arrays() (bounds/loopmask/A/tmask/dmask/is_out).
    ext_bw_gbps: effective external (network/DRAM) bandwidth for this chiplet's
      streaming traffic; defaults to the DRAM bandwidth. The system evaluator
      re-invokes with contention-derived effective bandwidth (fixed point).
    Returns a dict of scalars (all jnp float32) — see bottom of function.
    """
    bounds = wl["bounds"].astype(jnp.int32)
    loopmask = wl["loopmask"]
    A, tmask, dmask, is_out = wl["A"], wl["tmask"], wl["dmask"], wl["is_out"]
    rel = jnp.any(A != 0, axis=1) & tmask[:, None]            # (T,L)

    x0, y0, x1, y1, x2, y2 = [jnp.maximum(shape[i], 1) for i in range(6)]
    n_pe, n_core, n_chip = x0 * y0, x1 * y1, x2 * y2

    ext_bw = tech.dram_bw if ext_bw_gbps is None else ext_bw_gbps
    bpe = F(tech.bytes_per_elem)

    # ---- per-loop tiling / trip structure ---------------------------------
    s2 = _split_of(spatial[4], spatial[5], x2, y2)            # cluster split
    N2 = _cdiv(bounds, s2)                                    # per-chiplet share
    t2 = jnp.clip(tiling[1], 1, N2)
    n2 = jnp.where(loopmask, _cdiv(N2, t2), 1)                # chiplet trips

    s1 = _split_of(spatial[2], spatial[3], x1, y1)
    share1 = _cdiv(t2, s1)                                    # per-core share
    t1 = jnp.clip(tiling[0], 1, share1)
    n1 = jnp.where(loopmask, _cdiv(share1, t1), 1)            # core trips

    s0 = _split_of(spatial[0], spatial[1], x0, y0)
    p = jnp.where(loopmask, _cdiv(t1, s0), 1)                 # per-PE iters

    pos0 = _positions(order[0])
    pos1 = _positions(order[1])
    pos2 = _positions(order[2])

    # ---- compute cycles ----------------------------------------------------
    pe_pass = jnp.prod(p.astype(F))                 # cycles per core-tile pass
    n1_tot = jnp.prod(n1.astype(F))
    n2_tot = jnp.prod(n2.astype(F))
    total_macs = jnp.prod(jnp.where(loopmask, bounds, 1).astype(F))
    macs_per_chip = total_macs / F(n_chip)          # useful work (pre-padding)

    # ---- footprints --------------------------------------------------------
    f1 = _footprint(A, dmask, t1) * tmask           # core-buffer tile elems
    f2 = _footprint(A, dmask, t2) * tmask           # chiplet-buffer tile elems
    core_buf_bytes = jnp.sum(f1) * bpe
    chip_buf_bytes = jnp.sum(f2) * bpe

    # ---- level-0: core buffer <-> PE registers ----------------------------
    # A PE-array spatial loop that a tensor does NOT depend on forwards the
    # same element across the array (systolic multicast), so the buffer only
    # feeds the distinct elements at the array edge: n_pe / m0 per tensor.
    r0 = _refills(rel, pos0, p, loopmask)                     # per PE per pass
    d0 = _distinct(rel, p, loopmask)
    rd0 = jnp.where(is_out, r0 + jnp.maximum(r0 - d0, 0.0), r0)
    m0 = _multicast(rel, spatial[0], spatial[1], x0, y0)      # (T,)
    core_acc_pass = jnp.sum(rd0 * tmask / m0 * F(n_pe)) * bpe  # bytes/core/pass
    core_acc_total = core_acc_pass * n1_tot * n2_tot * F(n_core)

    # ---- level-1: chiplet buffer <-> core buffers --------------------------
    r1 = _refills(rel, pos1, n1, loopmask)          # t1-tile refills per pass
    d1 = _distinct(rel, n1, loopmask)
    rw1 = jnp.where(is_out, 2.0 * r1 - d1, r1)      # outputs: write + psum rd
    m1 = _multicast(rel, spatial[2], spatial[3], x1, y1)      # (T,)
    # broadcast on the intra-chiplet NoC: a tile multicast to m1 cores
    # crosses the shared fabric once (bus/tree multicast model)
    chipbuf_acc_pass = jnp.sum(rw1 * f1 * tmask / m1) * bpe * F(n_core)
    noc_bytes_pass = chipbuf_acc_pass
    chipbuf_acc_total = chipbuf_acc_pass * n2_tot
    noc_bytes_total = noc_bytes_pass * n2_tot

    # ---- level-2: external (network / DRAM) <-> chiplet buffer -------------
    r2 = _refills(rel, pos2, n2, loopmask)
    d2 = _distinct(rel, n2, loopmask)
    rw2 = jnp.where(is_out, 2.0 * r2 - d2, r2)
    ext_bytes = jnp.sum(rw2 * f2 * tmask) * bpe               # per chiplet
    m2 = _multicast(rel, spatial[4], spatial[5], x2, y2)
    # external traffic split per tensor (inputs in, outputs out) for the
    # communication-graph construction:
    ext_in_t = jnp.where(is_out, 0.0, r2 * f2 * tmask) * bpe
    ext_out_t = jnp.where(is_out, rw2 * f2 * tmask, 0.0) * bpe

    # ---- pipelined delays (ns; paper Sec III-C max-composition) ------------
    # pe_pass + output-stationary systolic fill/drain skew (2X + Y - 2),
    # the ScaleSim timing model our Sec.-V-A validation compares against
    skew = (2 * x0 + y0 - 2).astype(F)
    d_pe = (pe_pass + skew) / tech.clock_ghz
    d_b0 = core_acc_pass / F(tech.core_buf_bw)
    core_pass_d = jnp.maximum(d_pe, d_b0)
    d_noc = noc_bytes_pass / F(tech.chip_noc_bw)
    d_b1 = chipbuf_acc_pass / F(tech.chip_buf_bw)
    chip_pass_d = jnp.maximum(n1_tot * core_pass_d, jnp.maximum(d_noc, d_b1))
    d_ext_pass = (ext_bytes / n2_tot) / jnp.maximum(F(ext_bw), 1e-6)
    # Each external tile also pays a fixed launch overhead (DMA descriptor
    # setup / drain); default 0.0 so x + 0.0 keeps the seed model
    # bit-identical, and repro.calib fits it against simulator ground truth.
    delay = n2_tot * (jnp.maximum(chip_pass_d, d_ext_pass)
                      + F(tech.t_tile_overhead_ns))           # per chiplet, ns

    util = macs_per_chip / jnp.maximum(
        F(n_pe) * F(n_core) * delay * tech.clock_ghz, 1e-9)

    return dict(
        delay_ns=delay,
        ext_tiles=n2_tot,
        compute_cycles=n2_tot * n1_tot * pe_pass,
        utilization=util,
        total_macs=total_macs,
        n_chiplets=F(n_chip), n_cores=F(n_core), n_pes=F(n_pe),
        core_buf_bytes=core_buf_bytes, chip_buf_bytes=chip_buf_bytes,
        core_acc_bytes=core_acc_total,            # per chiplet
        chipbuf_acc_bytes=chipbuf_acc_total,      # per chiplet
        noc_bytes=noc_bytes_total,                # per chiplet
        ext_bytes=ext_bytes,                      # per chiplet
        ext_in_bytes_t=ext_in_t, ext_out_bytes_t=ext_out_t,
        ext_multicast_t=m2,
        reg_acc_bytes=(jnp.sum(rd0 * tmask) * bpe
                       * F(n_pe) * n1_tot * n2_tot * F(n_core)),
        mac_count=macs_per_chip * F(n_chip),
    )


analyze_chiplet_jit = jax.jit(analyze_chiplet, static_argnames=("tech",))
