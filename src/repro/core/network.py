"""In-package network model (paper Sec. III-A Def. 2 + Sec. III-C).

Communication graph: flows (src node, dst node, bandwidth-requirement bwr,
volume bytes).  The network is one of four deterministic-routing topology
families over up to ``MAX_NODES`` chiplet nodes plus one DRAM node:

    0 chain   — 1D line, dimension-order routing
    1 ring    — shortest direction, clockwise on tie
    2 mesh    — row-major 2D grid (rows = largest divisor <= sqrt(n)), XY routing
    3 star    — hub at node 0

DRAM (memory-controller) node = index ``n_nodes``; it attaches to column-0
nodes of a mesh and to node 0 otherwise (paper Fig. 1: boundary chiplets
connect to DRAM).

Flow control (paper Sec. III-C): links are provisioned uniformly at the
*hotspot* requirement, capped by the packaging's feasible per-link bandwidth;
if a link's total load exceeds its bandwidth, flows through it are throttled
in proportion to their requirements:

    ebw_c^f = bwr_f * min(1, bw_c / load_c),   ebw_f = min over links on path
    D(e)    = |f| * t_s + bytes / ebw_f

Routing is precomputed on host into next-hop tables (numpy); the contention
evaluation walks paths with ``lax.scan`` so it jits/vmaps with the rest of the
evaluator.
"""

from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

MAX_NODES = 36          # paper Sec. IV-C: placement field is up to 36 nodes
N_TOT = MAX_NODES + 1   # + DRAM node
FAM_CHAIN, FAM_RING, FAM_MESH, FAM_STAR = 0, 1, 2, 3
N_FAMILIES = 4


def _mesh_dims(n: int):
    r = int(math.isqrt(n))
    while r > 1 and n % r != 0:
        r -= 1
    return r, n // r          # rows, cols


def _build_next_hop(family: int, n: int) -> np.ndarray:
    """Next-hop table NH[s, d] for n chiplet nodes + DRAM node (= index n).

    NH[s, d] = next node on the deterministic path s -> d; NH[d, d] = d.
    Unused node slots route to themselves.
    """
    # default NH[s, d] = d (arrived / unused slots terminate immediately)
    NH = np.tile(np.arange(N_TOT, dtype=np.int16)[None, :], (N_TOT, 1))
    dram = n

    def set_hop(s, d, nxt):
        NH[s, d] = nxt

    if family == FAM_MESH:
        rows, cols = _mesh_dims(n)
    for s in range(n):
        for d in range(n):
            if s == d:
                continue
            if family == FAM_CHAIN:
                nxt = s + 1 if d > s else s - 1
            elif family == FAM_RING:
                fwd = (d - s) % n
                bwd = (s - d) % n
                nxt = (s + 1) % n if fwd <= bwd else (s - 1) % n
            elif family == FAM_MESH:
                sr, sc = divmod(s, cols)
                dr, dc = divmod(d, cols)
                if sc != dc:                       # X first
                    nxt = sr * cols + (sc + (1 if dc > sc else -1))
                else:                              # then Y
                    nxt = (sr + (1 if dr > sr else -1)) * cols + sc
            else:                                  # star via hub 0
                nxt = d if s == 0 else 0
            set_hop(s, d, nxt)
    # DRAM attachments
    if family == FAM_MESH:
        rows, cols = _mesh_dims(n)
        for d in range(n):
            dr = d // cols
            set_hop(dram, d, dr * cols)            # enter at column 0, own row
        for s in range(n):
            sr, sc = divmod(s, cols)
            set_hop(s, dram, dram if sc == 0 else sr * cols + (sc - 1))
    else:
        # DRAM attaches to node 0 only: enter/leave the network via node 0.
        for d in range(n):
            NH[dram, d] = np.int16(0)
        for s in range(n):
            NH[s, dram] = np.int16(dram) if s == 0 else NH[s, 0]
    return NH


@lru_cache(maxsize=1)
def next_hop_tables() -> np.ndarray:
    """Stacked NH tables, indexed by topo_code = family * (MAX_NODES+1) + n."""
    out = np.zeros((N_FAMILIES * (MAX_NODES + 1), N_TOT, N_TOT), np.int16)
    for fam in range(N_FAMILIES):
        for n in range(1, MAX_NODES + 1):
            out[fam * (MAX_NODES + 1) + n] = _build_next_hop(fam, n)
    return out


def topo_code(family: int, n_nodes: int) -> int:
    return family * (MAX_NODES + 1) + n_nodes


# ---------------------------------------------------------------------------
# jnp contention evaluation
# ---------------------------------------------------------------------------
def route_links(nh, src, dst):
    """Walk paths for all flows.  nh: (K, K) int next-hop table, K <= N_TOT
    a static node bound (every node id in ``src``/``dst`` and every next
    hop is below K); src/dst: (F,) int.
    Returns (links, hops): links (max(K-1, 1), F, 2) int32 with (u,v) per
    hop (u==v once arrived => no link), hops (F,) float.

    K-1 steps suffice: a path among n chiplet nodes plus DRAM has at most
    n hops in every family, and an id with no node in the table reaches
    its target in one."""
    def step(cur, _):
        nxt = nh[cur, dst].astype(jnp.int32)
        return nxt, jnp.stack([cur, nxt], axis=-1)
    _, links = jax.lax.scan(step, src.astype(jnp.int32), None,
                            length=max(nh.shape[0] - 1, 1))
    hops = jnp.sum(links[:, :, 0] != links[:, :, 1], axis=0).astype(jnp.float32)
    return links, hops


def evaluate_network(nh, src, dst, bwr, vol_bytes, fmask,
                     link_bw, dram_bw, router_delay_ns, n_nodes):
    """Contention-aware per-flow delay (paper Sec. III-C last equation).

    nh:        (K, K) next-hop table (jnp int), K <= N_TOT the static node
               bound of ``route_links``; links are ids u*K + v
    src, dst:  (F,) node ids per flow (DRAM node = n_nodes)
    bwr:       (F,) bandwidth requirement GB/s
    vol_bytes: (F,) transfer volume
    fmask:     (F,) bool valid-flow mask
    link_bw:   provisioned chiplet-link bandwidth (GB/s, scalar)
    Returns dict(delay_ns (F,), hops (F,), hotspot_load, link_bits_hops).
    """
    Fd = jnp.float32
    links, hops = route_links(nh, src, dst)
    u = links[:, :, 0].astype(jnp.int32)        # (H, F)
    v = links[:, :, 1].astype(jnp.int32)
    active = (u != v) & fmask[None, :]
    k = nh.shape[0]
    lid = u * k + v                              # directed link id

    load = jnp.zeros((k * k,), Fd)
    load = load.at[lid.reshape(-1)].add(
        jnp.where(active, bwr[None, :], 0.0).reshape(-1))
    hotspot = jnp.max(load)

    # per-link capacity: DRAM-attached links run at dram_bw, others at link_bw
    is_dram_link = (u == n_nodes) | (v == n_nodes)
    cap = jnp.where(is_dram_link, Fd(dram_bw), Fd(link_bw))   # (H, F)
    link_load = load[lid]                                      # (H, F)
    ratio = jnp.where(active,
                      jnp.minimum(1.0, cap / jnp.maximum(link_load, 1e-9)),
                      1.0)
    min_ratio = jnp.min(ratio, axis=0)                         # (F,)
    ebw = jnp.maximum(bwr * min_ratio, 1e-9)
    delay = hops * Fd(router_delay_ns) + vol_bytes / ebw
    delay = jnp.where(fmask, delay, 0.0)

    # bits x hops on chiplet-to-chiplet links (for D2D energy); DRAM-link
    # traversals counted separately (DRAM access energy).
    d2d_hops = jnp.sum(jnp.where(active & ~is_dram_link, 1.0, 0.0), axis=0)
    dram_hops = jnp.sum(jnp.where(active & is_dram_link, 1.0, 0.0), axis=0)
    return dict(delay_ns=delay, hops=hops, hotspot=hotspot,
                d2d_byte_hops=jnp.sum(vol_bytes * d2d_hops * fmask),
                dram_bytes=jnp.sum(vol_bytes * jnp.minimum(dram_hops, 1.0)
                                   * fmask),
                router_byte_hops=jnp.sum(vol_bytes * hops * fmask))
