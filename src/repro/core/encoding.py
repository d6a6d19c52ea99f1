"""Uniform encoding of the architecture + integration design space
(paper Sec. IV-B, Fig. 6a).

Architecture fields (per workload):
    shape   (W, 6)   geometry of PE / core / chiplet arrays (raw dims)
    spatial (W, 6)   spatially-parallelized loop per array dim per level
    order   (W, 3, L) loop permutation per level (execution order)
    tiling  (W, 2, L) tile sizes (core tile t1, chiplet tile t2)
    pipe    (W,)     pipelined loop id (== L means "not pipelined")
    logB    ()       log2 of pipeline tick count

Integration fields:
    packaging ()       0 organic / 1 passive / 2 active interposer
    family    ()       network topology family (chain/ring/mesh/star)
    placement (W*CH,)  global chiplet id -> network node id (a permutation
                       prefix; the paper's "placement" field, <= 36 nodes)

The BO engine owns the low-dimensional fields {shape, spatial, packaging,
family, logB}; the SA engine owns the high-dimensional {order, tiling,
placement, pipe} (paper Sec. IV-C).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .evaluate import SystemSpec
from .network import MAX_NODES, N_FAMILIES
from .workload import (MAX_LOOPS, graph_feature_rows, workload_signature)


@dataclasses.dataclass(frozen=True)
class DesignSpace:
    """Static bounds of the explorable space for one SystemSpec."""
    spec: SystemSpec
    max_shape: Tuple[int, ...] = (16, 16, 4, 4, 6, 6)   # per-level max dims
    max_logB: int = 6
    max_total_pes: int = 0          # 0 = unconstrained (Fig-7 fairness knob)
    fixed_packaging: int = -1       # >=0 pins the field (ablation studies)
    fixed_family: int = -1
    allow_pipeline: bool = True

    @property
    def W(self):
        return self.spec.W

    @property
    def CH(self):
        return self.spec.CH

    @property
    def n_loops(self) -> np.ndarray:
        return self.spec.arrays["loopmask"].sum(axis=1).astype(np.int32)

    @property
    def bounds(self) -> np.ndarray:
        return self.spec.arrays["bounds"]

    def max_nodes(self) -> int:
        return min(MAX_NODES, self.W * self.CH)


def _rand_perm_rows(key, W, levels, L):
    keys = jax.random.split(key, W * levels)
    perms = jnp.stack([jax.random.permutation(k, L) for k in keys])
    return perms.reshape(W, levels, L).astype(jnp.int32)


def _fit_pe_budget(shape, budget: int):
    """``shape`` with the PE-array and core-grid dims of every workload
    scaled by one factor (rounded down, at least 1), so that a draw over
    the PE budget lands near or under it; a draw within it is kept."""
    pes = jnp.sum(jnp.prod(shape, axis=1)).astype(jnp.float32)
    f = jnp.minimum(1.0, (budget / pes) ** 0.25)
    fit = jnp.maximum(1.0, jnp.floor(shape[:, :4] * f)).astype(shape.dtype)
    return shape.at[:, :4].set(fit)


def random_design(key, space: DesignSpace, nl=None, bounds=None) -> Dict:
    """Uniform random design point (the paper's 'Random' baseline); under
    a PE budget, a draw over it is scaled down to fit (``_fit_pe_budget``):
    with many workloads nearly every uniform draw exceeds the budget.

    ``nl``/``bounds`` may be passed as traced arrays (from the workload
    arrays) so the compiled sampler is workload-independent — same
    contract as ``mutate``."""
    W, CH, L = space.W, space.CH, MAX_LOOPS
    ks = jax.random.split(key, 10)
    mx = jnp.asarray(space.max_shape, jnp.int32)
    shape = jax.random.randint(ks[0], (W, 6), 1, mx + 1)
    if space.max_total_pes > 0:
        shape = _fit_pe_budget(shape, space.max_total_pes)
    nl = jnp.asarray(space.n_loops if nl is None else nl)
    spatial = jax.random.randint(ks[1], (W, 6), 0, jnp.maximum(nl, 1)[:, None])
    order = _rand_perm_rows(ks[2], W, 3, L)
    bounds = jnp.asarray(space.bounds if bounds is None else bounds)
    tmax = jnp.maximum(bounds, 1)
    u = jax.random.uniform(ks[3], (W, 2, L))
    tiling = jnp.maximum(
        1, (tmax[:, None, :].astype(jnp.float32) ** u)).astype(jnp.int32)
    pipe = jnp.where(
        jnp.asarray(space.allow_pipeline)
        & (jax.random.uniform(ks[4], (W,)) < 0.5),
        jax.random.randint(ks[5], (W,), 0, jnp.maximum(nl, 1)),
        jnp.full((W,), L, jnp.int32)).astype(jnp.int32)
    logB = jnp.where(space.allow_pipeline,
                     jax.random.randint(ks[6], (), 0, space.max_logB + 1), 0)
    packaging = (jnp.asarray(space.fixed_packaging, jnp.int32)
                 if space.fixed_packaging >= 0
                 else jax.random.randint(ks[7], (), 0, 3))
    family = (jnp.asarray(space.fixed_family, jnp.int32)
              if space.fixed_family >= 0
              else jax.random.randint(ks[8], (), 0, N_FAMILIES))
    placement = jax.random.permutation(ks[9], W * CH).astype(jnp.int32)
    return dict(shape=shape, spatial=spatial, order=order, tiling=tiling,
                pipe=pipe, logB=jnp.asarray(logB, jnp.int32),
                packaging=jnp.asarray(packaging, jnp.int32),
                family=jnp.asarray(family, jnp.int32), placement=placement)


def balanced_init(key, space: DesignSpace, total_pes: int = 4096) -> Dict:
    """Paper Sec. IV-B: assign PEs to each workload proportionally to its
    MAC count so pipeline stages are roughly balanced."""
    d = random_design(key, space)
    macs = np.array([w.macs for w in space.spec.graph.workloads], np.float64)
    share = macs / macs.sum()
    pes = np.maximum((share * total_pes).astype(np.int64), 64)
    side = np.clip(np.sqrt(pes / 4).astype(np.int32), 1,
                   np.asarray(space.max_shape)[:2].min())
    shape = np.array(d["shape"])
    shape[:, 0] = side
    shape[:, 1] = side
    shape[:, 2:4] = 2
    shape[:, 4:6] = 1
    d["shape"] = jnp.asarray(shape)
    return d


# ---------------------------------------------------------------------------
# SA neighborhood moves (jit-able; one random field mutation per call)
# ---------------------------------------------------------------------------
ARCH_FIELDS = ("shape", "spatial", "order", "tiling", "pipe")
INTEG_FIELDS = ("packaging", "family", "placement")
ALL_FIELDS = ARCH_FIELDS + INTEG_FIELDS
# high-dimensional fields owned by the SA engine (paper Sec. IV-C)
SA_FIELDS = ("order", "tiling", "pipe", "placement")
# low-dimensional fields owned by the Bayesian engine
BO_FIELDS = ("shape", "spatial", "packaging", "family")


def mutate(key, design: Dict, space: DesignSpace,
           fields: Tuple[str, ...] = ALL_FIELDS,
           nl=None, bounds=None) -> Dict:
    """One random neighbor move restricted to ``fields`` (static tuple).
    Field subsets drive the Fig.-8 ablation ladder (Res/Dfw/Arch/Net/Pkg/...)
    and the nested BO+SA engine (SA owns the high-dim fields).

    ``nl``/``bounds`` may be passed as traced arrays (from the workload
    arrays) so the compiled move kernel is workload-independent."""
    W, CH, L = space.W, space.CH, MAX_LOOPS
    ks = jax.random.split(key, 12)
    nl = jnp.maximum(jnp.asarray(space.n_loops if nl is None else nl), 1)
    bounds_arr = jnp.asarray(space.bounds if bounds is None else bounds)
    wsel = jax.random.randint(ks[0], (), 0, W)

    d = {k: v for k, v in design.items()}

    # --- architecture moves -------------------------------------------------
    def mv_shape(d):
        i = jax.random.randint(ks[2], (), 0, 6)
        delta = jax.random.choice(ks[3], jnp.asarray([-2, -1, 1, 2]))
        mx = jnp.asarray(space.max_shape, jnp.int32)
        s = d["shape"].at[wsel, i].add(delta)
        d["shape"] = jnp.clip(s, 1, mx[None, :])
        return d

    def mv_spatial(d):
        i = jax.random.randint(ks[2], (), 0, 6)
        v = jax.random.randint(ks[3], (), 0, nl[wsel])
        d["spatial"] = d["spatial"].at[wsel, i].set(v)
        return d

    def mv_order(d):
        lvl = jax.random.randint(ks[2], (), 0, 3)
        i = jax.random.randint(ks[3], (), 0, L)
        j = jax.random.randint(ks[4], (), 0, L)
        row = d["order"][wsel, lvl]
        a, b = row[i], row[j]
        row = row.at[i].set(b).at[j].set(a)
        d["order"] = d["order"].at[wsel, lvl].set(row)
        return d

    def mv_tiling(d):
        lvl = jax.random.randint(ks[2], (), 0, 2)
        i = jax.random.randint(ks[3], (), 0, nl[wsel])
        f = jax.random.choice(ks[4], jnp.asarray([0.25, 0.5, 2.0, 4.0]))
        bmax = bounds_arr[wsel, i]
        t = d["tiling"][wsel, lvl, i].astype(jnp.float32) * f
        t = jnp.clip(t.astype(jnp.int32), 1, bmax)
        d["tiling"] = d["tiling"].at[wsel, lvl, i].set(
            jnp.maximum(t, 1).astype(jnp.int32))
        return d

    def mv_pipe(d):
        on = jax.random.uniform(ks[2]) < (0.7 if space.allow_pipeline else 0.0)
        loop = jax.random.randint(ks[3], (), 0, nl[wsel])
        d["pipe"] = d["pipe"].at[wsel].set(
            jnp.where(on, loop, jnp.int32(L)).astype(jnp.int32))
        d["logB"] = jnp.where(
            on, jnp.clip(d["logB"]
                         + jax.random.randint(ks[4], (), -1, 2),
                         0, space.max_logB),
            d["logB"]).astype(jnp.int32)
        return d

    # --- integration moves ---------------------------------------------------
    def mv_packaging(d):
        if space.fixed_packaging >= 0:
            return d
        d["packaging"] = jax.random.randint(ks[2], (), 0, 3)
        return d

    def mv_family(d):
        if space.fixed_family >= 0:
            return d
        d["family"] = jax.random.randint(ks[2], (), 0, N_FAMILIES)
        return d

    def mv_placement(d):
        i = jax.random.randint(ks[2], (), 0, W * CH)
        j = jax.random.randint(ks[3], (), 0, W * CH)
        p = d["placement"]
        a, b = p[i], p[j]
        d["placement"] = p.at[i].set(b).at[j].set(a)
        return d

    all_moves = dict(shape=mv_shape, spatial=mv_spatial, order=mv_order,
                     tiling=mv_tiling, pipe=mv_pipe, packaging=mv_packaging,
                     family=mv_family, placement=mv_placement)
    moves = [all_moves[f] for f in fields]
    mid = jax.random.randint(ks[1], (), 0, len(moves))
    branches = [lambda op, m=m: m(dict(d)) for m in moves]
    return jax.lax.switch(mid, branches, 0)


def feasibility_penalty(space: DesignSpace, design: Dict, metrics: Dict):
    """Soft constraints: total chiplets <= placeable nodes; optional PE budget
    (Fig. 7 iso-PE comparisons).  Returned as a multiplicative penalty."""
    n_chips = jnp.sum(design["shape"][:, 4] * design["shape"][:, 5])
    over_nodes = jnp.maximum(
        n_chips - jnp.int32(space.max_nodes()), 0).astype(jnp.float32)
    pes = jnp.sum(design["shape"][:, 0] * design["shape"][:, 1]
                  * design["shape"][:, 2] * design["shape"][:, 3]
                  * design["shape"][:, 4] * design["shape"][:, 5])
    over_pes = jnp.where(
        space.max_total_pes > 0,
        jnp.maximum(pes - space.max_total_pes, 0).astype(jnp.float32), 0.0)
    return 1.0 + over_nodes + over_pes / 64.0


# ---------------------------------------------------------------------------
# portable (spec-independent) design IR — the cross-workload transfer
# substrate.  A raw design is a pytree of arrays padded to ONE SystemSpec's
# (W, CH, E); a PortableDesign re-keys those arrays by *workload identity*
# (``workload_signature``) so knowledge moves between spec spaces:
#
#     design_A --to_portable--> PortableDesign --from_portable--> design_B
#
# ``migrate`` composes the two; ``repair`` makes any design dict feasible
# under a destination DesignSpace (permutation fields re-ranked, bounds
# clipped, chiplet-count / PE-budget constraints enforced), so migrated
# seeds are always legal population members.
# ---------------------------------------------------------------------------
_PLACE_FAR = 1e15          # placement rank key for unmatched chiplet slots


@dataclasses.dataclass(frozen=True)
class SpaceDigest:
    """The facts about an exploration problem that migration needs — a
    pure-data view of (SystemSpec.graph, DesignSpace) that is JSON-portable,
    so the cross-spec archive manifest can persist it and a later process
    can migrate out of a cached archive *without* reconstructing the source
    ``WorkloadGraph``."""
    W: int
    CH: int
    signatures: Tuple[str, ...]        # per-workload identity hashes
    features: np.ndarray               # (W, WL_FEATURE_DIM) matching rows
    bounds: np.ndarray                 # (W, MAX_LOOPS) padded loop bounds
    n_loops: np.ndarray                # (W,)
    max_shape: Tuple[int, ...]
    max_logB: int
    max_total_pes: int
    fixed_packaging: int
    fixed_family: int
    allow_pipeline: bool

    def max_nodes(self) -> int:
        return min(MAX_NODES, self.W * self.CH)

    def to_json_dict(self) -> Dict:
        return dict(
            W=int(self.W), CH=int(self.CH),
            signatures=list(self.signatures),
            features=np.asarray(self.features, np.float64).tolist(),
            bounds=np.asarray(self.bounds, np.int64).tolist(),
            n_loops=np.asarray(self.n_loops, np.int64).tolist(),
            max_shape=[int(v) for v in self.max_shape],
            max_logB=int(self.max_logB),
            max_total_pes=int(self.max_total_pes),
            fixed_packaging=int(self.fixed_packaging),
            fixed_family=int(self.fixed_family),
            allow_pipeline=bool(self.allow_pipeline))

    @classmethod
    def from_dict(cls, d: Dict) -> "SpaceDigest":
        return cls(
            W=int(d["W"]), CH=int(d["CH"]),
            signatures=tuple(d["signatures"]),
            features=np.asarray(d["features"], np.float64),
            bounds=np.asarray(d["bounds"], np.int64),
            n_loops=np.asarray(d["n_loops"], np.int64),
            max_shape=tuple(int(v) for v in d["max_shape"]),
            max_logB=int(d["max_logB"]),
            max_total_pes=int(d["max_total_pes"]),
            fixed_packaging=int(d["fixed_packaging"]),
            fixed_family=int(d["fixed_family"]),
            allow_pipeline=bool(d["allow_pipeline"]))


def space_digest(space: DesignSpace) -> SpaceDigest:
    graph = space.spec.graph
    return SpaceDigest(
        W=space.W, CH=space.CH,
        signatures=tuple(workload_signature(w) for w in graph.workloads),
        features=graph_feature_rows(graph),
        bounds=np.asarray(space.bounds, np.int64),
        n_loops=np.asarray(space.n_loops, np.int64),
        max_shape=tuple(space.max_shape), max_logB=space.max_logB,
        max_total_pes=space.max_total_pes,
        fixed_packaging=space.fixed_packaging,
        fixed_family=space.fixed_family,
        allow_pipeline=space.allow_pipeline)


SpaceLike = Union[DesignSpace, SpaceDigest, Dict]


def _as_digest(x: SpaceLike) -> SpaceDigest:
    if isinstance(x, SpaceDigest):
        return x
    if isinstance(x, DesignSpace):
        return space_digest(x)
    if isinstance(x, dict):
        return SpaceDigest.from_dict(x)
    raise TypeError(f"cannot digest {type(x).__name__}")


@dataclasses.dataclass
class PortableDesign:
    """One design point in spec-independent form: per-workload records
    (each carrying the workload's identity signature + feature row and its
    architecture fields) plus the global integration fields.  ``place_key``
    is the workload's chiplet slots' positions in the source placement
    permutation — relative order, not absolute node ids — so placements
    survive re-ranking into any destination permutation length."""
    records: List[Dict]
    logB: int
    packaging: int
    family: int


def to_portable(design: Dict, src: SpaceLike) -> PortableDesign:
    dg = _as_digest(src)
    d = {k: np.asarray(v) for k, v in design.items()}
    records = []
    for wi in range(dg.W):
        g0 = wi * dg.CH
        records.append(dict(
            signature=dg.signatures[wi],
            features=np.asarray(dg.features[wi], np.float64),
            shape=d["shape"][wi].copy(),
            spatial=d["spatial"][wi].copy(),
            order=d["order"][wi].copy(),
            tiling=d["tiling"][wi].copy(),
            pipe=np.int32(d["pipe"][wi]),
            place_key=d["placement"][g0:g0 + dg.CH].astype(np.float64)))
    return PortableDesign(records=records, logB=int(d["logB"]),
                          packaging=int(d["packaging"]),
                          family=int(d["family"]))


def _match_records(records: Sequence[Dict], dg: SpaceDigest) -> List[int]:
    """One source record per destination workload: first-unused exact
    signature match, then any exact match, then nearest feature row
    (unused records preferred on ties).  Deterministic."""
    sigs = [r["signature"] for r in records]
    feats = np.stack([np.asarray(r["features"], np.float64)
                      for r in records])
    used: set = set()
    out: List[int] = []
    for wi in range(dg.W):
        cand = [k for k, s in enumerate(sigs) if s == dg.signatures[wi]]
        j = next((k for k in cand if k not in used),
                 cand[0] if cand else None)
        if j is None:
            f = np.asarray(dg.features[wi], np.float64)
            if feats.shape[1] == f.shape[0]:
                dist = np.linalg.norm(feats - f[None, :], axis=1)
            else:           # feature layout drifted across versions: any
                #             record is as good as any other
                dist = np.arange(len(records), dtype=np.float64)
            dist = dist + 1e-9 * np.asarray(
                [k in used for k in range(len(records))], np.float64)
            j = int(np.argmin(dist))
        used.add(j)
        out.append(j)
    return out


def from_portable(pd: PortableDesign, dst: SpaceLike) -> Dict:
    """Materialize a PortableDesign into a destination space's raw design
    dict.  Always ends in ``repair``, so the result is feasible whatever
    the source/destination mismatch."""
    dg = _as_digest(dst)
    if not pd.records:
        raise ValueError("cannot materialize an empty PortableDesign")
    W, CH, L = dg.W, dg.CH, MAX_LOOPS
    match = _match_records(pd.records, dg)
    shape = np.ones((W, 6), np.int32)
    spatial = np.zeros((W, 6), np.int32)
    order = np.zeros((W, 3, L), np.int32)
    tiling = np.ones((W, 2, L), np.int32)
    pipe = np.full((W,), L, np.int32)
    keys = np.empty((W * CH,), np.float64)
    for wi, j in enumerate(match):
        r = pd.records[j]
        shape[wi] = r["shape"]
        spatial[wi] = r["spatial"]
        order[wi] = r["order"]
        tiling[wi] = r["tiling"]
        pipe[wi] = r["pipe"]
        pk = np.asarray(r["place_key"], np.float64)
        for c in range(CH):
            g = wi * CH + c
            keys[g] = pk[c] if c < len(pk) else _PLACE_FAR + g
    design = dict(
        shape=shape, spatial=spatial, order=order, tiling=tiling, pipe=pipe,
        logB=np.asarray(pd.logB, np.int32),
        packaging=np.asarray(pd.packaging, np.int32),
        family=np.asarray(pd.family, np.int32),
        placement=keys)           # repair re-ranks into a permutation
    return repair(design, dg)


def migrate(design: Dict, src: SpaceLike, dst: SpaceLike) -> Dict:
    """Move one design between spec spaces: re-key its per-workload fields
    by workload identity, re-rank its placement, repair into feasibility.
    Migrating a repaired design through a superset space (same workloads,
    >= CH, >= bounds) and back is the identity."""
    return from_portable(to_portable(design, src), dst)


def portable_signature(design: Dict, space: SpaceLike) -> str:
    """Content hash of one design in its portable form: the per-workload
    records (each keyed by the workload's structural signature) plus the
    global integration fields.  Two repaired designs in the same space
    hash equal iff their portable forms are identical, so the transfer
    seeding path uses this to drop migrated seeds that duplicate points
    the destination archive already holds (migration is the identity on
    same-space repaired designs, so an archive's own front re-offered as
    seeds dedups to nothing)."""
    pd = to_portable(design, space)
    h = hashlib.sha256()
    h.update(repr((int(pd.logB), int(pd.packaging),
                   int(pd.family))).encode())
    for r in pd.records:
        h.update(r["signature"].encode())
        for k in ("shape", "spatial", "order", "tiling", "pipe"):
            h.update(np.asarray(r[k], np.int64).tobytes())
        h.update(np.asarray(r["place_key"], np.float64).tobytes())
    return h.hexdigest()[:16]


def _rank(values: np.ndarray) -> np.ndarray:
    """Stable rank — any real-valued key vector becomes a permutation of
    ``range(n)`` preserving relative order; a permutation maps to itself."""
    return np.argsort(np.argsort(values, kind="stable"), kind="stable")


def repair(design: Dict, space: SpaceLike) -> Dict:
    """Project a design dict onto the feasible set of a destination space:
    every field clipped into its legal range, ``order``/``placement``
    re-ranked into valid permutations, and the hard constraints
    (chiplet count <= placeable nodes, optional total-PE budget) enforced
    by halving the widest offending dims.  Idempotent; pure numpy."""
    dg = _as_digest(space)
    W, CH, L = dg.W, dg.CH, MAX_LOOPS
    d = {k: np.array(v) for k, v in design.items()}
    mx = np.asarray(dg.max_shape, np.int64)
    nl = np.maximum(np.asarray(dg.n_loops, np.int64), 1)
    bounds = np.maximum(np.asarray(dg.bounds, np.int64), 1)

    d["shape"] = np.clip(d["shape"].reshape(W, 6), 1,
                         mx[None, :]).astype(np.int32)
    d["spatial"] = np.clip(d["spatial"].reshape(W, 6), 0,
                           (nl - 1)[:, None]).astype(np.int32)
    o = d["order"].reshape(W * 3, L)
    d["order"] = np.stack([_rank(row) for row in o]).astype(
        np.int32).reshape(W, 3, L)
    d["tiling"] = np.clip(d["tiling"].reshape(W, 2, L), 1,
                          bounds[:, None, :]).astype(np.int32)
    pipe = d["pipe"].reshape(W).astype(np.int64)
    pipe = np.where((pipe < 0) | (pipe >= nl), L, pipe)
    if not dg.allow_pipeline:
        pipe = np.full((W,), L, np.int64)
    d["pipe"] = pipe.astype(np.int32)
    logB = int(np.clip(np.asarray(d["logB"]).reshape(()), 0, dg.max_logB))
    d["logB"] = np.asarray(logB if dg.allow_pipeline else 0, np.int32)
    pkg = int(np.clip(np.asarray(d["packaging"]).reshape(()), 0, 2))
    d["packaging"] = np.asarray(
        dg.fixed_packaging if dg.fixed_packaging >= 0 else pkg, np.int32)
    fam = int(np.clip(np.asarray(d["family"]).reshape(()), 0,
                      N_FAMILIES - 1))
    d["family"] = np.asarray(
        dg.fixed_family if dg.fixed_family >= 0 else fam, np.int32)
    d["placement"] = _rank(
        np.asarray(d["placement"], np.float64).reshape(W * CH)).astype(
            np.int32)

    # hard constraint 1: total chiplets <= placeable network nodes
    sh = d["shape"].astype(np.int64)
    while int((sh[:, 4] * sh[:, 5]).sum()) > dg.max_nodes():
        w = int(np.argmax(sh[:, 4] * sh[:, 5]))
        j = 4 + int(np.argmax(sh[w, 4:6]))
        if sh[w, j] <= 1:
            break
        sh[w, j] //= 2
    # hard constraint 2: optional total-PE budget
    if dg.max_total_pes > 0:
        while int(np.prod(sh, axis=1).sum()) > dg.max_total_pes:
            w = int(np.argmax(np.prod(sh, axis=1)))
            j = int(np.argmax(sh[w]))
            if sh[w, j] <= 1:
                break
            sh[w, j] //= 2
    d["shape"] = sh.astype(np.int32)
    return d
