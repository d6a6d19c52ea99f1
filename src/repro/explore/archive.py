"""Pareto archive: the canonical dominance math + a fixed-capacity,
jit-compatible nondominated archive with a persistent on-disk cache.

This module is deliberately standalone (jax/numpy plus the equally
dependency-free ``repro.obs`` tracing layer — no ``repro.core`` imports)
so both the optimizer (``repro.core.optimizer``) and the benchmark
suite can use one dominance convention without import cycles:

    a dominates b  <=>  all(a <= b) and any(a < b)      (all minimized)

Layers:

* ``pareto_front`` / ``dominance_counts`` / ``crowding_distance`` — the
  vectorized dominance primitives (vmapped O(n^2) comparisons; each
  insertion is a single fused comparison against the whole archive).
* ``ParetoArchive`` — fixed-capacity archive over stacked design pytrees
  plus an (n, k) objective matrix.  Insertion concatenates the batch,
  recomputes the nondominated mask and prunes to capacity by crowding
  distance (boundary points carry infinite crowding, so extremes survive).
* ``spec_space_key`` / ``save`` / ``load`` — persistence keyed by a
  canonical hash of the (SystemSpec, DesignSpace) pair, so a re-run of the
  same exploration problem warm-starts from disk instead of recomputing.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as obs

F = jnp.float32
BIG = 1e30         # sentinel objective for invalid / non-finite rows

# shared log-space hypervolume reference: all convergence telemetry (the
# in-scan NSGA trace and the archive-projected plateau checks) measures
# 2-D hypervolume over clipped log-metrics against (HV_LOG_REF,)*2, so
# values are directly comparable across generations, scan segments and
# the host/device implementations.  e^41 ~ 6e17 comfortably exceeds every
# feasible raw metric; points beyond the reference contribute nothing.
HV_LOG_REF = 41.0


# ---------------------------------------------------------------------------
# dominance primitives (host + jit variants share one convention)
# ---------------------------------------------------------------------------
def pareto_front(points) -> List[int]:
    """Indices of the Pareto-optimal rows of an (n, k) objective array
    (all objectives minimized).  Duplicate points are all kept — neither
    strictly dominates the other.  This is THE canonical implementation;
    ``repro.core.optimizer.pareto_front`` and ``benchmarks.bench_pareto``
    both delegate here."""
    pts = np.asarray(points, np.float64)
    if pts.ndim == 1:
        pts = pts[:, None]
    n = len(pts)
    if n == 0:
        return []
    le = np.all(pts[:, None, :] <= pts[None, :, :], axis=-1)   # le[i,j]: i<=j
    lt = np.any(pts[:, None, :] < pts[None, :, :], axis=-1)
    dominated = np.any(le & lt, axis=0)                        # any i dom j
    return [int(i) for i in np.flatnonzero(~dominated)]


def dominates(a, b):
    """True iff point ``a`` dominates ``b`` (jnp, all minimized)."""
    return jnp.all(a <= b) & jnp.any(a < b)


# pools at least this large route dominance counting through the tiled
# ``kernels/pareto_rank`` dispatcher (Pallas on TPU / interpret mode, the
# identical jnp math elsewhere) instead of materializing the fused
# (n, n, k) comparison in one shot — the only O(n^2) step in selection
_PARETO_RANK_MIN_N = int(os.environ.get("REPRO_PARETO_RANK_MIN_N", "128"))


def dominance_counts(objs, valid):
    """(n,) number of *valid* points dominating each row of ``objs`` (n, k).
    Zero => nondominated.  One fused (n, n, k) comparison — the vmapped
    'O(1) scans' insertion primitive — below ``_PARETO_RANK_MIN_N``; the
    tiled ``pareto_rank`` kernel above it.  Every ranking consumer (NSGA
    environmental selection, ``ParetoArchive.insert``) funnels through
    here, so the kernel serves the whole search path."""
    n = int(objs.shape[0])
    if n >= _PARETO_RANK_MIN_N:
        # local import: the kernel layer is optional compute, and this
        # module stays importable standalone
        from ..kernels.pareto_rank.ops import \
            dominance_counts as _tiled_counts
        return _tiled_counts(objs, valid)
    le = jnp.all(objs[:, None, :] <= objs[None, :, :], axis=-1)
    lt = jnp.any(objs[:, None, :] < objs[None, :, :], axis=-1)
    dom = le & lt & valid[:, None]
    return jnp.sum(dom, axis=0)


def crowding_distance(objs, valid):
    """NSGA-II crowding distance over the ``valid`` subset of ``objs`` (n, k).
    Boundary points (per-objective min/max among valid rows) get +inf;
    invalid rows get 0.  jit/vmap-safe (fixed shapes, argsort-based)."""
    n = objs.shape[0]
    nv = jnp.sum(valid)

    def per_objective(col):
        c = jnp.where(valid, col, jnp.inf)         # invalid rows sort last
        order = jnp.argsort(c)
        s = c[order]
        lo = s[0]
        hi = s[jnp.clip(nv - 1, 0, n - 1)]
        rng = jnp.maximum(hi - lo, 1e-12)
        prev = jnp.concatenate([s[:1], s[:-1]])
        nxt = jnp.concatenate([s[1:], s[-1:]])
        i = jnp.arange(n)
        gap = (nxt - prev) / rng
        gap = jnp.where((i == 0) | (i == nv - 1), jnp.inf, gap)
        gap = jnp.where(i < nv, gap, 0.0)
        return jnp.zeros(n, F).at[order].set(gap.astype(F))

    return jnp.sum(jax.vmap(per_objective, in_axes=1, out_axes=1)(
        objs.astype(F)), axis=1)


def hypervolume_2d(points, ref) -> float:
    """Exact 2-D hypervolume (area dominated w.r.t. ``ref``, both objectives
    minimized).  Non-finite points and points not dominating ``ref`` are
    ignored; dominated points contribute nothing."""
    pts = np.asarray(points, np.float64).reshape(-1, 2)
    ref = np.asarray(ref, np.float64)
    ok = np.all(np.isfinite(pts), axis=1) & np.all(pts < ref[None, :], axis=1)
    pts = pts[ok]
    if len(pts) == 0:
        return 0.0
    pts = pts[np.argsort(pts[:, 0], kind="stable")]
    hv, ymin = 0.0, ref[1]
    for x, y in pts:
        if y < ymin:
            hv += (ref[0] - x) * (ymin - y)
            ymin = y
    return float(hv)


def hypervolume_2d_jit(points, ref, valid=None):
    """jit/vmap-safe exact 2-D hypervolume (both objectives minimized).

    Same staircase as ``hypervolume_2d`` but fixed-shape jnp: filtered
    points (non-finite, not dominating ``ref``, or masked out by
    ``valid``) are moved onto the reference point where they contribute
    zero area.  Used by the NSGA scan body to trace per-generation front
    hypervolume with no host round-trip and no extra evaluations."""
    pts = jnp.asarray(points, F).reshape(-1, 2)
    ref = jnp.asarray(ref, F).reshape(2)
    ok = jnp.all(jnp.isfinite(pts), axis=1) & jnp.all(pts < ref[None, :],
                                                     axis=1)
    if valid is not None:
        ok = ok & jnp.asarray(valid, bool)
    x = jnp.where(ok, pts[:, 0], ref[0])
    y = jnp.where(ok, pts[:, 1], ref[1])
    order = jnp.argsort(x)
    xs, ys = x[order], y[order]
    # running staircase minimum BEFORE each point (ref height to start)
    ymin_prev = jnp.concatenate([ref[1:2], jax.lax.cummin(ys)[:-1]])
    return jnp.sum((ref[0] - xs) * jnp.maximum(ymin_prev - ys, 0.0))


def objective_pairs(n: int) -> Tuple[Tuple[int, int], ...]:
    """All C(n, 2) index pairs (i < j) — the 2-D hypervolume projections
    traced for an ``n``-objective exploration.  Empty for n < 2."""
    return tuple((i, j) for i in range(n) for j in range(i + 1, n))


# ---------------------------------------------------------------------------
# convergence telemetry (shared by repro.explore.nsga / .service and the
# scalarized repro.core.optimizer loop)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ConvergenceTrace:
    """Per-generation convergence telemetry of one search run.

    All arrays are indexed by generation (length ``G``).  ``hypervolume``
    carries one column per objective *pair* (``pairs`` labels them): the
    running (cumulative-best) 2-D hypervolume of the population's feasible
    front over clipped log-metrics w.r.t. ``(HV_LOG_REF,)*2`` — monotone
    non-decreasing by construction, so a plateau is a genuine convergence
    signal rather than crowding-pruning noise.  ``best`` is the running
    best penalized scalarized objective (monotone non-increasing).
    ``archive_hv`` (optional, one row per scan *segment*) is the
    archive-projected hypervolume the service's plateau detector ranks on.
    ``hv_gen`` (optional) is the *instantaneous* (non-cumulative) front
    hypervolume of each generation's population — unlike the running
    ``hypervolume`` it resolves WHEN quality arrived, which is what the
    transfer trust calibration measures (a seeded run front-loads its
    gains into the earliest generations).
    """
    objectives: Tuple[str, ...]
    pairs: Tuple[Tuple[str, str], ...]
    front_size: np.ndarray          # (G,) population front size
    hypervolume: np.ndarray         # (G, P) running log-space hv per pair
    best: np.ndarray                # (G,) running best scalarized objective
    feasible_frac: np.ndarray       # (G,) feasible fraction of the children
    n_evals: np.ndarray             # (G,) cumulative evaluations
    archive_hv: Optional[np.ndarray] = None     # (S, P) per scan segment
    hv_gen: Optional[np.ndarray] = None         # (G, P) instantaneous per
    #                                 generation (not running max)

    def __post_init__(self):
        self.objectives = tuple(self.objectives)
        self.pairs = tuple(tuple(p) for p in self.pairs)

    @property
    def generations(self) -> int:
        return len(self.front_size)

    @classmethod
    def from_scan(cls, objectives: Sequence[str], scan_trace: Dict,
                  evals_per_generation: int) -> "ConvergenceTrace":
        """Adopt the stacked (G, ...) telemetry a ``make_nsga`` run scanned
        out (zero extra evaluations were spent producing it)."""
        objectives = tuple(objectives)
        g = np.asarray(scan_trace["front_size"]).shape[0]
        return cls(
            objectives=objectives,
            pairs=tuple((objectives[i], objectives[j])
                        for i, j in objective_pairs(len(objectives))),
            front_size=np.asarray(scan_trace["front_size"], np.int64),
            hypervolume=np.asarray(scan_trace["hypervolume"], np.float64),
            best=np.asarray(scan_trace["best"], np.float64),
            feasible_frac=np.asarray(scan_trace["feasible_frac"],
                                     np.float64),
            n_evals=(np.arange(g, dtype=np.int64) + 1)
            * int(evals_per_generation),
            hv_gen=(np.asarray(scan_trace["hv_now"], np.float64)
                    if "hv_now" in scan_trace else None))

    @classmethod
    def from_history(cls, history: Sequence, evals_per_step: int = 1,
                     objectives: Sequence[str] = ("objective",)
                     ) -> "ConvergenceTrace":
        """Adapt a scalarized engine's ``(iteration, best)`` history (the
        BO x SA loop tracks one incumbent, so ``front_size`` is 1 and there
        are no hypervolume pairs)."""
        vals = [float(v) for i, v in history
                if isinstance(i, (int, np.integer))]
        g = len(vals)
        best = (np.minimum.accumulate(np.asarray(vals, np.float64))
                if g else np.zeros(0))
        return cls(objectives=tuple(objectives), pairs=(),
                   front_size=np.ones(g, np.int64),
                   hypervolume=np.zeros((g, 0)),
                   best=best, feasible_frac=np.ones(g),
                   n_evals=(np.arange(g, dtype=np.int64) + 1)
                   * int(evals_per_step))

    def extend(self, other: "ConvergenceTrace") -> "ConvergenceTrace":
        """Concatenate a follow-on segment: evaluation counts accumulate,
        and the running hv / best stay monotone across the seam."""
        if other.objectives != self.objectives:
            raise ValueError("cannot extend a trace across objective sets")
        off = int(self.n_evals[-1]) if len(self.n_evals) else 0
        cat = lambda a, b: np.concatenate([np.asarray(a), np.asarray(b)])
        hv = np.maximum.accumulate(
            cat(self.hypervolume, other.hypervolume), axis=0)
        ahv = [a for a in (self.archive_hv, other.archive_hv)
               if a is not None]
        hvg = [a for a in (self.hv_gen, other.hv_gen) if a is not None]
        return ConvergenceTrace(
            objectives=self.objectives, pairs=self.pairs,
            front_size=cat(self.front_size, other.front_size),
            hypervolume=hv,
            best=np.minimum.accumulate(cat(self.best, other.best)),
            feasible_frac=cat(self.feasible_frac, other.feasible_frac),
            n_evals=cat(self.n_evals, np.asarray(other.n_evals) + off),
            archive_hv=np.concatenate(ahv, axis=0) if ahv else None,
            hv_gen=np.concatenate(hvg, axis=0) if hvg else None)

    def summary(self) -> Dict:
        """JSON-serializable digest persisted alongside the archive npz."""
        g = self.generations
        return dict(
            generations=int(g),
            n_evals=int(self.n_evals[-1]) if g else 0,
            objectives=list(self.objectives),
            pairs=[list(p) for p in self.pairs],
            front_size_final=int(self.front_size[-1]) if g else 0,
            hypervolume_final=[float(v) for v in self.hypervolume[-1]]
            if g else [],
            best_final=float(self.best[-1]) if g else None,
            feasible_frac_mean=float(np.mean(self.feasible_frac))
            if g else 0.0)


# ---------------------------------------------------------------------------
# crash-safe npz persistence (archives + the cross-spec manifest)
# ---------------------------------------------------------------------------
def atomic_savez(path, **arrays) -> Path:
    """``np.savez_compressed`` through a same-directory temp file and an
    atomic ``os.replace``: a crash or kill mid-write leaves the previous
    file (or nothing) in place, never a truncated npz.  The temp file is
    opened explicitly so numpy cannot append a second ``.npz`` suffix."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(
        f".{path.name}.tmp{os.getpid()}.{threading.get_ident()}")
    try:
        with open(tmp, "wb") as f:
            np.savez_compressed(f, **arrays)
        os.replace(tmp, path)
    finally:
        tmp.unlink(missing_ok=True)
    return path


# ---------------------------------------------------------------------------
# jit-compatible archive update
# ---------------------------------------------------------------------------
def _sanitize(objs):
    return jnp.where(jnp.isfinite(objs), objs.astype(F), F(BIG))


@jax.jit
def _archive_update(objs, valid, designs, new_objs, new_valid, new_designs):
    """Merge a batch into the archive state and prune to capacity.

    All shapes static (capacity from ``objs.shape[0]``, batch from
    ``new_objs.shape[0]``); one call = one vmapped dominance pass over
    archive+batch, so insertion cost is independent of insertion history."""
    cap = objs.shape[0]
    a_objs = jnp.concatenate([objs, _sanitize(new_objs)], axis=0)
    a_valid = jnp.concatenate([valid, new_valid], axis=0)
    a_valid = a_valid & jnp.all(a_objs < BIG, axis=-1)
    a_designs = jax.tree.map(
        lambda x, y: jnp.concatenate([x, y], axis=0), designs, new_designs)

    with jax.named_scope("dominance"):
        nd = dominance_counts(a_objs, a_valid)
        front = (nd == 0) & a_valid
    with jax.named_scope("crowding"):
        crowd = crowding_distance(a_objs, front)
        # ranking (ascending): nondominated by descending crowding
        # (boundary points carry inf crowding => kept first), then
        # dominated/invalid rows.
        keyv = jnp.where(front, -jnp.minimum(crowd, F(1e9)),
                         F(BIG) + nd.astype(F))
        order = jnp.argsort(keyv)[:cap]
    return (a_objs[order], front[order],
            jax.tree.map(lambda x: x[order], a_designs))


def flatten_design(design: Dict) -> jnp.ndarray:
    """One design pytree -> a flat float32 feature vector, leaves raveled
    in CANONICAL sorted-key order.  jit/vmap-safe (shape is static per
    design template).  This layout IS the surrogate dataset contract:
    ``ParetoArchive.export_rows`` emits training rows in exactly this
    order, and the gated NSGA scan encodes candidates with this function
    — the two must never diverge."""
    return jnp.concatenate([jnp.ravel(jnp.asarray(design[k])).astype(F)
                            for k in sorted(design)])


def design_encoding_dim(template: Dict) -> int:
    """Length of ``flatten_design`` output for one design template."""
    return int(sum(np.asarray(v).size for v in template.values()))


class ParetoArchive:
    """Fixed-capacity nondominated archive over stacked design pytrees.

    ``template`` is one design point (a dict of arrays) fixing the leaf
    shapes/dtypes; objectives are an (n, ``n_obj``) matrix, all minimized.
    After every ``insert`` the archive contains only mutually nondominated
    points (capacity permitting — overflow is pruned by crowding distance,
    which always preserves per-objective boundary points)."""

    def __init__(self, capacity: int, template: Dict, n_obj: int = 4,
                 obj_keys: Optional[Sequence[str]] = None):
        self.capacity = int(capacity)
        self.n_obj = int(n_obj)
        self.obj_keys = tuple(obj_keys) if obj_keys else None
        self.objs = np.full((capacity, n_obj), BIG, np.float32)
        self.valid = np.zeros(capacity, bool)
        self.designs = {
            k: np.zeros((capacity,) + np.asarray(v).shape,
                        np.asarray(v).dtype)
            for k, v in template.items()}
        self.n_evals = 0            # total evaluations recorded against this
        #                             archive (cache-freshness metadata)
        self.searched = ()          # objective names search effort was ever
        #                             spent on (cache-coverage metadata)
        self.budget_covered = 0     # largest query budget this archive has
        #                             answered: plateau early-stopping may
        #                             spend FEWER than ``n_evals`` requested
        #                             evaluations, yet the query counts as
        #                             covered (the front had converged)
        self.trace_summary = {}     # last refinement's ConvergenceTrace
        #                             .summary(), persisted for dashboards

    def __len__(self) -> int:
        return int(self.valid.sum())

    def insert(self, designs: Dict, objs, mask=None, count_evals=True):
        """Insert a stacked batch: ``designs`` leaves (m, ...), ``objs``
        (m, n_obj), ``mask`` (m,); leading batch axes (e.g. a scan's
        (generations, pop)) are flattened into m.  Non-finite objective
        rows are dropped.  Dispatching the update and reading its result
        back are two spans, ``archive.insert`` and ``explore.fetch``:
        the read is where the host waits for the device."""
        with obs.span("archive.insert"):
            objs = jnp.asarray(objs, F).reshape(-1, self.n_obj)
            m = objs.shape[0]
            new_valid = (jnp.ones(m, bool) if mask is None
                         else jnp.asarray(mask, bool).reshape(m))
            new_designs = {
                k: jnp.asarray(v).reshape((m,) + self.designs[k].shape[1:])
                for k, v in designs.items()}
            # the archive is one-device state: a batch sharded over an
            # island mesh comes to the default device first, since the
            # update's Mosaic dominance kernel cannot be partitioned
            # automatically
            objs, new_valid, new_designs = jax.device_put(
                (objs, new_valid, new_designs), jax.devices()[0])
            o, v, d = _archive_update(
                jnp.asarray(self.objs), jnp.asarray(self.valid),
                {k: jnp.asarray(v) for k, v in self.designs.items()},
                objs, new_valid, new_designs)
        with obs.span("explore.fetch"):
            self.objs = np.asarray(o)
            self.valid = np.asarray(v)
            self.designs = {k: np.asarray(x) for k, x in d.items()}
        if count_evals:
            self.n_evals += int(m)
        return self

    def merge(self, other: "ParetoArchive") -> "ParetoArchive":
        """Fold another archive of the SAME problem into this one — the
        reload-under-lock half of the shared-cache write path: a writer
        about to ``save`` merges whatever a peer process put on disk
        since it last loaded, so concurrent refinements union instead of
        last-``os.replace``-wins.

        Only rows not already present are inserted (exact objective-row
        bytes; nondominated duplicates would otherwise coexist, since
        neither dominates the other), with ``count_evals=False`` — the
        evaluations behind ``other``'s rows were counted by the process
        that paid for them.  Counters take the element-wise max (both
        sides descend from a common disk state, so max is the tightest
        merge that never *under*-reports coverage), ``searched`` is the
        union."""
        if set(other.designs) != set(self.designs):
            raise ValueError("cannot merge archives of different design "
                             "templates")
        have = {r.tobytes() for r in self.objs[self.valid]}
        sel = np.flatnonzero(other.valid)
        sel = np.asarray([i for i in sel
                          if other.objs[i].tobytes() not in have], int)
        if sel.size:
            self.insert({k: v[sel] for k, v in other.designs.items()},
                        other.objs[sel], count_evals=False)
        self.n_evals = max(self.n_evals, other.n_evals)
        self.budget_covered = max(self.budget_covered, other.budget_covered)
        self.searched = tuple(sorted(set(self.searched)
                                     | set(other.searched)))
        if not self.trace_summary:
            self.trace_summary = dict(other.trace_summary)
        return self

    def front(self) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        """(stacked designs of the valid rows, their (n, n_obj) objectives)."""
        sel = np.flatnonzero(self.valid)
        return ({k: v[sel] for k, v in self.designs.items()},
                self.objs[sel].astype(np.float64))

    def export_rows(self) -> Tuple[np.ndarray, np.ndarray]:
        """Surrogate training rows from this archive: ``(X, Y)`` where
        ``X`` is the (n, D) float32 matrix of flattened design encodings
        (``flatten_design`` layout — canonical sorted-key order) of every
        valid row and ``Y`` the matching (n, n_obj) float64 raw-metric
        matrix.  Every evaluation the fleet ever archived is a free
        labelled example; cold archives export ``(0, D)``/``(0, n_obj)``
        so callers can concatenate unconditionally."""
        D = design_encoding_dim({k: v[0] for k, v in self.designs.items()})
        sel = np.flatnonzero(self.valid)
        if not sel.size:
            return (np.zeros((0, D), np.float32),
                    np.zeros((0, self.n_obj), np.float64))
        X = np.stack([
            np.concatenate([np.ravel(self.designs[k][i]).astype(np.float32)
                            for k in sorted(self.designs)])
            for i in sel])
        return X, self.objs[sel].astype(np.float64)

    def projected_hypervolume(self, pair: Tuple[int, int],
                              ref: float = HV_LOG_REF) -> float:
        """2-D hypervolume of the archived front projected onto a pair of
        objective columns, over clipped log-metrics w.r.t. ``(ref, ref)`` —
        the same scale the NSGA scan traces, so the service's plateau
        detector compares archive state across scan segments directly."""
        i, j = pair
        pts = self.objs[self.valid][:, [i, j]].astype(np.float64)
        return hypervolume_2d(np.log(np.maximum(pts, 1e-3)), (ref, ref))

    # ---- persistence -------------------------------------------------------
    def save(self, path) -> Path:
        meta = dict(capacity=self.capacity, n_obj=self.n_obj,
                    n_evals=self.n_evals, searched=list(self.searched),
                    obj_keys=list(self.obj_keys or ()),
                    budget_covered=self.budget_covered,
                    trace_summary=self.trace_summary)
        with obs.span("archive.save", key=Path(path).stem,
                      n_front=len(self)):
            return atomic_savez(
                path, __meta=np.frombuffer(
                    json.dumps(meta).encode(), dtype=np.uint8),
                objs=self.objs, valid=self.valid,
                **{f"d_{k}": v for k, v in self.designs.items()})

    @classmethod
    def load(cls, path) -> "ParetoArchive":
        with obs.span("archive.load", key=Path(path).stem), \
                np.load(Path(path)) as z:
            meta = json.loads(bytes(z["__meta"]).decode())
            designs = {k[2:]: z[k] for k in z.files if k.startswith("d_")}
            template = {k: v[0] for k, v in designs.items()}
            arc = cls(meta["capacity"], template, n_obj=meta["n_obj"],
                      obj_keys=meta["obj_keys"] or None)
            arc.objs = z["objs"].copy()
            arc.valid = z["valid"].copy()
            arc.designs = {k: v.copy() for k, v in designs.items()}
            arc.n_evals = int(meta["n_evals"])
            arc.searched = tuple(meta.get("searched", ()))
            # archives written before budget accounting: evaluations
            # recorded then were always full-budget spends
            arc.budget_covered = int(meta.get("budget_covered",
                                              meta["n_evals"]))
            arc.trace_summary = dict(meta.get("trace_summary", {}))
        return arc


# ---------------------------------------------------------------------------
# canonical (SystemSpec, DesignSpace) hashing for the on-disk cache
# ---------------------------------------------------------------------------
def spec_space_key(spec, space, extra=None) -> str:
    """Stable content hash of an exploration problem: the padded workload
    arrays plus every static ``DesignSpace`` bound.  Equal workload graphs
    explored under equal bounds share one archive file, whatever Python
    objects they were built from.  ``extra`` folds any further
    cache-identity into the key; callers pass a STABLE string digest — the
    evaluator's tech identity is ``core.constants.tech_key(tech)``, never
    the object's ``repr`` (see ``ExplorationService.problem_key`` /
    ``Session._cache_key``).  Duck-typed so this module stays free of
    ``repro.core`` imports."""
    h = hashlib.sha256()
    if extra is not None:
        h.update(repr(extra).encode())
    h.update(repr((int(spec.W), int(spec.CH), int(spec.E))).encode())
    for k in sorted(spec.arrays):
        a = np.asarray(spec.arrays[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    h.update(repr((tuple(space.max_shape), int(space.max_logB),
                   int(space.max_total_pes), int(space.fixed_packaging),
                   int(space.fixed_family),
                   bool(space.allow_pipeline))).encode())
    return h.hexdigest()[:20]


# ---------------------------------------------------------------------------
# cross-spec archive manifest: the nearest-neighbor index over every cached
# exploration problem, keyed by workload-feature embedding
# ---------------------------------------------------------------------------
MANIFEST_NAME = "manifest.npz"


@dataclasses.dataclass(frozen=True)
class ManifestPolicy:
    """Growth policy of the cross-spec manifest index.

    ``max_entries`` bounds the index: past it, the least-recently-*used*
    entry (lowest ``last_used`` tick; transfer lookups and refreshes both
    count as use) is evicted — index entries only, the archive npz files
    they pointed at stay on disk and are re-indexed on their next use.
    ``dedup_radius`` > 0 merges entries whose embeddings are within that
    Euclidean distance (the better-explored twin survives, counters are
    merged), so a fleet cache full of near-identical problems does not
    crowd genuinely different neighbors out of ``nearest``.
    ``max_trust_records`` bounds the per-(src, dst) transfer-outcome table
    (oldest records dropped first).
    ``reap_evicted_after`` > 0 opts into archive-file GC: an archive npz
    whose manifest entry stayed evicted (LRU-evicted or dedup-merged away,
    and never re-indexed) for that many LRU ticks is deleted from disk at
    the next ``reap_evicted`` sweep.  The default 0 keeps the historic
    behavior — eviction bounds the index only, files stay."""
    max_entries: int = 64
    dedup_radius: float = 0.0
    max_trust_records: int = 256
    reap_evicted_after: int = 0


@dataclasses.dataclass(frozen=True)
class TrustModel:
    """Ridge regression ``lift ~ w0 + w . |embedding delta|`` fitted over
    recorded transfer outcomes: how much of a seeded run's hypervolume
    gain arrived in its earliest generations, as a function of how far the
    seed's source workload sat from the destination in embedding space.
    ``predict`` returns the expected lift for a candidate (src, dst) pair;
    callers treat larger as more trustworthy (clamping at 0)."""
    weights: np.ndarray                # (D + 1,) intercept first

    def predict(self, delta) -> float:
        d = np.abs(np.asarray(delta, np.float64).ravel())
        if d.shape[0] + 1 != self.weights.shape[0]:
            return 0.0                 # embedding layout drifted: neutral
        # clamp at 0, as promised: a linear extrapolation far outside the
        # fitted delta range can go arbitrarily negative, and consumers
        # divide distances by (1 + lift) — a lift <= -1 would flip or
        # explode the ranking instead of merely zeroing the reweighting
        return float(max(self.weights[0] + self.weights[1:] @ d, 0.0))


def fit_trust_model(records: Sequence[Dict], dim: Optional[int] = None,
                    ridge: float = 1.0,
                    min_records: int = 3) -> Optional[TrustModel]:
    """Fit a ``TrustModel`` over transfer-outcome records (dicts with
    ``delta`` (D,) and ``lift`` float).  Records whose delta dimension
    disagrees with ``dim`` (default: the *modal* dimension across the
    records — one drifted-layout straggler must not silently disqualify
    the whole majority-dim history) are skipped and counted on the
    ``explore.trust.skipped_records`` counter; fewer than
    ``min_records`` usable records yields ``None`` — callers fall back
    to unweighted Euclidean ranking."""
    usable = [r for r in records
              if np.all(np.isfinite(np.asarray(r["delta"], np.float64)))
              and np.isfinite(r["lift"])]
    if not usable:
        return None
    if dim is None:
        sizes = [np.asarray(r["delta"]).size for r in usable]
        # modal dim, newest-layout wins ties: count per dim, then prefer
        # the dim seen most; among equally-common dims the one appearing
        # latest in the record stream (the freshest layout)
        counts: Dict[int, int] = {}
        for s in sizes:
            counts[s] = counts.get(s, 0) + 1
        dim = max(counts, key=lambda s: (counts[s],
                                         max(i for i, sz in enumerate(sizes)
                                             if sz == s)))
    kept = [r for r in usable
            if np.asarray(r["delta"]).size == dim]
    if len(kept) < len(usable):
        obs.inc("explore.trust.skipped_records", len(usable) - len(kept))
    usable = kept
    if len(usable) < max(int(min_records), 1):
        return None
    X = np.stack([np.concatenate(
        [[1.0], np.abs(np.asarray(r["delta"], np.float64).ravel())])
        for r in usable])
    y = np.asarray([float(r["lift"]) for r in usable])
    A = X.T @ X + ridge * np.eye(X.shape[1])
    A[0, 0] -= ridge                   # don't shrink the intercept
    try:
        w = np.linalg.solve(A, X.T @ y)
    except np.linalg.LinAlgError:
        return None
    return TrustModel(weights=w)


class ArchiveManifest:
    """Index of an explore cache directory: one entry per archived problem
    key, carrying the problem's workload-feature embedding (fixed-dim; see
    ``repro.core.workload.workload_features``), its padded dims, freshness
    counters, an LRU ``last_used`` tick, and an opaque JSON-portable
    *space digest* (everything ``repro.core.encoding.migrate`` needs to
    move designs OUT of that archive without reconstructing the source
    graph).  A ``ManifestPolicy`` bounds growth (LRU eviction +
    embedding-space dedup, see there), and a *trust table* of per-(src,
    dst) transfer outcomes rides along for ``fit_trust_model``.

    ``nearest(embedding, k)`` ranks cached problems by Euclidean distance
    in embedding space — the cross-workload transfer lookup; with
    ``trust=`` a fitted ``TrustModel``, distances are reweighted by
    predicted lift so calibrated-useful neighbors rank ahead of merely
    geometrically-close ones.  Persistence is a single atomically-written
    npz; a damaged or truncated manifest is discarded with a warning,
    never fatal (a cache index is disposable).  This module stays free of
    ``repro.core`` imports: digests are stored and returned as plain
    dicts."""

    def __init__(self, path=None, policy: ManifestPolicy = ManifestPolicy()):
        self.path = Path(path) if path is not None else None
        self.policy = policy
        self.entries: Dict[str, Dict] = {}
        self.trust: List[Dict] = []    # per-(src, dst) transfer outcomes
        self.clock = 0                 # monotone LRU tick
        self.evicted: Dict[str, int] = {}   # key -> tick it left the index
        #                                     (LRU eviction or dedup merge);
        #                                     cleared on re-index, consumed
        #                                     by ``reap_evicted``

    def __len__(self) -> int:
        return len(self.entries)

    def _tick(self) -> int:
        self.clock += 1
        return self.clock

    def touch(self, key: str):
        """Mark one entry as just-used (transfer lookups call this for the
        neighbors they actually seeded from, so useful sources stay
        resident under LRU pressure)."""
        if key in self.entries:
            self.entries[key]["last_used"] = self._tick()
        return self

    def update(self, key: str, embedding, dims: Tuple[int, int, int],
               n_evals: int, budget_covered: int,
               searched: Sequence[str], digest: Optional[Dict] = None):
        """Insert or refresh one problem's entry (digest kept from the
        previous entry when not re-supplied), then enforce the growth
        policy — the entry being written is never the one evicted or
        merged away."""
        prev = self.entries.get(key, {})
        self.evicted.pop(key, None)    # re-indexed: no longer a GC victim
        self.entries[key] = dict(
            embedding=np.asarray(embedding, np.float64),
            dims=tuple(int(v) for v in dims),
            n_evals=int(n_evals), budget_covered=int(budget_covered),
            searched=tuple(searched),
            digest=digest if digest is not None else prev.get("digest"),
            last_used=self._tick())
        self.enforce(protect=(key,))
        return self

    # ---- growth policy -----------------------------------------------------
    def enforce(self, protect: Sequence[str] = ()):
        """Apply the growth policy: embedding-space dedup first (merging
        frees room without losing coverage), then LRU eviction down to
        ``max_entries``.  ``protect`` keys are never removed."""
        self.dedup(protect=protect)
        prot = set(protect)
        while len(self.entries) > max(int(self.policy.max_entries), 1):
            victims = [k for k in self.entries if k not in prot]
            if not victims:
                break
            victim = min(victims, key=lambda k: (
                self.entries[k].get("last_used", 0), k))
            del self.entries[victim]
            self.evicted[victim] = self.clock
            obs.inc("explore.manifest.evictions")
        return self

    def reap_evicted(self, cache_dir=None) -> Tuple[str, ...]:
        """Opt-in archive-file GC (``policy.reap_evicted_after`` > 0):
        delete the archive npz of every key that left the index at least
        that many LRU ticks ago and was never re-indexed since.  Returns
        the reaped keys; their eviction records are dropped (nothing left
        to reap).  A no-op under the default policy, and never touches
        keys currently in the index."""
        after = int(self.policy.reap_evicted_after)
        if after <= 0 or not self.evicted:
            return ()
        root = Path(cache_dir) if cache_dir is not None else (
            self.path.parent if self.path is not None else None)
        if root is None:
            return ()
        reaped = []
        for key, tick in list(self.evicted.items()):
            if key in self.entries:          # defensive: indexed keys are
                self.evicted.pop(key)        # never GC victims
                continue
            if self.clock - int(tick) < after:
                continue
            (root / f"{key}.npz").unlink(missing_ok=True)
            self.evicted.pop(key)
            reaped.append(key)
        return tuple(reaped)

    def _survivor(self, a: str, b: str, protect: Sequence[str]) -> str:
        """Which of two near-identical entries survives a merge: protected
        keys always win, then the better-explored one, ties broken on the
        key alone — never on insertion order or LRU ticks, so merging is
        commutative (the same survivor whichever order the entries
        arrived in)."""
        if (a in protect) != (b in protect):
            return a if a in protect else b
        score = lambda k: (self.entries[k]["n_evals"],
                           self.entries[k]["budget_covered"],
                           k)
        return max((a, b), key=score)

    def dedup(self, protect: Sequence[str] = ()):
        """Merge entries whose embeddings are within ``dedup_radius`` of
        each other.  The survivor keeps its own key/embedding/digest and
        absorbs the max of both freshness counters and the union of their
        searched objectives.  Scanning key-sorted pairs with a symmetric
        survivor rule makes the merge idempotent, commutative, and
        invariant under entry-insertion order."""
        radius = float(self.policy.dedup_radius)
        if radius <= 0 or len(self.entries) < 2:
            return self
        keys = sorted(self.entries)
        gone: set = set()
        for i, a in enumerate(keys):
            if a in gone:
                continue
            for b in keys[i + 1:]:
                if a in gone:
                    break
                if b in gone:
                    continue
                ea, eb = self.entries[a], self.entries[b]
                if ea["embedding"].shape != eb["embedding"].shape:
                    continue
                if np.linalg.norm(ea["embedding"]
                                  - eb["embedding"]) > radius:
                    continue
                keep = self._survivor(a, b, protect)
                drop = b if keep == a else a
                ek, ed = self.entries[keep], self.entries[drop]
                ek["n_evals"] = max(ek["n_evals"], ed["n_evals"])
                ek["budget_covered"] = max(ek["budget_covered"],
                                           ed["budget_covered"])
                ek["searched"] = tuple(sorted(
                    set(ek["searched"]) | set(ed["searched"])))
                ek["last_used"] = max(ek.get("last_used", 0),
                                      ed.get("last_used", 0))
                gone.add(drop)
        for k in gone:
            del self.entries[k]
            self.evicted[k] = self.clock    # merged away counts as evicted
            #                                 for the opt-in file GC too
            obs.inc("explore.manifest.dedup_merges")
        return self

    def merge(self, other: "ArchiveManifest") -> "ArchiveManifest":
        """Fold another manifest into this one — the reload-under-lock
        half of the shared-index write path (see ``ParetoArchive.merge``
        for the race it closes).  Typically ``self`` is the manifest
        just re-read from disk and ``other`` carries this process's
        pending mutations; the merge is field-wise so neither side's
        records are dropped:

        * entries: union by key; a key present on both sides keeps
          ``self``'s embedding/dims/digest (same problem, same content)
          and takes the max of the freshness counters and LRU tick, and
          the union of ``searched`` — counters only ever grow, so max
          never un-covers a budget a peer already paid for.
        * trust records: union, deduplicated by full record identity
          (two processes recording the same outcome from a shared
          journal must not double-weight the fit).
        * ``clock``/``evicted``: max tick wins; a key any side currently
          indexes is not evicted.

        Growth-policy enforcement is the CALLER's job (the writer holds
        the lock and knows which key to protect)."""
        for key, e in other.entries.items():
            mine = self.entries.get(key)
            if mine is None:
                self.entries[key] = dict(
                    e, embedding=np.asarray(e["embedding"], np.float64),
                    searched=tuple(e["searched"]))
                continue
            mine["n_evals"] = max(mine["n_evals"], e["n_evals"])
            mine["budget_covered"] = max(mine["budget_covered"],
                                         e["budget_covered"])
            mine["searched"] = tuple(sorted(set(mine["searched"])
                                            | set(e["searched"])))
            mine["last_used"] = max(mine.get("last_used", 0),
                                    e.get("last_used", 0))
            if mine.get("digest") is None:
                mine["digest"] = e.get("digest")
        seen = {(r["src"], r["dst"], r["lift"], r["delta"].tobytes())
                for r in self.trust}
        for r in other.trust:
            ident = (r["src"], r["dst"], r["lift"], r["delta"].tobytes())
            if ident not in seen:
                seen.add(ident)
                self.trust.append(dict(r))
        keep = max(int(self.policy.max_trust_records), 1)
        if len(self.trust) > keep:
            self.trust = self.trust[-keep:]
        self.clock = max(self.clock, other.clock)
        for k, t in other.evicted.items():
            self.evicted[k] = max(self.evicted.get(k, 0), int(t))
        for k in list(self.evicted):
            if k in self.entries:
                del self.evicted[k]
        return self

    # ---- trust table -------------------------------------------------------
    def record_transfer(self, src: str, dst: str, delta, lift: float):
        """Append one observed transfer outcome: seeds migrated from
        ``src`` into ``dst``'s run, whose workload embeddings differ by
        ``delta`` (per-dimension absolute difference), produced ``lift``
        (fraction of the run's hypervolume gain landed in its earliest
        generations — measured from the run's own ``ConvergenceTrace``,
        zero extra evaluations).  Oldest records roll off past
        ``max_trust_records``."""
        self.trust.append(dict(
            src=str(src), dst=str(dst),
            delta=np.asarray(delta, np.float64).ravel(),
            lift=float(lift)))
        keep = max(int(self.policy.max_trust_records), 1)
        if len(self.trust) > keep:
            self.trust = self.trust[-keep:]
        return self

    def export_index(self, exclude: Sequence[str] = ()
                     ) -> List[Tuple[str, np.ndarray]]:
        """The surrogate-dataset half of the manifest: ``(key,
        embedding)`` for every indexed problem whose archive holds paid
        evaluations on disk, sorted by key (deterministic harvest order),
        minus ``exclude`` — the target problem itself, or holdout graphs
        a benchmark keeps out of training."""
        skip = set(exclude)
        return [(k, e["embedding"]) for k, e in sorted(self.entries.items())
                if k not in skip and e["n_evals"] > 0
                and e.get("digest") is not None]

    def trust_model(self, dim: Optional[int] = None):
        """The fitted trust model over this manifest's recorded outcomes
        (``None`` until enough records accumulate).  With a deep record
        table (>= ``surrogate.NONLINEAR_TRUST_MIN``) the non-linear
        MLP head takes over from the ridge ``TrustModel`` — same
        ``predict(delta) -> lift >= 0`` contract, but it can learn that
        e.g. only SOME embedding axes predict transfer failure.  Falls
        back to the ridge fit whenever the MLP cannot be fit."""
        from .surrogate import NONLINEAR_TRUST_MIN, fit_nonlinear_trust
        if len(self.trust) >= NONLINEAR_TRUST_MIN:
            tm = fit_nonlinear_trust(self.trust, dim=dim)
            if tm is not None:
                return tm
        return fit_trust_model(self.trust, dim=dim)

    def nearest(self, embedding, k: int = 3,
                exclude: Sequence[str] = (),
                trust: Optional[TrustModel] = None
                ) -> List[Tuple[str, float]]:
        """The ``k`` cached problems closest to ``embedding`` (ascending
        effective distance), skipping excluded keys, empty archives and
        entries whose embedding dimension does not match the query's.
        Plain Euclidean by default; with ``trust``, each distance is
        divided by ``1 + max(predicted lift, 0)`` so neighbors the model
        learned to trust rank closer.  Ties break on key, so the result
        is invariant under entry-insertion order."""
        q = np.asarray(embedding, np.float64).ravel()
        out = []
        for key, e in self.entries.items():
            if key in exclude or e["n_evals"] <= 0:
                continue
            emb = e["embedding"]
            if emb.shape != q.shape:
                continue
            dist = float(np.linalg.norm(emb - q))
            if trust is not None:
                dist = dist / (1.0 + max(trust.predict(q - emb), 0.0))
            out.append((key, dist))
        out.sort(key=lambda t: (t[1], t[0]))
        return out[:max(int(k), 0)]

    # ---- persistence -------------------------------------------------------
    def save(self, path=None) -> Path:
        path = Path(path) if path is not None else self.path
        if path is None:
            raise ValueError("manifest has no path")
        keys = sorted(self.entries)
        meta = dict(
            version=3,
            keys=keys,
            clock=int(self.clock),
            evicted={k: int(t) for k, t in self.evicted.items()},
            entries={k: dict(
                dims=list(self.entries[k]["dims"]),
                n_evals=self.entries[k]["n_evals"],
                budget_covered=self.entries[k]["budget_covered"],
                searched=list(self.entries[k]["searched"]),
                last_used=int(self.entries[k].get("last_used", 0)),
                digest=self.entries[k]["digest"]) for k in keys},
            trust=[dict(src=r["src"], dst=r["dst"], lift=r["lift"],
                        delta=[float(v) for v in r["delta"]])
                   for r in self.trust])
        # one array per entry, NOT one stacked matrix: entries written
        # under different embedding layouts (a WL_EMBED_DIM upgrade) must
        # not wedge persistence with a ragged np.stack
        emb = {f"emb_{i}": np.asarray(self.entries[k]["embedding"],
                                      np.float64)
               for i, k in enumerate(keys)}
        return atomic_savez(
            path, __meta=np.frombuffer(json.dumps(meta).encode(),
                                       dtype=np.uint8),
            **emb)

    @classmethod
    def load(cls, path,
             policy: ManifestPolicy = ManifestPolicy()) -> "ArchiveManifest":
        """Load a manifest, tolerating absence and damage: anything
        unreadable yields an EMPTY manifest (with a warning) so one bad
        write can never take the exploration service down.  Version-1
        manifests (no LRU ticks, no trust table) load with zeroed
        ``last_used`` and an empty trust table."""
        path = Path(path)
        m = cls(path, policy=policy)
        if not path.exists():
            return m
        try:
            with np.load(path) as z:
                meta = json.loads(bytes(z["__meta"]).decode())
                if "embeddings" in z.files:     # stacked pre-v2 layout
                    stacked = np.asarray(z["embeddings"], np.float64)
                    emb = [stacked[i] for i in range(len(meta["keys"]))]
                else:
                    emb = [np.asarray(z[f"emb_{i}"], np.float64)
                           for i in range(len(meta["keys"]))]
            for i, k in enumerate(meta["keys"]):
                e = meta["entries"][k]
                m.entries[k] = dict(
                    embedding=emb[i],
                    dims=tuple(e["dims"]),
                    n_evals=int(e["n_evals"]),
                    budget_covered=int(e["budget_covered"]),
                    searched=tuple(e["searched"]),
                    digest=e.get("digest"),
                    last_used=int(e.get("last_used", 0)))
            m.clock = int(meta.get("clock", 0))
            m.evicted = {str(k): int(t)
                         for k, t in meta.get("evicted", {}).items()}
            m.trust = [dict(src=r["src"], dst=r["dst"],
                            delta=np.asarray(r["delta"], np.float64),
                            lift=float(r["lift"]))
                       for r in meta.get("trust", [])]
        except Exception as exc:        # disposable index: never fatal
            warnings.warn(f"discarding unreadable explore manifest "
                          f"{path}: {exc}")
            m.entries = {}
            m.trust = []
            m.clock = 0
            m.evicted = {}
        # honor THIS reader's policy immediately: a file written under a
        # laxer bound (or unbounded v1) must not keep a read-mostly
        # service over budget until its first write
        m.enforce()
        return m
