"""The exploration *service*: the NSGA engine backend behind
``repro.api.Session.submit`` (``run_queries``), plus the historic
``explore`` / ``explore_batch`` entry points as deprecation shims.

Turns the one-shot DSE scripts into a reusable, cache-accelerated query
backend.  Four tricks make repeated / concurrent exploration cheap:

* **Query batching** — ``explore_batch`` groups concurrent queries whose
  (SystemSpec, DesignSpace) hash matches into ONE NSGA-II run over the
  union of their objectives and the max of their budgets; every query then
  projects its own front out of the shared archive.  One vmapped
  evaluation serves the whole group.
* **Archive cache** — before spending compute, the service consults the
  per-problem ``ParetoArchive`` (in memory, then on disk under
  ``cache_dir``).  A query whose budget is already covered by recorded
  evaluations is answered straight from the archive: no evaluator, no jit.
* **Warm starts** — when compute IS needed, the initial population is
  seeded from the cached front (topped up with ``random_design`` samples
  drawn by one compiled ``nsga.sample_designs`` program),
  so follow-up queries with bigger budgets refine rather than restart.
* **Adaptive budgets** (``BudgetPolicy``) — a query's budget is spent in
  quantized scan *segments*; after each segment the archive-projected
  hypervolume of the queried objective pairs is checked, and once its
  relative improvement stays below ``plateau_rel`` for ``patience``
  consecutive segments the refinement stops early.  The unspent
  evaluations are *banked* in a per-problem budget ledger, and
  ``explore_batch`` reallocates banked credit to the batch's
  under-explored, still-improving archives (lowest eval-count first).

The archive rows are always the full 4-metric vector (``METRIC_KEYS``), so
one cache serves latency-energy, latency-cost, ... projections alike.
Every cold answer carries a ``ConvergenceTrace`` — the per-generation
telemetry the NSGA scan emits for free — and a summary is persisted with
the archive npz.

* **Cross-workload transfer v2** — ``transfer=True`` seeds cold starts
  AND budget-increase refinements from the migrated fronts of the best
  cached neighbors (``ArchiveManifest.nearest``, reweighted by the
  manifest's fitted ``TrustModel`` once enough per-(src, dst) outcomes
  accumulate); seeds dedup against the destination archive's own front
  (``portable_signature``) and every seeded run books its observed
  hypervolume lift back into the trust table at zero extra evaluations.
  The manifest itself is growth-bounded (``ManifestPolicy``: LRU
  eviction + embedding-space dedup) and mtime-reloaded, so fleet-shared
  cache directories stay consistent.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import os
import threading
import time
import warnings
from collections import OrderedDict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.constants import DEFAULT_TECH, TechConstants, tech_key
from ..core.encoding import (DesignSpace, balanced_init, migrate,
                             portable_signature, repair,
                             space_digest)
from ..core.evaluate import SystemSpec
from ..core.optimizer import METRIC_KEYS
from ..core.workload import (WorkloadGraph, embedding_delta,
                             workload_features)
from .archive import (MANIFEST_NAME, ArchiveManifest, ConvergenceTrace,
                      ManifestPolicy, ParetoArchive, atomic_savez,
                      design_encoding_dim, objective_pairs, pareto_front,
                      spec_space_key)
from . import quantize
from .locks import LockTimeout, file_lock, lock_path
from .nsga import (ISLAND_AXIS, NSGAConfig, _static_key, design_template,
                   make_nsga, make_nsga_fused, make_nsga_gated,
                   sample_designs)
from .surrogate import Surrogate, SurrogateConfig, fit_surrogate, harvest_rows

# the default archive cache is anchored to the repo root (four levels above
# this file: src/repro/explore/service.py), NOT the process CWD — otherwise
# every working directory silently grows its own fragmented cache.
# $REPRO_EXPLORE_CACHE (the historic name), $REPRO_CACHE_DIR (the fleet-wide
# name) or an explicit ``cache_dir`` override it, in that order.
DEFAULT_CACHE_DIR = (Path(__file__).resolve().parents[3]
                     / "artifacts" / "explore_cache")
DEFAULT_OBJECTIVES = ("latency_ns", "cost_usd")


def resolve_cache_dir(cache_dir=None) -> Path:
    """The cache directory a service will really use, validated: an
    explicit ``cache_dir`` wins, then ``$REPRO_EXPLORE_CACHE``, then
    ``$REPRO_CACHE_DIR``, then the repo-anchored default.  The directory
    is created here (so a fleet-wide env var pointing somewhere unwritable
    fails loudly at service CONSTRUCTION, not at the first archive save
    deep inside a query)."""
    p = Path(cache_dir
             or os.environ.get("REPRO_EXPLORE_CACHE")
             or os.environ.get("REPRO_CACHE_DIR")
             or DEFAULT_CACHE_DIR).expanduser()
    try:
        p.mkdir(parents=True, exist_ok=True)
    except OSError as e:
        raise ValueError(f"explore cache directory {p} is unusable "
                         f"(check REPRO_CACHE_DIR / REPRO_EXPLORE_CACHE / "
                         f"cache_dir): {e}") from e
    if not os.access(p, os.W_OK):      # mkdir(exist_ok) is a silent no-op
        #                                on a pre-existing read-only dir
        raise ValueError(f"explore cache directory {p} is not writable "
                         f"(check REPRO_CACHE_DIR / REPRO_EXPLORE_CACHE / "
                         f"cache_dir)")
    return p


# `_pow2` kept as a module-level alias: the quantization lattice now
# lives in `repro.explore.quantize` (shared with megabatch bucketing and
# `api` plan math), but external callers historically import it from here.
_pow2 = quantize.pow2_ceil


def _transfer_lift(trace: ConvergenceTrace) -> float:
    """Front-loadedness of one seeded run, in [0, 1]: the mean of the
    per-generation population-front hypervolume (``hv_gen``) normalized
    into the run's own [min, max] range — the area under the normalized
    trajectory.  A run whose seeded start already carried the quality
    spends every generation near its own maximum (→ 1); a run that had
    to search for everything climbs slowly (≈ 0.5 for a linear climb,
    lower for a late jump).  Self-normalized per run, so values compare
    across problems and archive maturities, at zero extra evaluations;
    a flat trajectory carries no temporal signal either way and records
    a neutral 0.5.  (Under elitist selection the trajectory is
    near-monotone, so any single-generation statistic — e.g. generation
    0's own position — degenerates to ~0 for every run; the area does
    not.)"""
    hv = trace.hv_gen if trace.hv_gen is not None else trace.hypervolume
    if hv is None or hv.size == 0:
        return 0.0
    col = np.asarray(hv[:, 0], np.float64)
    lo, hi = float(col.min()), float(col.max())
    if hi - lo <= 1e-9 * max(abs(hi), 1.0):
        return 0.5                  # flat run: no temporal signal at all
    return float(np.clip(np.mean((col - lo) / (hi - lo)), 0.0, 1.0))


@dataclasses.dataclass(frozen=True)
class BudgetPolicy:
    """How a query's evaluation budget is spent.

    ``chunk_generations`` splits the NSGA scan into segments of that many
    generations (quantized to a power of two, so segment runners compile
    once per size); between segments the service is on the host and can
    observe the archive.  With ``adaptive`` on, refinement stops early
    once EVERY queried objective pair's archive-projected hypervolume
    improved by less than ``plateau_rel`` (relative) for ``patience``
    consecutive segments; the unspent evaluations are banked in the
    service's per-problem ledger.  ``reallocate`` lets ``explore_batch``
    spend banked credit on the batch's under-explored, still-improving
    archives.  Single-objective queries have no hypervolume pairs and
    never stop early.

    ``megabatch`` lets ``run_queries`` fuse DIFFERENT problems whose spec
    arrays and quantized schedules coincide into one vmapped dispatch
    (lane counts pow2-padded, capped at ``megabatch_lanes``); individual
    queries opt out via ``ExploreQuery.megabatch=False``, and the fused
    path is skipped entirely under ``resume=True`` (checkpoints stay
    per-group) or when the service shards over a device mesh."""
    chunk_generations: int = 8
    plateau_rel: float = 0.005
    patience: int = 2
    adaptive: bool = True
    reallocate: bool = True
    megabatch: bool = True
    megabatch_lanes: int = 8


@dataclasses.dataclass
class PlateauState:
    """The plateau detector's memory across the scan segments refining
    ONE archive: the previous segment's archive-projected hypervolume
    vector and the current below-threshold streak.

    Held per problem *group* (not per ``_refine`` call) so a
    checkpointed resume continues the streak exactly where the killed
    run left it, and so the detector's history is an explicit object
    with an explicit lifetime: ``reset()`` forgets it, and is called
    when a reallocation top-up grants fresh budget — a topped-up archive
    must earn a NEW streak before being declared plateaued, never be
    stopped one segment into its top-up on the strength of pre-top-up
    stagnation."""
    last_hv: Optional[np.ndarray] = None
    streak: int = 0

    def observe(self, hv_now, rel_tol: float, count: bool = True) -> int:
        """Record one segment's hypervolume vector and return the
        updated streak.  ``count=False`` records the vector without
        judging it (the empty-archive case: nothing found yet is
        stagnation, not convergence — it must never feed the streak,
        but the NEXT segment still compares against this one)."""
        hv_now = np.asarray(hv_now, np.float64)
        if (count and self.last_hv is not None
                and self.last_hv.shape == hv_now.shape):
            rel = (hv_now - self.last_hv) / np.maximum(
                np.abs(self.last_hv), 1e-9)
            self.streak = self.streak + 1 if np.all(rel < rel_tol) else 0
        self.last_hv = hv_now
        return self.streak

    def reset(self) -> "PlateauState":
        self.last_hv = None
        self.streak = 0
        return self


class RunControl:
    """Cooperative stop token for a running submission.  ``stop()``
    (from any thread) makes the engine break at the NEXT scan-segment
    boundary: the segment in flight completes, the resume checkpoint
    stays on disk, and every result of the interrupted submission
    carries ``interrupted=True`` with ``budget_covered`` NOT bumped — a
    later ``resume=True`` submission of the same problem picks up from
    that checkpoint and spends only the residual budget."""

    __slots__ = ("_stop",)

    def __init__(self):
        self._stop = threading.Event()

    def stop(self) -> None:
        self._stop.set()

    @property
    def stopped(self) -> bool:
        return self._stop.is_set()


@dataclasses.dataclass
class ExploreQuery:
    """One front request.  ``space_kwargs`` are forwarded to ``DesignSpace``
    (e.g. ``max_shape``, ``max_total_pes``) and participate in the cache
    key, so differently-bounded explorations never share an archive.

    ``spec``/``space`` optionally carry a prebuilt problem (the
    ``repro.api`` path builds them once on its ``Problem``); when absent
    the service derives them from ``graph``/``ch_max``/``space_kwargs``."""
    graph: WorkloadGraph
    objectives: Tuple[str, ...] = DEFAULT_OBJECTIVES
    budget: int = 2048              # total design evaluations this query
    #                                 is willing to pay for (cold)
    ch_max: int = 4
    space_kwargs: Optional[Dict] = None
    transfer: bool = False          # seed cold starts AND budget-increase
    #                                 refinements from migrated fronts of
    #                                 the trust-ranked nearest cached specs
    #                                 (balanced_init fallback on a cold
    #                                 start with no neighbor; resumed
    #                                 archives dedup seeds against their
    #                                 own front and take no fallback)
    spec: Optional[SystemSpec] = None
    space: Optional[DesignSpace] = None
    megabatch: bool = True          # allow this query's group to fuse with
    #                                 other problems into one compiled
    #                                 dispatch (see BudgetPolicy.megabatch)
    surrogate: Optional[Dict] = None    # surrogate-gated evaluation: None
    #                                 (off — the exact path, byte-for-byte
    #                                 historical), or a dict of
    #                                 ``SurrogateConfig`` overrides (``{}``
    #                                 for defaults; ``True`` normalizes to
    #                                 ``{}``).  An extra ``"exclude"`` key
    #                                 lists archive keys held out of
    #                                 surrogate training (benchmark
    #                                 holdouts).  With no usable training
    #                                 rows in the fleet cache the query
    #                                 silently runs exact — bit-identical
    #                                 to surrogate=None.

    def __post_init__(self):
        self.objectives = tuple(self.objectives)
        if not self.objectives:
            raise ValueError("at least one objective required")
        bad = [o for o in self.objectives if o not in METRIC_KEYS]
        if bad:
            raise ValueError(f"unknown objectives {bad}; pick from "
                             f"{METRIC_KEYS}")
        if self.surrogate is True:
            self.surrogate = {}
        if self.surrogate is not None and not isinstance(self.surrogate,
                                                         dict):
            raise ValueError("surrogate must be None, True or a dict of "
                             "SurrogateConfig overrides")

    def build(self) -> Tuple[SystemSpec, DesignSpace]:
        """This query's (spec, space), built on demand and memoized."""
        if self.spec is None:
            self.spec = SystemSpec.build(self.graph, ch_max=self.ch_max)
        if self.space is None:
            self.space = DesignSpace(self.spec, **(self.space_kwargs or {}))
        return self.spec, self.space


@dataclasses.dataclass(frozen=True)
class SegmentEvent:
    """One streamed scan-segment boundary (see ``run_queries``'s
    ``on_segment``): the archive ``cache_key`` being refined, the segment
    index within its phase, the segment's incremental ``ConvergenceTrace``
    slice (extend the slices to recover the run's full trace), and the
    phase — ``"refine"`` for a group's own budget, ``"realloc"`` for a
    reallocation top-up spending banked ledger credit (scalarized engines
    fire one completion event tagged with the engine name).

    ``elapsed_s`` is the segment's wall-clock, measured once at the scan
    boundary from the same monotonic clock as the result's ``elapsed_s``
    accounting — consumers get per-segment timing without running their
    own timers or a journal.  ``seq`` totally orders the events of one
    execution stream (monotone across ALL phases of a ``run_queries`` /
    ``Session.submit`` call, while ``segment`` restarts per phase)."""
    cache_key: str
    segment: int
    trace: ConvergenceTrace
    phase: str = "refine"
    elapsed_s: float = 0.0
    seq: int = 0


@dataclasses.dataclass
class ExploreResult:
    objectives: Tuple[str, ...]
    front_objs: np.ndarray          # (n, len(objectives)) nondominated rows
    front_metrics: np.ndarray       # (n, 4) full METRIC_KEYS rows
    front_designs: List[Dict[str, np.ndarray]]
    from_cache: bool                # True => served without any evaluation
    n_evals_run: int                # evaluations spent by the shared run
    #                                 that answered this query's GROUP (the
    #                                 cost is reported on every result of
    #                                 the group, booked once in the
    #                                 archive); 0 when served from cache
    elapsed_s: float                # wall time of the group's answer
    cache_key: str
    trace: Optional[ConvergenceTrace] = None    # per-generation telemetry
    #                                 of the group's run (None on pure
    #                                 cache hits — see the archive's
    #                                 persisted ``trace_summary``)
    plateaued: bool = False         # hypervolume plateaued => stopped early
    n_evals_banked: int = 0         # evaluations the early stop banked
    #                                 into the budget ledger
    n_evals_realloc: int = 0        # extra evaluations this group received
    #                                 from the batch's banked credit
    transferred_from: Tuple[str, ...] = ()      # neighbor archive keys whose
    #                                 migrated fronts seeded this cold run
    n_transfer_seeds: int = 0       # seed designs injected into the initial
    #                                 population (migrated or balanced_init)
    interrupted: bool = False       # a RunControl stop (or checkpointed
    #                                 kill) ended the run before its budget:
    #                                 the front reflects partial progress
    #                                 and budget_covered was NOT bumped
    surrogate_used: bool = False    # a fleet surrogate gated this group's
    #                                 evaluations (False when not requested
    #                                 OR the cache was too cold to fit one
    #                                 — the latter runs the exact path,
    #                                 bit-identical to surrogate=None)
    surrogate_hits: int = 0         # candidate evaluations skipped on the
    #                                 surrogate's say-so (the realized eval
    #                                 savings)
    surrogate_fallbacks: int = 0    # 1 when segment-mean ensemble
    #                                 disagreement abandoned the surrogate
    #                                 mid-run (exact for the remainder)


@dataclasses.dataclass
class SurrogateGate:
    """A fitted fleet surrogate bound to one group's workload embedding —
    everything ``_refine`` needs to gate a refinement's evaluations."""
    model: Surrogate
    embedding: np.ndarray
    cfg: SurrogateConfig


class ExplorationService:
    """Holds per-problem archives (memory + disk) and a shared NSGA engine.

    ``cache_dir`` defaults to ``$REPRO_EXPLORE_CACHE`` or the repo-anchored
    ``artifacts/explore_cache``; archives live at ``<cache_dir>/<key>.npz``.
    ``policy`` governs adaptive budget spending (see ``BudgetPolicy``);
    ``ledger`` maps problem key -> evaluations banked by plateau early
    stops, spendable by later batches' under-explored problems.
    """

    def __init__(self, cache_dir=None, capacity: int = 256,
                 nsga: NSGAConfig = NSGAConfig(), tech=None,
                 policy: BudgetPolicy = BudgetPolicy(),
                 transfer_k: int = 3,
                 manifest_policy: ManifestPolicy = ManifestPolicy(),
                 mesh=None):
        # nsga.generations is not used on the query path — each query's
        # budget sets the scan length (see _refine); the config's pop /
        # fields / crossover / mutation / immigrant knobs apply as given.
        # ``mesh`` (a jax.sharding.Mesh with an "islands" axis) shards
        # every refinement's population across the mesh as island-model
        # NSGA (see make_nsga); quantized populations too small to shard
        # fall back to the single-device scan, and megabatching is
        # disabled while a mesh is set (the two layouts are mutually
        # exclusive — fusing sharded runs is a follow-on).
        self.cache_dir = resolve_cache_dir(cache_dir)
        self.capacity = int(capacity)
        self.nsga = nsga
        if tech is not None and not isinstance(tech, TechConstants):
            # preset name / artifact path / CalibratedTech -> constants
            from ..core.presets import resolve_tech
            _, tech = resolve_tech(tech)
        self.tech = tech
        self.policy = policy
        self.mesh = mesh
        self.transfer_k = int(transfer_k)
        self.manifest_policy = manifest_policy
        self.ledger: Dict[str, int] = {}
        self._archives: Dict[str, ParetoArchive] = {}
        # neighbor archives loaded ONLY to migrate seeds out of live in a
        # small LRU side-cache keyed on the npz mtime (stale fronts are
        # re-read): repeated transfer queries don't re-read the npz, yet
        # a churning fleet can't grow memory without bound
        self._neighbor_cache: \
            "OrderedDict[str, Tuple[int, ParetoArchive]]" = OrderedDict()
        self._neighbor_cache_cap = max(8, 2 * self.transfer_k)
        self._manifest: Optional[ArchiveManifest] = None
        self._manifest_mtime: Optional[int] = None
        # per-key npz mtime at the last load/save THIS service performed:
        # a differing disk mtime at save time means a peer process wrote
        # the archive since, and the locked save merges before replacing
        self._archive_sync: Dict[str, Optional[int]] = {}

    def _manifest_stat(self) -> Optional[int]:
        try:
            return (self.cache_dir / MANIFEST_NAME).stat().st_mtime_ns
        except OSError:
            return None

    @property
    def manifest(self) -> ArchiveManifest:
        """The cross-spec index of this cache directory (lazy-loaded;
        damaged or absent files yield an empty manifest).  The file's
        mtime is checked on EVERY access: a second service writing the
        same cache directory invalidates this one's in-memory copy, so
        eviction/dedup/transfer decisions never act on a stale index.
        Multi-step operations (seeding, trust recording) snapshot the
        property ONCE and work on that object — a mid-operation reload
        must never yank entries out from under an iteration; the
        snapshot's mutations are saved at the end (last writer wins)."""
        mtime = self._manifest_stat()
        if self._manifest is None or mtime != self._manifest_mtime:
            if self._manifest is not None:      # a genuine staleness
                obs.inc("explore.manifest.reloads")     # reload, not the
            #                                     first lazy load
            with obs.span("manifest.reload"):
                self._manifest = ArchiveManifest.load(
                    self.cache_dir / MANIFEST_NAME,
                    policy=self.manifest_policy)
            self._manifest_mtime = mtime
        return self._manifest

    # ---- cache plumbing ----------------------------------------------------
    def _path(self, key: str) -> Path:
        return self.cache_dir / f"{key}.npz"

    def problem_key(self, spec: SystemSpec, space: DesignSpace) -> str:
        """Archive identity for one exploration problem under THIS
        service's tech constants — metrics evaluated under a different
        ``TechConstants`` (including a calibrated preset) must never be
        served as this problem's front.  The tech folds in as its stable
        ``tech_key()`` content digest, not its repr."""
        return spec_space_key(spec, space,
                              extra=tech_key(self.tech or DEFAULT_TECH))

    def archive_for(self, spec: SystemSpec, space: DesignSpace,
                    key: Optional[str] = None) -> ParetoArchive:
        """The (possibly empty) archive for one exploration problem —
        memory first, then disk, else freshly created."""
        key = key or self.problem_key(spec, space)
        if key in self._archives:
            return self._archives[key]
        arc = None
        p = self._path(key)
        if p.exists():
            try:
                arc = ParetoArchive.load(p)
            except Exception as e:          # a cache is disposable: never
                #                             let a damaged file kill a query
                warnings.warn(f"discarding unreadable explore cache {p}: {e}")
                p.unlink(missing_ok=True)
        if arc is None:
            arc = ParetoArchive(self.capacity, design_template(space),
                                n_obj=len(METRIC_KEYS),
                                obj_keys=METRIC_KEYS)
        else:
            self._mark_sync(key, p)
        self._archives[key] = arc
        return arc

    def _mark_sync(self, key: str, p: Path) -> None:
        try:
            self._archive_sync[key] = p.stat().st_mtime_ns
        except OSError:
            self._archive_sync.pop(key, None)

    def _merge_disk(self, key: str, arc: ParetoArchive, p: Path) -> None:
        """Fold a peer process's on-disk archive state into ``arc`` when
        the npz changed since this service last synced it.  Unreadable
        peer state is skipped with a warning — a cache merge must never
        fail the query riding on it."""
        try:
            mt = p.stat().st_mtime_ns
        except OSError:
            return
        if mt == self._archive_sync.get(key):
            return
        try:
            arc.merge(ParetoArchive.load(p))
            self._archive_sync[key] = mt
            obs.inc("explore.archive.merges")
        except Exception as e:
            warnings.warn(f"could not merge peer archive state {p}: {e}")

    def save(self, key: str):
        """Persist one archive, lock → reload → merge → replace: under
        the per-archive file lock, anything a peer process put on disk
        since this service last synced is merged in before the atomic
        replace, so concurrent refinements of one problem union instead
        of last-``os.replace``-wins.  A lock timeout degrades to the
        historic unmerged save with a warning — a wedged peer must never
        fail the query whose results are being persisted."""
        arc = self._archives.get(key)
        if arc is None:
            return
        p = self._path(key)
        try:
            with file_lock(lock_path(p)):
                self._merge_disk(key, arc, p)
                arc.save(p)
                self._mark_sync(key, p)
        except LockTimeout as e:
            warnings.warn(f"archive lock busy for {key} ({e}); "
                          f"saving without peer merge")
            arc.save(p)
            self._mark_sync(key, p)

    def refresh_archive(self, spec: SystemSpec, space: DesignSpace,
                        key: Optional[str] = None) -> ParetoArchive:
        """The freshest known archive for one problem: the in-memory
        copy merged with whatever peer processes have put on disk since
        this service last synced it.  The overload/degradation path
        serves (possibly stale) fronts straight from here, spending zero
        evaluations — fresh enough beats perfectly fresh when the
        alternative is an unbounded queue."""
        key = key or self.problem_key(spec, space)
        arc = self.archive_for(spec, space, key=key)
        self._merge_disk(key, arc, self._path(key))
        return arc

    def _ckpt_path(self, key: str) -> Path:
        """Where a resumable submission checkpoints mid-run state (one
        atomic npz beside the archive; deleted on normal completion)."""
        return self.cache_dir / f"{key}.ckpt.npz"

    # ---- the query API -----------------------------------------------------
    def explore(self, graph: WorkloadGraph,
                objectives: Sequence[str] = DEFAULT_OBJECTIVES,
                budget: int = 2048, ch_max: int = 4,
                space_kwargs: Optional[Dict] = None,
                transfer: bool = False, key=None) -> ExploreResult:
        """DEPRECATED shim — routes through ``repro.api.Session.submit``
        (``Query(Problem(...), engine="nsga")``) and returns the same
        ``ExploreResult`` the NSGA backend produced."""
        warnings.warn(
            "legacy entry point ExplorationService.explore() is "
            "deprecated; use repro.api: Session(...).submit(Query("
            "Problem(graph, objectives, ...), budget=..., transfer=...))",
            DeprecationWarning, stacklevel=2)
        from .api import Problem, Query, Session
        q = Query(Problem(graph, objectives=tuple(objectives),
                          ch_max=ch_max, space_kwargs=space_kwargs),
                  budget=budget, engine="nsga", transfer=transfer)
        return Session(service=self).submit(q, key=key).raw

    def explore_batch(self, queries: Sequence[ExploreQuery],
                      key=None) -> List[ExploreResult]:
        """DEPRECATED shim — routes through ``repro.api.Session.submit``
        with one ``Query`` per legacy ``ExploreQuery`` (same grouping,
        batching and reallocation semantics; see ``run_queries``)."""
        warnings.warn(
            "legacy entry point ExplorationService.explore_batch() is "
            "deprecated; use repro.api: Session(...).submit([Query(...), "
            "...])",
            DeprecationWarning, stacklevel=2)
        from .api import Problem, Query, Session
        qs = [Query(Problem(q.graph, objectives=q.objectives,
                            ch_max=q.ch_max, space_kwargs=q.space_kwargs),
                    budget=q.budget, engine="nsga", transfer=q.transfer,
                    engine_opts=({"surrogate": q.surrogate}
                                 if q.surrogate is not None else None))
              for q in queries]
        return [r.raw for r in Session(service=self).submit(qs, key=key)]

    def run_queries(self, queries: Sequence[ExploreQuery], key=None,
                    on_segment=None, resume: bool = False,
                    control: Optional[RunControl] = None
                    ) -> List[ExploreResult]:
        """The NSGA engine backend: answer a batch of queries, merging
        same-problem queries into one vmapped NSGA run (union objectives,
        max budget).  This is the execution path behind
        ``repro.api.Session.submit``; the legacy ``explore`` /
        ``explore_batch`` shims arrive here too.

        After every group has spent (or banked) its own budget, banked
        credit — this batch's plus any ledger balance carried over from
        earlier early stops — is reallocated to the batch's still-improving
        groups (the ones that exhausted their budget without plateauing),
        lowest recorded eval-count first.

        ``on_segment`` (callable taking one ``SegmentEvent``) streams each
        scan segment's incremental ``ConvergenceTrace`` slice as soon as
        the segment finishes — the dashboard/async-serving hook.  Callback
        failures are warned about (with phase and segment index), counted
        on the ``obs.on_segment_errors`` counter, and journaled as
        ``callback_error`` records — never fatal to the query.

        ``resume=True`` makes every cold group checkpoint its mid-run
        state after each segment (one atomic npz beside the archive) and
        restore from a matching checkpoint on entry: a killed run
        re-submitted with the same queries and ``key`` replays from the
        last completed segment, spends only the residual budget, and
        lands on the bit-identical final front (the PRNG chain folds the
        segment index, so segment ``s`` draws the same keys whichever
        attempt runs it).  ``control`` (a ``RunControl``) requests a
        cooperative stop at the next segment boundary — interrupted
        results carry ``interrupted=True`` and do NOT mark the budget
        covered."""
        key = jax.random.PRNGKey(0) if key is None else key
        # group by canonical problem hash
        groups: Dict[str, Dict] = {}
        order: List[Tuple[str, int]] = []      # (cache_key, slot in group)
        for q in queries:
            spec, space = q.build()
            ck = self.problem_key(spec, space)
            g = groups.setdefault(ck, dict(spec=spec, space=space,
                                           queries=[]))
            order.append((ck, len(g["queries"])))
            g["queries"].append(q)

        # one monotone event sequence across every phase of this batch
        seq = itertools.count()
        with obs.span("explore.run_queries", queries=len(queries),
                      groups=len(groups)):
            # per-group keys are fixed by enumeration order BEFORE any
            # batching decision, so a group's PRNG chain — and therefore
            # its refined front — is identical whether it runs
            # sequentially or fused into a megabatch lane
            gkeys = {ck: jax.random.fold_in(key, i)
                     for i, ck in enumerate(groups)}
            fused = set()
            if (self.policy.megabatch and not resume and self.mesh is None
                    and len(groups) > 1):
                fused = self._megabatch_pass(groups, gkeys, on_segment,
                                             seq, control)
            for ck, g in groups.items():
                if ck in fused:
                    continue
                self._refine_group(ck, g, gkeys[ck],
                                   on_segment=on_segment, seq=seq,
                                   resume=resume, control=control)
            if self.policy.reallocate:
                self._reallocate(groups,
                                 jax.random.fold_in(key, len(groups)),
                                 on_segment=on_segment, seq=seq,
                                 control=control)

        group_results = {ck: self._project_group(ck, g)
                         for ck, g in groups.items()}
        return [group_results[ck][slot] for ck, slot in order]

    @staticmethod
    def _segment_cb(on_segment, ck: str, phase: str, seq=None):
        """Wrap the user callback for one group's refinement: tag events
        with the archive key, phase, stream sequence number and the
        segment's wall-clock (measured once, at the scan boundary in
        ``_refine``), journal one ``segment`` record per boundary, and
        never let a callback failure kill the query it was observing —
        failures are warned about with their phase/segment coordinates,
        counted (``obs.on_segment_errors``) and journaled so telemetry
        consumers can see the events they lost.  ``None`` (skip event
        assembly entirely) when nobody is listening."""
        if on_segment is None and not obs.active():
            return None
        seq = seq if seq is not None else itertools.count()

        def cb(s: int, tr: ConvergenceTrace, elapsed_s: float,
               compiled: bool):
            ev = SegmentEvent(ck, s, tr, phase, elapsed_s=elapsed_s,
                              seq=next(seq))
            if obs.active():
                hv = (tr.archive_hv[-1] if tr.archive_hv is not None
                      and len(tr.archive_hv) else None)
                obs.emit(dict(
                    type="segment", key=ck, phase=phase, segment=s,
                    seq=ev.seq, elapsed_s=elapsed_s, compile=compiled,
                    n_evals=int(tr.n_evals[-1]) if len(tr.n_evals) else 0,
                    front_size=(int(tr.front_size[-1])
                                if len(tr.front_size) else 0),
                    hv=[float(v) for v in hv] if hv is not None else None))
            if on_segment is None:
                return
            try:
                on_segment(ev)
            except Exception as e:
                obs.inc("obs.on_segment_errors")
                if obs.active():
                    obs.emit(dict(type="callback_error", key=ck,
                                  phase=phase, segment=s, seq=ev.seq,
                                  error=repr(e)))
                warnings.warn(
                    f"on_segment callback failed for {ck} "
                    f"(phase={phase}, segment={s}): {e}")
        return cb

    # ---- one problem group -------------------------------------------------
    def _open_group(self, ck: str, g: Dict) -> bool:
        """Shared prologue of one group's refinement (sequential OR
        megabatched): resolve the archive, record the query facts on
        ``g`` and return the warm verdict (True => served straight from
        cache, nothing to refine).  Idempotent — the megabatch pre-pass
        may open a group the sequential loop later revisits."""
        if "warm" in g:
            return g["warm"]
        with obs.span("explore.open_group", key=ck):
            arc = g["arc"] = self.archive_for(g["spec"], g["space"],
                                              key=ck)
            g["embedding"] = workload_features(g["spec"].graph)
            budget = g["budget"] = max(q.budget for q in g["queries"])
            union = g["union"] = tuple(
                k for k in METRIC_KEYS
                if any(k in q.objectives for q in g["queries"]))
            warm = self.warm_verdict(arc, union, budget)
            obs.inc("explore.cache.hit" if warm else "explore.cache.miss")
            g.update(warm=warm, n_run=0, trace=None, plateaued=False,
                     banked=0, realloc=0, transferred_from=(), n_seeds=0,
                     interrupted=False, plateau=PlateauState(),
                     # any group member asking for surrogate gating turns
                     # it on for the shared run (like budget: max wins)
                     surrogate=next((q.surrogate for q in g["queries"]
                                     if q.surrogate is not None), None),
                     sur_used=False, sur_hits=0, sur_fallbacks=0)
            if warm and ck not in self.manifest.entries:
                self._update_manifest(ck, g)     # backfill pre-manifest
                #                                  caches into the index
        return warm

    def _group_seeds(self, ck: str, g: Dict, key) -> Optional[Dict]:
        """Transfer seeds for one opened group, when any of its queries
        asked for them.  Cold starts AND warm refinements take seeds: a
        half-explored archive profits from neighbor fronts it has never
        seen, but its own front head keeps at least half the
        population."""
        if not any(q.transfer for q in g["queries"]):
            return None
        arc = g["arc"]
        pop_eff = self._effective_pop(g["budget"])
        cap = pop_eff if len(arc) == 0 else max(pop_eff // 2, 1)
        with obs.span("explore.transfer_seeds", key=ck):
            seeds, srcs = self._transfer_seeds(
                ck, g["space"], g["embedding"],
                jax.random.fold_in(key, 0x7e5), arc=arc, cap=cap)
        g["transferred_from"] = srcs
        g["n_seeds"] = (int(next(iter(seeds.values())).shape[0])
                        if seeds else 0)
        return seeds

    def _book_refinement(self, ck: str, g: Dict, sp, n_run: int, trace,
                         plateaued: bool, banked: int,
                         interrupted: bool) -> None:
        """Shared epilogue of one group's refinement: archive accounting,
        eval/bank counters, trust calibration and manifest/disk sync."""
        with obs.span("explore.book", key=ck):
            arc, union, budget = g["arc"], g["union"], g["budget"]
            arc.searched = tuple(k for k in METRIC_KEYS
                                 if k in arc.searched or k in union)
            if not interrupted:
                # an interrupted run must NOT mark the budget covered —
                # the resumed attempt still owes the residual segments
                arc.budget_covered = max(arc.budget_covered, budget)
            obs.inc("explore.evals.spent", n_run)
            if banked:
                obs.inc("explore.evals.banked", banked)
                self.ledger[ck] = self.ledger.get(ck, 0) + banked
            g.update(n_run=n_run, trace=trace, plateaued=plateaued,
                     banked=banked, interrupted=interrupted)
            if sp is not None:
                sp.set(n_run=n_run, plateaued=plateaued, banked=banked,
                       n_seeds=g["n_seeds"], interrupted=interrupted)
            if trace is not None:       # a stop before the first segment
                arc.trace_summary = trace.summary()     # leaves no trace
            self.save(ck)
            m = self.manifest           # ONE snapshot: the trust records
            #                             land in the same object the
            #                             index update saves below
            self._record_trust(ck, g, trace, m)
            self._update_manifest(ck, g, m)

    def _refine_group(self, ck: str, g: Dict, key, on_segment=None,
                      seq=None, resume: bool = False,
                      control: Optional[RunControl] = None) -> None:
        """Phase 1: spend (or bank) the group's own budget.  Mutates ``g``
        with the run's accounting; fronts are projected later, after any
        cross-group budget reallocation topped the archive up."""
        t0 = time.perf_counter()
        if self._open_group(ck, g):
            g["elapsed"] = time.perf_counter() - t0
            return
        budget, union, arc = g["budget"], g["union"], g["arc"]
        with obs.span("explore.refine_group", key=ck, budget=budget) as sp:
            seeds = self._group_seeds(ck, g, key)
            gate = (self._fit_gate(ck, g)
                    if g["surrogate"] is not None else None)
            n_run, trace, plateaued, banked, interrupted, sstats = \
                self._refine(
                    arc, g["spec"], g["space"], union, budget, key,
                    seeds=seeds,
                    on_segment=self._segment_cb(on_segment, ck, "refine",
                                                seq=seq),
                    plateau=g["plateau"], control=control,
                    checkpoint=self._ckpt_path(ck) if resume else None,
                    gate=gate)
            g.update(sur_used=sstats["used"], sur_hits=sstats["hits"],
                     sur_fallbacks=sstats["fallbacks"])
            self._book_refinement(ck, g, sp, n_run, trace, plateaued,
                                  banked, interrupted)
        g["elapsed"] = time.perf_counter() - t0

    def _fit_gate(self, ck: str, g: Dict) -> Optional[SurrogateGate]:
        """Fit the evaluation-gating surrogate for one opened group from
        every OTHER cached archive the fleet manifest indexes (plus the
        group's own archived rows, when it is a warm refinement).
        Returns ``None`` when the harvest is too cold to fit
        (``SurrogateConfig.min_rows``) — the caller then runs the exact
        path, bit-identical to ``surrogate=None``."""
        opts = dict(g["surrogate"])
        exclude = tuple(opts.pop("exclude", ()))
        try:
            cfg = SurrogateConfig(**opts)
        except TypeError as e:
            raise ValueError(f"bad surrogate options "
                             f"{sorted(opts)}: {e}") from None
        arc = g["arc"]
        emb = np.asarray(g["embedding"], np.float32).ravel()
        design_dim = design_encoding_dim(
            {k: v[0] for k, v in arc.designs.items()})
        with obs.span("explore.surrogate_fit", key=ck):
            index = self.manifest.export_index(exclude=(ck,) + exclude)
            X, Y = harvest_rows(index, self._load_neighbor, design_dim,
                                emb.size)
            own_X, own_Y = arc.export_rows()
            if len(own_X):
                own = np.concatenate(
                    [own_X, np.tile(emb, (len(own_X), 1))], axis=1)
                X = np.concatenate([X, own]) if len(X) else own
                Y = np.concatenate([Y, own_Y]) if len(Y) else own_Y
            sur = fit_surrogate(X, Y, cfg)
        if sur is None:
            obs.inc("explore.surrogate.cold")
            return None
        return SurrogateGate(model=sur, embedding=emb, cfg=cfg)

    # ---- cross-problem megabatching ----------------------------------------
    def _fuse_signature(self, g: Dict):
        """Everything that must coincide for two problem groups to share
        one fused compiled dispatch: the NSGA scan statics (padded dims,
        space bounds, objective columns, variation config, tech) plus the
        quantized segment schedule.  Spec ARRAY VALUES are free to differ
        — they ride the lane axis."""
        spec, space = g["spec"], g["space"]
        sched = quantize.schedule(g["budget"], self.nsga.pop,
                                  self.policy.chunk_generations)
        idx = tuple(METRIC_KEYS.index(o) for o in g["union"])
        cfg = dataclasses.replace(self.nsga, pop=sched.pop,
                                  generations=sched.chunk)
        return _static_key((spec.W, spec.CH, spec.E), idx, cfg,
                           self.tech or DEFAULT_TECH, space) + (sched,)

    def _megabatch_pass(self, groups: Dict[str, Dict], gkeys, on_segment,
                        seq, control) -> set:
        """Bucket this batch's cold, megabatch-willing groups by fused
        compile signature and answer every bucket of >= 2 problems with
        one vmapped lockstep refinement.  Returns the keys of the groups
        fully handled here (warm groups it served count too); the caller
        runs the rest sequentially."""
        done: set = set()
        buckets: Dict[tuple, List[Tuple[str, Dict]]] = {}
        for ck, g in groups.items():
            if not all(getattr(q, "megabatch", True)
                       for q in g["queries"]):
                continue
            if any(getattr(q, "surrogate", None) is not None
                   for q in g["queries"]):
                continue    # surrogate gating runs the sequential loop —
                #             fusing gated lanes is a follow-on
            t0 = time.perf_counter()
            if self._open_group(ck, g):
                g["elapsed"] = time.perf_counter() - t0     # warm: served
                done.add(ck)
                continue
            buckets.setdefault(self._fuse_signature(g), []).append((ck, g))
        cap = max(2, int(self.policy.megabatch_lanes))
        for bucket in buckets.values():
            for lo in range(0, len(bucket), cap):
                part = bucket[lo:lo + cap]
                if len(part) < 2:       # nothing to fuse with — leave it
                    continue            # to the sequential loop
                self._refine_group_fused(part, gkeys, on_segment, seq,
                                         control)
                done.update(ck for ck, _ in part)
        return done

    def _refine_group_fused(self, bucket: List[Tuple[str, Dict]], gkeys,
                            on_segment, seq, control) -> None:
        """Run one bucket of distinct-problem groups as fused lanes of a
        single vmapped NSGA dispatch, then book each group exactly as the
        sequential path would."""
        t0 = time.perf_counter()
        with obs.span("explore.megabatch", lanes=len(bucket),
                      keys=",".join(ck for ck, _ in bucket)) as sp:
            lanes = []
            for ck, g in bucket:
                lanes.append(dict(
                    ck=ck, g=g, key=gkeys[ck],
                    seeds=self._group_seeds(ck, g, gkeys[ck]),
                    cb=self._segment_cb(on_segment, ck, "refine", seq=seq)))
            results = self._refine_fused(lanes, control=control)
            for (ck, g), r in zip(bucket, results):
                self._book_refinement(ck, g, None, *r)
            sp.set(n_run=sum(r[0] for r in results))
        dt = time.perf_counter() - t0
        for _, g in bucket:     # wall-clock is genuinely shared: every
            g["elapsed"] = dt   # lane waited on the same dispatches

    def _refine_fused(self, lanes: List[Dict], control=None
                      ) -> List[Tuple]:
        """The megabatched ``_refine``: every lane (one problem group)
        shares a single quantized schedule and one ``make_nsga_fused``
        runner; per-lane archives, seeding, plateau streaks, traces and
        banking follow the sequential semantics segment by segment.

        The lane count of each dispatch is pow2-padded
        (``quantize.bucket_lanes``); padding slots replay the first live
        lane and their outputs are DISCARDED — masked per-problem lanes,
        in exchange for a lane-count compile lattice of O(log(batch)).
        When a lane plateaus it stops booking results but the dispatch
        width stays fixed (no recompile mid-run).  No checkpoint support:
        ``run_queries`` only fuses when ``resume`` is off.  Returns one
        ``(n_run, trace, plateaued, banked, interrupted)`` per lane, in
        order."""
        policy = self.policy
        g0 = lanes[0]["g"]
        union = g0["union"]
        sched = quantize.schedule(g0["budget"], self.nsga.pop,
                                  policy.chunk_generations)
        pop, chunk, n_seg = sched.pop, sched.chunk, sched.n_seg
        cfg = dataclasses.replace(self.nsga, pop=pop, generations=chunk)
        lanes_pad = quantize.bucket_lanes(len(lanes))
        run = make_nsga_fused(g0["spec"], g0["space"], union, cfg,
                              tech=self.tech, lanes=lanes_pad)
        hv_pairs = [(METRIC_KEYS.index(union[i]),
                     METRIC_KEYS.index(union[j]))
                    for i, j in objective_pairs(len(union))]
        with obs.span("explore.init_population", lanes=len(lanes)):
            for ln in lanes:
                k_init, k_run = jax.random.split(ln["key"])
                ln.update(
                    k_run=k_run, trace=None, plateaued=False,
                    interrupted=False, spent_g=0, live=True,
                    st=ln["g"]["plateau"],
                    filler=sample_designs(k_init, ln["g"]["space"], pop))
        for s in range(n_seg):
            live = [ln for ln in lanes if ln["live"]]
            if not live:
                break
            if control is not None and control.stopped:
                for ln in live:
                    ln["interrupted"] = True
                break
            t_seg = time.perf_counter()
            compiled = not run.compile_state["executed"]
            slots = live + [live[0]] * (lanes_pad - len(live))
            with obs.span("explore.seed", lanes=len(slots)):
                pops = [_seed_population(ln["g"]["arc"], pop, ln["filler"],
                                         ln["seeds"] if s == 0 else None)
                        for ln in slots]
                pop_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *pops)
            with obs.span("explore.dispatch", lanes=len(slots)):
                keys_s = [jax.random.fold_in(ln["k_run"], s)
                          for ln in slots]
                pop_s, _raw, _sel, ev_d, ev_r, ev_f, tr = run(
                    keys_s, pop_stack,
                    [ln["g"]["spec"].arrays for ln in slots])
            # per-lane booking: identical to one sequential _refine
            # segment; padding slots (j >= len(live)) book nothing
            staged = []
            for j, ln in enumerate(live):
                arc = ln["g"]["arc"]
                with obs.span("archive.insert"):
                    lane = (jax.tree.map(lambda x: x[j], ev_d), ev_r[j],
                            ev_f[j])
                arc.insert(lane[0], lane[1], mask=lane[2],
                           count_evals=False)
                arc.n_evals += pop * chunk
                ln["spent_g"] += chunk
                with obs.span("explore.seed"):
                    ln["filler"] = jax.tree.map(lambda x: x[j], pop_s)
                tr_j = {k: v[j] for k, v in tr.items()}
                with obs.span("explore.fetch"):
                    seg_trace = ConvergenceTrace.from_scan(union, tr_j, pop)
                hv_now = np.asarray([arc.projected_hypervolume(p)
                                     for p in hv_pairs])
                seg_trace.archive_hv = hv_now[None, :]
                ln["trace"] = (seg_trace if ln["trace"] is None
                               else ln["trace"].extend(seg_trace))
                staged.append((ln, seg_trace, hv_now))
            # all host-side archive work has drained the dispatch by
            # here: dt is the honest wall-clock of the fused segment,
            # reported to every lane (they genuinely shared it)
            dt = time.perf_counter() - t_seg
            obs.inc("explore.segments")
            obs.observe("explore.segment_compile_s" if compiled
                        else "explore.segment_s", dt)
            for ln, seg_trace, hv_now in staged:
                if ln["cb"] is not None:
                    ln["cb"](s, seg_trace, dt, compiled)
                if policy.adaptive and hv_pairs:
                    streak = ln["st"].observe(
                        hv_now, policy.plateau_rel,
                        count=bool(len(ln["g"]["arc"])))
                    if streak >= policy.patience and s + 1 < n_seg:
                        ln["plateaued"] = True
                        ln["live"] = False
                        obs.inc("explore.plateau_stops")
        out = []
        for ln in lanes:
            n_run = ln["spent_g"] * pop
            banked = max(0, ln["g"]["budget"] - n_run) \
                if ln["plateaued"] else 0
            out.append((n_run, ln["trace"], ln["plateaued"], banked,
                        ln["interrupted"]))
        return out

    @staticmethod
    def warm_verdict(arc: ParetoArchive, objectives: Sequence[str],
                     budget: int) -> bool:
        """True when ``arc`` can answer a query over ``objectives`` at
        ``budget`` straight from cache: warm only when the covered budget
        (evaluations recorded, or credited by a plateau early stop) and
        every queried objective are covered — points found while
        optimizing other axes are no substitute for search effort on
        these ones.  The service's cache-hit rule and the one
        ``repro.api.Session.plan`` predicts with."""
        return (len(arc) > 0
                and max(arc.n_evals, arc.budget_covered) >= budget
                and all(o in arc.searched for o in objectives))

    def _record_trust(self, ck: str, g: Dict, trace: ConvergenceTrace,
                      m: Optional[ArchiveManifest] = None) -> None:
        """Book one calibration outcome per seeding neighbor: the run's
        observed hypervolume lift (see ``_transfer_lift``), keyed by the
        (src, dst) embedding delta.  Also LRU-touches the neighbors that
        actually seeded — useful sources stay resident.  Single-objective
        runs have no hypervolume pairs, hence no lift signal: nothing is
        recorded (a meaningless 0 would poison the regression).
        Telemetry bookkeeping must never fail a query."""
        if not g["transferred_from"] or trace is None or not trace.pairs:
            return
        try:
            m = m if m is not None else self.manifest
            lift = _transfer_lift(trace)
            for nk in g["transferred_from"]:
                ent = m.entries.get(nk)
                if ent is None:
                    continue
                m.record_transfer(
                    nk, ck, embedding_delta(g["embedding"],
                                            ent["embedding"]), lift)
                m.touch(nk)
        except Exception as e:
            warnings.warn(f"transfer trust recording failed for {ck}: {e}")

    def _update_manifest(self, ck: str, g: Dict,
                         m: Optional[ArchiveManifest] = None) -> None:
        """Refresh the cross-spec index entry for one problem (embedding,
        freshness counters, migration digest) and persist it, lock →
        reload → merge → replace.  Works on the caller's manifest
        snapshot when given, so a mid-operation mtime reload can't drop
        sibling mutations (trust records) before the save.

        The commit itself runs under the manifest's file lock: when the
        file's mtime moved past the state this snapshot descends from, a
        peer process committed in between — the snapshot is MERGED into
        a fresh read of the disk state instead of replacing it, closing
        the lost-update race where the slower of two writers silently
        dropped the faster one's index entries and trust records.  Index
        maintenance must never fail a query."""
        arc, spec = g["arc"], g["spec"]
        try:
            m = m if m is not None else self.manifest
            m.update(
                ck, embedding=g["embedding"],
                dims=(spec.W, spec.CH, spec.E),
                n_evals=arc.n_evals, budget_covered=arc.budget_covered,
                searched=arc.searched,
                digest=space_digest(g["space"]).to_json_dict())
            path = self.cache_dir / MANIFEST_NAME
            with file_lock(lock_path(path)):
                if self._manifest_stat() != self._manifest_mtime:
                    disk = ArchiveManifest.load(
                        path, policy=self.manifest_policy)
                    disk.merge(m)
                    disk.enforce(protect=(ck,))
                    m = disk
                    obs.inc("explore.manifest.merges")
                m.reap_evicted(self.cache_dir)   # opt-in archive-file GC
                m.save()
                self._manifest = m      # what was just saved IS current
                self._manifest_mtime = self._manifest_stat()
        except LockTimeout as e:        # wedged peer: the historic
            #                             unmerged save beats losing OUR
            #                             records too
            warnings.warn(f"manifest lock busy ({e}); saving unmerged")
            try:
                m.save()
                self._manifest = m
                self._manifest_mtime = self._manifest_stat()
            except Exception as e2:
                warnings.warn(f"explore manifest update failed for "
                              f"{ck}: {e2}")
        except Exception as e:
            warnings.warn(f"explore manifest update failed for {ck}: {e}")

    def _load_neighbor(self, nk: str) -> Optional[ParetoArchive]:
        """A neighbor archive for seed migration, through the bounded LRU
        side-cache.  Entries are keyed on the npz's mtime: when another
        service of a shared cache directory improves a neighbor's
        archive, the next transfer query re-reads the better front
        instead of serving the stale one (mirroring the manifest's
        staleness rule).  ``None`` for absent/unreadable files — a broken
        neighbor must never fail the query it was helping."""
        p = self._path(nk)
        try:
            mt = p.stat().st_mtime_ns
        except OSError:
            return None
        hit = self._neighbor_cache.get(nk)
        if hit is not None and hit[0] == mt:
            self._neighbor_cache.move_to_end(nk)
            return hit[1]
        try:
            arc = ParetoArchive.load(p)
        except Exception as e:
            warnings.warn(f"skipping unreadable neighbor archive {p}: {e}")
            return None
        # LRU side-cache, NOT self._archives: repeat queries skip the npz
        # re-read, but seed-only neighbors can't grow memory without bound
        self._neighbor_cache[nk] = (mt, arc)
        self._neighbor_cache.move_to_end(nk)
        while len(self._neighbor_cache) > self._neighbor_cache_cap:
            self._neighbor_cache.popitem(last=False)
        return arc

    def _transfer_plan(self, ck: str, embedding, cap: int
                       ) -> Tuple[ArchiveManifest,
                                  List[Tuple[str, float]], Dict[str, int]]:
        """The *prediction* half of transfer seeding, evaluation-free: one
        manifest snapshot, the trust-reweighted ``transfer_k`` nearest
        cached neighbors of ``embedding`` (excluding ``ck`` itself), and
        each neighbor's seed quota out of ``cap``.  ``_transfer_seeds``
        executes exactly this plan; ``repro.api.Session.plan`` reports it
        to the caller before any compute is spent."""
        m = self.manifest               # ONE snapshot for the whole
        #                                 lookup: a concurrent service's
        #                                 eviction must not yank entries
        #                                 between nearest() and indexing
        trust = m.trust_model(dim=int(np.asarray(embedding).size))
        neigh = m.nearest(embedding, k=self.transfer_k,
                          exclude=(ck,), trust=trust)
        cap = max(int(cap), 1)
        if trust is not None and neigh:
            w = [1.0 + max(trust.predict(embedding_delta(
                embedding, m.entries[nk]["embedding"])), 0.0)
                for nk, _ in neigh]
            quotas = {nk: max(1, int(round(cap * wi / sum(w))))
                      for (nk, _), wi in zip(neigh, w)}
        else:
            quota = max(1, cap // max(self.transfer_k, 1))
            quotas = {nk: quota for nk, _ in neigh}
        return m, neigh, quotas

    def _transfer_seeds(self, ck: str, space: DesignSpace, embedding,
                        key, arc: Optional[ParetoArchive] = None,
                        cap: Optional[int] = None
                        ) -> Tuple[Optional[Dict], Tuple[str, ...]]:
        """Seed designs for a cold or resumed query: the migrated (and
        repaired) fronts of the ``transfer_k`` best cached neighbors,
        capped at ``cap`` designs.  Neighbor ranking and per-neighbor seed
        quotas are *trust-calibrated* once the manifest's outcome table
        supports a model: distances are reweighted by predicted lift and
        higher-trust neighbors earn proportionally more of the cap.
        Migrated seeds that duplicate the destination archive's own front
        (``portable_signature`` match) are dropped — resuming a problem
        with its own designs injects nothing.  With no usable neighbor, a
        COLD start gets one repaired ``balanced_init`` design (never worse
        off for having asked to transfer); a resumed archive already has
        its front head and gets no filler seed."""
        dst = space_digest(space)
        cap = max(self.nsga.pop, 1) if cap is None else max(int(cap), 1)
        n_front = len(arc) if arc is not None else 0
        m, neigh, quotas = self._transfer_plan(ck, embedding, cap)
        taken: set = set()
        if n_front and neigh:           # hashing the whole front is only
            #                             worth it when there IS a
            #                             neighbor to dedup against
            fr_designs, _ = arc.front()
            for i in range(n_front):
                d = {k2: v[i] for k2, v in fr_designs.items()}
                taken.add(portable_signature(d, dst))
        seeds: List[Dict] = []
        srcs: List[str] = []
        for nk, _dist in neigh:
            ent = m.entries[nk]
            if ent.get("digest") is None:
                continue
            n_arc = self._archives.get(nk)
            if n_arc is None:
                n_arc = self._load_neighbor(nk)
            if n_arc is None:
                continue
            migrated: List[Dict] = []
            try:
                designs, objs = n_arc.front()
                for i in range(len(objs)):
                    if len(migrated) >= quotas.get(nk, 1):
                        break
                    d = {k2: v[i] for k2, v in designs.items()}
                    md = migrate(d, ent["digest"], dst)
                    sig = portable_signature(md, dst)
                    if sig in taken:    # already on the destination front
                        obs.inc("explore.transfer.seeds_deduped")
                        continue        # (or offered by a closer neighbor)
                    taken.add(sig)
                    migrated.append(md)
            except Exception as e:      # a broken neighbor must never
                #                         fail the query it was helping;
                #                         designs migrated before the
                #                         failure are still good seeds
                warnings.warn(f"transfer from {nk} failed: {e}")
            if migrated:                # seeds and telemetry stay
                #                         consistent: nk is credited iff
                #                         its designs were injected
                obs.inc("explore.transfer.seeds_injected", len(migrated))
                seeds.extend(migrated)
                srcs.append(nk)
            if len(seeds) >= cap:
                break
        if not seeds:
            if n_front:
                return None, ()
            bi = jax.tree.map(np.asarray, balanced_init(key, space))
            seeds = [repair(bi, dst)]
        seeds = seeds[:cap]
        return ({k2: np.stack([s[k2] for s in seeds])
                 for k2 in seeds[0]}, tuple(srcs))

    def _reallocate(self, groups: Dict[str, Dict], key,
                    on_segment=None, seq=None,
                    control: Optional[RunControl] = None) -> None:
        """Phase 2: spend the ledger on this batch's under-explored
        archives — groups that ran to budget exhaustion WITHOUT plateauing
        (their front was still improving), lowest eval-count first.  Spent
        credit is drained FIFO from the ledger; credit no group can use
        stays banked for future batches.  Interrupted groups take no
        top-up (their own budget is still owed) and a stopped control
        token ends the phase at the next boundary."""
        pool = sum(self.ledger.values())
        takers = sorted(
            ((ck, g) for ck, g in groups.items()
             if not g["warm"] and g["n_run"] and not g["plateaued"]
             and not g["interrupted"]),
            key=lambda item: item[1]["arc"].n_evals)
        for i, (ck, g) in enumerate(takers):
            if control is not None and control.stopped:
                break
            if pool < 8:                 # below the smallest runnable pop
                break
            arc = g["arc"]
            t0 = time.perf_counter()
            # a top-up is FRESH budget: the plateau streak the group's own
            # refinement accumulated must not carry into the realloc
            # segments, or a topped-up archive gets declared plateaued one
            # segment after receiving credit it never got to spend
            g["plateau"].reset()
            # quantize_down caps the spend at the available credit — the
            # ledger must never be overdrawn by pow2 rounding
            with obs.span("explore.reallocate", key=ck, pool=pool) as sp:
                n_run, trace, plateaued, _, interrupted, _ = self._refine(
                    arc, g["spec"], g["space"], g["union"], pool,
                    jax.random.fold_in(key, i), quantize_down=True,
                    on_segment=self._segment_cb(on_segment, ck, "realloc",
                                                seq=seq),
                    plateau=g["plateau"], control=control)
                sp.set(n_run=n_run)
            obs.inc("explore.evals.realloc", n_run)
            pool -= n_run                # only what was actually spent
            self._drain_ledger(n_run)
            g["elapsed"] += time.perf_counter() - t0
            g["n_run"] += n_run
            g["realloc"] += n_run
            g["plateaued"] = plateaued
            g["interrupted"] = g["interrupted"] or interrupted
            if trace is not None:
                g["trace"] = (g["trace"].extend(trace)
                              if g["trace"] is not None else trace)
            if g["trace"] is not None:
                arc.trace_summary = g["trace"].summary()
            self.save(ck)
            self._update_manifest(ck, g)

    def _drain_ledger(self, spent: int) -> None:
        for ck in list(self.ledger):
            if spent <= 0:
                break
            take = min(self.ledger[ck], spent)
            self.ledger[ck] -= take
            spent -= take
            if self.ledger[ck] <= 0:
                del self.ledger[ck]

    def _project_group(self, ck: str, g: Dict) -> List[ExploreResult]:
        """Phase 3: project every query's front out of the group archive.
        ``elapsed`` covers the group's own refinement (plus any
        reallocation top-up it received), not the whole batch."""
        with obs.span("explore.project", key=ck):
            designs, metrics = g["arc"].front()
            elapsed = g["elapsed"]
            results = []
            for q in g["queries"]:
                idx = [METRIC_KEYS.index(o) for o in q.objectives]
                cols = metrics[:, idx]
                keep = pareto_front(cols) if len(cols) else []
                results.append(ExploreResult(
                    objectives=q.objectives,
                    front_objs=cols[keep],
                    front_metrics=metrics[keep],
                    front_designs=[{k: v[i] for k, v in designs.items()}
                                   for i in keep],
                    from_cache=g["warm"], n_evals_run=g["n_run"],
                    elapsed_s=elapsed, cache_key=ck,
                    trace=g["trace"], plateaued=g["plateaued"],
                    n_evals_banked=g["banked"],
                    n_evals_realloc=g["realloc"],
                    transferred_from=g["transferred_from"],
                    n_transfer_seeds=g["n_seeds"],
                    interrupted=g["interrupted"],
                    surrogate_used=g["sur_used"],
                    surrogate_hits=g["sur_hits"],
                    surrogate_fallbacks=g["sur_fallbacks"]))
        return results

    def _effective_pop(self, budget: int, quantize_down: bool = False
                       ) -> int:
        """The population width ``_refine`` will actually run for one
        budget: sub-``nsga.pop`` budgets shrink the population (pow2 ceil
        normally, pow2 floor when the budget is a hard cap; floored at
        8).  Factored out so the seeding path caps transfer seeds at what
        the run can really inject."""
        return quantize.effective_pop(budget, self.nsga.pop, quantize_down)

    def _mesh_for(self, pop: int):
        """The service mesh, when a ``pop``-wide population can actually
        shard over it (every island at least 2 designs); ``None`` (the
        single-device scan) otherwise — small quantized budgets must not
        fail, they just don't scale."""
        if self.mesh is None:
            return None
        n = int(self.mesh.shape.get(ISLAND_AXIS, 1))
        return self.mesh if (pop % n == 0 and pop // n >= 2) else None

    def _ckpt_signature(self, objectives: Tuple[str, ...], budget: int,
                        pop: int, generations: int, chunk: int, key,
                        seeds: Optional[Dict],
                        gate_digest: Optional[str] = None) -> str:
        """Identity of one deterministic refinement: everything that
        fixes the segment-by-segment PRNG/compute chain.  A checkpoint
        written under a different signature answers a DIFFERENT run and
        is ignored — resuming must never splice two unequal runs."""
        h = hashlib.sha256()
        mesh = self._mesh_for(pop)      # island count changes the PRNG /
        #                                 migration chain: a sharded run's
        #                                 checkpoint answers a different
        #                                 numeric stream than an unsharded
        islands = int(mesh.shape[ISLAND_AXIS]) if mesh is not None else 1
        h.update(repr((tuple(objectives), int(budget), int(pop),
                       int(generations), int(chunk), int(self.capacity),
                       repr(self.nsga), islands,
                       tech_key(self.tech or DEFAULT_TECH),
                       gate_digest)).encode())
        #             gate_digest: a surrogate-gated run's numeric stream
        #             depends on the fitted model — a checkpoint written
        #             under a different (or no) surrogate must not splice
        h.update(np.asarray(key).tobytes())
        if seeds is not None:
            for k in sorted(seeds):
                h.update(k.encode())
                h.update(np.asarray(seeds[k]).tobytes())
        return h.hexdigest()[:16]

    @staticmethod
    def _save_ckpt(path, sig: str, s_next: int, spent_g: int,
                   spent_e: int, fell_back: bool, arc: ParetoArchive,
                   filler: Dict, trace: ConvergenceTrace,
                   st: PlateauState) -> None:
        """One atomic npz holding a CONSISTENT mid-run snapshot: the
        archive state after segment ``s_next - 1``'s insert, the evolving
        population that segment produced, the accumulated trace, and the
        plateau detector's memory.  Written via ``atomic_savez``, so a
        kill mid-checkpoint leaves the previous segment's snapshot — the
        resume replays at most one extra segment, never sees a torn one.
        Checkpoint failure is a warning: losing resumability must not
        fail the run being protected."""
        try:
            meta = dict(
                sig=sig, s_next=int(s_next), spent_g=int(spent_g),
                spent_e=int(spent_e),   # exact evaluations (differs from
                #                         spent_g * pop under gating)
                fell_back=bool(fell_back),  # disagreement abandoned the
                #                         surrogate: a resume must stay
                #                         exact, not re-enable the gate
                streak=int(st.streak),
                last_hv=([float(v) for v in st.last_hv]
                         if st.last_hv is not None else None),
                arc=dict(n_evals=arc.n_evals,
                         budget_covered=arc.budget_covered,
                         searched=list(arc.searched)),
                trace=dict(objectives=list(trace.objectives),
                           pairs=[list(p) for p in trace.pairs],
                           has_archive_hv=trace.archive_hv is not None,
                           has_hv_gen=trace.hv_gen is not None))
            arrays = dict(
                objs=arc.objs, valid=arc.valid,
                t_front_size=np.asarray(trace.front_size),
                t_hypervolume=np.asarray(trace.hypervolume),
                t_best=np.asarray(trace.best),
                t_feasible_frac=np.asarray(trace.feasible_frac),
                t_n_evals=np.asarray(trace.n_evals))
            if trace.archive_hv is not None:
                arrays["t_archive_hv"] = np.asarray(trace.archive_hv)
            if trace.hv_gen is not None:
                arrays["t_hv_gen"] = np.asarray(trace.hv_gen)
            arrays.update({f"d_{k}": np.asarray(v)
                           for k, v in arc.designs.items()})
            with obs.span("explore.fetch"):
                arrays.update({f"f_{k}": np.asarray(v)
                               for k, v in filler.items()})
            with obs.span("explore.checkpoint", segment=int(s_next) - 1):
                atomic_savez(path, __meta=np.frombuffer(
                    json.dumps(meta).encode(), dtype=np.uint8), **arrays)
        except Exception as e:
            warnings.warn(f"resume checkpoint write failed ({path}): {e}")

    @staticmethod
    def _load_ckpt(path, sig: str, arc: ParetoArchive, st: PlateauState
                   ) -> Optional[Tuple[int, int, Optional[int], bool,
                                       Dict, ConvergenceTrace]]:
        """Restore a mid-run snapshot into ``arc``/``st`` if ``path``
        holds a checkpoint of THIS run (signature match, compatible
        shapes).  Returns ``(s_next, spent_g, spent_e, fell_back,
        filler, trace)`` — ``spent_e`` is ``None`` for pre-surrogate
        checkpoints (the caller derives ``spent_g * pop``) — or ``None``
        (no/foreign/damaged checkpoint → start from scratch, never
        fatal)."""
        path = Path(path)
        if not path.exists():
            return None
        try:
            with np.load(path) as z:
                meta = json.loads(bytes(z["__meta"]).decode())
                if meta["sig"] != sig:
                    return None
                objs, valid = z["objs"], z["valid"]
                designs = {k[2:]: z[k].copy() for k in z.files
                           if k.startswith("d_")}
                if (objs.shape != arc.objs.shape
                        or set(designs) != set(arc.designs)):
                    return None
                filler = {k[2:]: z[k].copy() for k in z.files
                          if k.startswith("f_")}
                tm = meta["trace"]
                trace = ConvergenceTrace(
                    objectives=tuple(tm["objectives"]),
                    pairs=tuple(tuple(p) for p in tm["pairs"]),
                    front_size=z["t_front_size"].copy(),
                    hypervolume=z["t_hypervolume"].copy(),
                    best=z["t_best"].copy(),
                    feasible_frac=z["t_feasible_frac"].copy(),
                    n_evals=z["t_n_evals"].copy(),
                    archive_hv=(z["t_archive_hv"].copy()
                                if tm["has_archive_hv"] else None),
                    hv_gen=(z["t_hv_gen"].copy()
                            if tm["has_hv_gen"] else None))
            arc.objs = objs.copy()
            arc.valid = valid.copy()
            arc.designs = designs
            arc.n_evals = int(meta["arc"]["n_evals"])
            arc.budget_covered = int(meta["arc"]["budget_covered"])
            arc.searched = tuple(meta["arc"]["searched"])
            st.streak = int(meta["streak"])
            st.last_hv = (np.asarray(meta["last_hv"], np.float64)
                          if meta["last_hv"] is not None else None)
            obs.inc("explore.resume.restored")
            spent_e = meta.get("spent_e")
            return (int(meta["s_next"]), int(meta["spent_g"]),
                    int(spent_e) if spent_e is not None else None,
                    bool(meta.get("fell_back", False)), filler, trace)
        except Exception as e:
            warnings.warn(f"discarding unreadable resume checkpoint "
                          f"{path}: {e}")
            return None

    def _refine(self, arc: ParetoArchive, spec: SystemSpec,
                space: DesignSpace, objectives: Tuple[str, ...],
                budget: int, key, quantize_down: bool = False,
                seeds: Optional[Dict] = None, on_segment=None,
                plateau: Optional[PlateauState] = None,
                control: Optional[RunControl] = None,
                checkpoint=None, gate: Optional[SurrogateGate] = None
                ) -> Tuple[int, Optional[ConvergenceTrace], bool, int,
                           bool, Dict[str, int]]:
        """Spend up to ~``budget`` evaluations improving the archive:
        warm-start the population from the cached front, evolve in scan
        segments, re-insert every evaluation, stop early on plateau.

        The query budget — not ``self.nsga.generations`` — fixes the scan
        length here; the population (for sub-``nsga.pop`` budgets), the
        total generation count and the per-segment chunk are all quantized
        to powers of two, so a long-lived service compiles
        O(log^2(max_budget)) scan variants instead of one per distinct
        budget; the service's ``nsga`` config supplies the population
        ceiling and variation knobs.

        Returns ``(n_run, trace, plateaued, banked, interrupted,
        sur_stats)``: evaluations spent by THIS attempt (a resumed run
        reports only its residual spend; the archive's counters carry
        the total), the
        concatenated per-generation ``ConvergenceTrace`` spanning every
        attempt (with one archive-projected hypervolume row per
        segment; ``None`` if stopped before any segment ran), whether
        the hypervolume plateau stopped the run early, the evaluations
        of the *requested* budget that early stop left unspent (never
        more than the caller offered, however the scan was quantized),
        and whether a ``control`` stop ended the run before its budget.

        ``plateau`` (a ``PlateauState``) carries the streak detector's
        memory across attempts of one group; ``checkpoint`` (a path)
        turns on per-segment crash checkpointing and resume-on-entry;
        ``control`` is polled at each segment boundary.

        ``quantize_down`` floors instead of ceils the pow2 generation
        quantization, guaranteeing the run never spends more than
        ``budget`` — used when spending ledger credit, which must not be
        exceeded.

        ``seeds`` (a stacked numpy design pytree) is injected into segment
        0's population right behind the archive-front head — the transfer
        warm-start path.  Later segments carry the evolving population, so
        a bad seed is selected out after one generation.

        ``gate`` (a ``SurrogateGate``) switches each segment to the
        surrogate-gated scan: only ``cfg.n_exact(pop)`` of every
        generation's candidates get exact evaluations (the rest are
        skipped on the surrogate's ranking and counted as hits), and a
        segment whose mean ensemble disagreement exceeds
        ``gate.cfg.fallback_tau`` abandons the surrogate for the rest of
        the run.  ``gate=None`` is byte-for-byte the historical exact
        path.  The final ``sur_stats`` dict reports ``used`` / ``hits``
        / ``fallbacks``.
        """
        policy = self.policy
        sched = quantize.schedule(budget, self.nsga.pop,
                                  policy.chunk_generations, quantize_down)
        pop, generations = sched.pop, sched.generations
        chunk, n_seg = sched.chunk, sched.n_seg
        cfg = dataclasses.replace(self.nsga, pop=pop, generations=chunk)
        mesh = self._mesh_for(pop)
        run = make_nsga(spec, space, objectives, cfg, tech=self.tech,
                        mesh=mesh)
        sur_stats = dict(used=False, hits=0, fallbacks=0)
        run_g, sur, n_exact = None, None, pop
        if gate is not None:
            n_exact = gate.cfg.n_exact(pop)
            if n_exact < pop and mesh is None:
                # gating is mutually exclusive with island sharding (the
                # gated scan is single-device); a meshed service quietly
                # runs exact rather than fail the query
                run_g = make_nsga_gated(spec, space, objectives, cfg,
                                        tech=self.tech, n_exact=n_exact,
                                        beta=gate.cfg.beta,
                                        tau=gate.cfg.tau)
                sur = gate.model.scan_arrays(gate.embedding)
            else:
                n_exact = pop
        # archive-projected hypervolume pairs, in METRIC_KEYS column space
        hv_pairs = [(METRIC_KEYS.index(objectives[i]),
                     METRIC_KEYS.index(objectives[j]))
                    for i, j in objective_pairs(len(objectives))]
        k_init, k_run = jax.random.split(key)

        def seed(filler, extra=None):
            return _seed_population(arc, pop, filler, extra)

        with obs.span("explore.init_population"):
            filler = sample_designs(k_init, space, pop)
        st = plateau if plateau is not None else PlateauState()
        trace = None
        plateaued, interrupted, spent_g = False, False, 0
        spent_e = 0                     # exact evaluations this attempt
        s0, spent0, sig = 0, 0, None    # spent0: chunks paid for by a
        #                                 killed earlier attempt
        spent0_e = 0
        if checkpoint is not None:
            sig = self._ckpt_signature(
                objectives, budget, pop, generations, chunk, key, seeds,
                gate_digest=(gate.model.digest()
                             if run_g is not None else None))
            rest = self._load_ckpt(checkpoint, sig, arc, st)
            if rest is not None:
                s0, spent0, r_e, fell_back0, filler, trace = rest
                spent0_e = r_e if r_e is not None else spent0 * pop
                if fell_back0 and run_g is not None:
                    run_g = None        # the dead attempt had already
                    sur_stats["used"] = True    # abandoned the surrogate
                    sur_stats["fallbacks"] += 1
        for s in range(s0, n_seg):
            if control is not None and control.stopped:
                interrupted = True      # the checkpoint (if any) stays:
                break                   # a resume picks up right here
            t_seg = time.perf_counter()
            # first call of this scan variant pays XLA lowering — attribute
            # it separately so plan-vs-actual tables and the segment-time
            # histogram aren't polluted by one-off compiles
            active = run_g if run_g is not None else run
            compiled = not active.compile_state["executed"]
            with obs.span("explore.seed"):
                pop0 = seed(filler, seeds if s == 0 else None)
            with obs.span("explore.dispatch"):
                if run_g is not None:
                    pop_s, _raw, _sel, ev_designs, ev_raw, ev_feas, tr = \
                        run_g(jax.random.fold_in(k_run, s), pop0, sur)
                    per_gen = n_exact   # only the gate's exact slots cost
                else:
                    pop_s, _raw, _sel, ev_designs, ev_raw, ev_feas, tr = \
                        run(jax.random.fold_in(k_run, s), pop0)
                    per_gen = pop
            # archive EVERY evaluation of the segment, not just the
            # survivors — masked to feasible designs so the archive (and
            # every front served from it) never carries a
            # constraint-violating point; ``insert`` flattens the
            # (generations, pop) axes
            arc.insert(ev_designs, ev_raw, mask=ev_feas, count_evals=False)
            arc.n_evals += per_gen * chunk  # one vmapped evaluation per
            spent_g += chunk                # step (gated: exact slots)
            spent_e += per_gen * chunk
            filler = pop_s
            with obs.span("explore.fetch"):
                seg_trace = ConvergenceTrace.from_scan(objectives, tr,
                                                       per_gen)
            if run_g is not None:
                skipped = (pop - n_exact) * chunk
                sur_stats["used"] = True
                sur_stats["hits"] += skipped
                obs.inc("explore.surrogate.hits", skipped)
                obs.inc("explore.surrogate.forced_exact",
                        int(np.sum(np.asarray(tr["forced_exact"]))))
                dis = float(np.mean(np.asarray(tr["disagreement"])))
                if dis > gate.cfg.fallback_tau:
                    # the ensemble is out of its depth on this region of
                    # the design space — exact for the rest of the run
                    run_g = None
                    sur_stats["fallbacks"] += 1
                    obs.inc("explore.surrogate.fallbacks")
            hv_now = np.asarray([arc.projected_hypervolume(p)
                                 for p in hv_pairs])
            seg_trace.archive_hv = hv_now[None, :]
            trace = seg_trace if trace is None else trace.extend(seg_trace)
            # the trace/hypervolume work above runs on the host, so the
            # async dispatch has drained by here: dt is honest wall-clock
            dt = time.perf_counter() - t_seg
            obs.inc("explore.segments")
            obs.observe("explore.segment_compile_s" if compiled
                        else "explore.segment_s", dt)
            if on_segment is not None:     # stream the segment boundary:
                on_segment(s, seg_trace, dt, compiled)     # the
                #                            incremental trace slice
            # ---- plateau check on the archive-projected hypervolume ----
            # an empty archive means NOTHING has been found yet — that is
            # stagnation, not convergence, and must never feed the streak
            # (count=False records the vector without judging it)
            if policy.adaptive and hv_pairs:
                streak = st.observe(hv_now, policy.plateau_rel,
                                    count=bool(len(arc)))
                if streak >= policy.patience and s + 1 < n_seg:
                    plateaued = True
                    obs.inc("explore.plateau_stops")
                    break
            if checkpoint is not None:  # AFTER the plateau observation:
                #                         the snapshot must carry this
                #                         segment's hv as the comparison
                #                         base, or a resume re-judges the
                #                         seam against a stale vector
                self._save_ckpt(checkpoint, sig, s + 1, spent0 + spent_g,
                                spent0_e + spent_e,
                                gate is not None and run_g is None,
                                arc, filler, trace, st)
        n_run = spent_e
        # the ledger may only be fed from budget the CALLER offered and
        # the run — ALL attempts of it — left unspent: the pow2
        # quantization headroom above the requested budget is not real
        # credit, and a resumed attempt's own spend understates the
        # total.  Only a PLATEAU banks — a gated run that merely spent
        # less than its budget reports the savings as surrogate hits,
        # not as ledger credit (reallocation would respend them and
        # erase the saving)
        banked = max(0, budget - (spent0_e + spent_e)) \
            if plateaued else 0
        if checkpoint is not None and not interrupted:
            Path(checkpoint).unlink(missing_ok=True)    # run complete:
            #                                 nothing left to resume
        return n_run, trace, plateaued, banked, interrupted, sur_stats


def _seed_population(arc: ParetoArchive, pop: int, filler: Dict,
                     extra: Optional[Dict] = None) -> Dict:
    """Population for the next segment: archive front head (the all-time
    best designs), then any transfer ``extra`` seeds, ``filler`` tail
    (fresh random samples for segment 0, then the carried evolving
    population).  Transfer seeds reserve their slots FIRST (the caller
    caps them at half the population when the archive is non-empty, see
    ``_group_seeds``), so a warm refinement's large front head cannot
    crowd out the migrated neighbors it asked for.  Shared by the
    sequential ``_refine`` loop and the megabatched lanes — one seeding
    rule, wherever a population is assembled."""
    fr_designs, _ = arc.front()
    n_ext = 0
    if extra is not None:
        # the CALLER caps the seed count (at most half the effective
        # population when the archive is non-empty) — re-deriving the cap
        # here would just be a second copy of that logic waiting to drift
        n_ext = min(int(next(iter(extra.values())).shape[0]), pop)
    n_warm = min(len(arc), pop - n_ext)
    if n_warm + n_ext == 0:
        return filler

    def leaf(k, v):
        parts = []
        if n_warm:
            parts.append(jnp.asarray(fr_designs[k][:n_warm]))
        if n_ext:
            parts.append(jnp.asarray(extra[k][:n_ext]))
        parts.append(jnp.asarray(v)[n_warm + n_ext:])
        return jnp.concatenate(parts)

    return {k: leaf(k, v) for k, v in filler.items()}


# ---------------------------------------------------------------------------
# module-level convenience: a default singleton service
# ---------------------------------------------------------------------------
_DEFAULT: Optional[ExplorationService] = None


def default_service(**kwargs) -> ExplorationService:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = ExplorationService(**kwargs)
    elif kwargs:
        raise RuntimeError(
            "the default exploration service is already initialized; "
            "construct ExplorationService(...) directly for a custom "
            "configuration")
    return _DEFAULT


def explore(graph: WorkloadGraph,
            objectives: Sequence[str] = DEFAULT_OBJECTIVES,
            budget: int = 2048, ch_max: int = 4,
            space_kwargs: Optional[Dict] = None,
            transfer: bool = False,
            service: Optional[ExplorationService] = None,
            key=None) -> ExploreResult:
    """One-call front query against the process-wide default service.

    DEPRECATED — delegates to the ``ExplorationService.explore`` shim
    (one ``DeprecationWarning``); use ``repro.api.submit`` instead."""
    svc = service or default_service()
    return svc.explore(graph, objectives, budget, ch_max, space_kwargs,
                       transfer=transfer, key=key)
