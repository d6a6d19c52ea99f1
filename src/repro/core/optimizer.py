"""Monad's nested optimization engine (paper Sec. IV-C, Fig. 6b).

Outer loop: **Bayesian optimization** over the low-dimensional fields
(shape, spatial, packaging, network family) — Gaussian-process surrogate
(Matern-5/2) + *probability of improvement* acquisition, exactly the paper's
choices.  Each BO sample is *evaluated by running a simulated-annealing
engine* over the high-dimensional fields (order, tiling, pipe, placement)
with the low-dim fields frozen.

The SA inner loop is a single ``lax.scan`` jitted over vmapped chains — the
whole nested engine evaluates thousands of design points per second on one
host and scales to accelerators unchanged (the TPU-native re-think of the
paper's engine; see DESIGN.md Sec. 2).
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .encoding import (ALL_FIELDS, ARCH_FIELDS, BO_FIELDS, INTEG_FIELDS,
                       SA_FIELDS, DesignSpace, feasibility_penalty, mutate,
                       random_design, repair)
from .evaluate import SystemSpec, evaluate_system
from .network import N_FAMILIES

F = jnp.float32

# the objective axes every engine (scalarized BO x SA, NSGA-II fronts,
# Pareto archives) agrees on, in canonical order
METRIC_KEYS = ("latency_ns", "energy_pj", "cost_usd", "area_mm2")

# objective weights over log-metrics: (latency, energy, cost, area)
OBJ_EDP = (1.0, 1.0, 0.0, 0.0)
OBJ_LATENCY = (1.0, 0.0, 0.0, 0.0)
OBJ_ENERGY = (0.0, 1.0, 0.0, 0.0)
OBJ_COST_EDP = (1.0, 1.0, 1.0, 0.0)     # cost-effectiveness (Fig. 9/10)


def metric_stack(metrics: Dict) -> jnp.ndarray:
    """(4,) raw metric vector in ``METRIC_KEYS`` order (archive rows)."""
    return jnp.stack([jnp.asarray(metrics[k], F) for k in METRIC_KEYS])


def log_metric_stack(metrics: Dict) -> jnp.ndarray:
    """(4,) clipped log-metric vector — the shared evaluation path under
    both the scalarized engines here and ``repro.explore.nsga``."""
    return jnp.stack([jnp.log(jnp.maximum(metrics[k], 1e-3))
                      for k in METRIC_KEYS])


def penalty_log(space: DesignSpace, design: Dict, metrics: Dict):
    """log feasibility penalty (shared by scalarized + front explorers)."""
    return jnp.log(feasibility_penalty(space, design, metrics))


def objective_from_metrics(space: DesignSpace, design: Dict, metrics: Dict,
                           weights) -> jnp.ndarray:
    """sum_i w_i * log(metric_i) + log(feasibility penalty); minimize."""
    w = jnp.asarray(weights, F)
    return (jnp.sum(w * log_metric_stack(metrics))
            + 8.0 * penalty_log(space, design, metrics))


# ---------------------------------------------------------------------------
# simulated annealing (jit'd scan, vmapped chains)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SAConfig:
    steps: int = 400
    chains: int = 8
    t0: float = 1.0
    t1: float = 0.01


# compiled SA runners, keyed on everything that shapes the compiled code;
# all workload graphs with the same padded dims share one compilation.
_SA_CACHE: dict = {}

# compiled stacked-designs -> metrics evaluators, shared by every spec with
# equal padded dims (used by the archive-recording path below)
_BATCH_EVAL_CACHE: dict = {}


def _batch_metrics(spec: SystemSpec, tech):
    from .evaluate import evaluate_arrays
    dims = (spec.W, spec.CH, spec.E)
    key = (dims, tech)
    if key not in _BATCH_EVAL_CACHE:
        _BATCH_EVAL_CACHE[key] = jax.jit(
            lambda ds, arr: jax.vmap(
                lambda d: evaluate_arrays(arr, d, dims, tech))(ds))
    f = _BATCH_EVAL_CACHE[key]
    arr = {k: jnp.asarray(v) for k, v in spec.arrays.items()}
    return lambda ds: f(ds, arr)


def make_sa(spec: SystemSpec, space: DesignSpace,
            fields: Tuple[str, ...] = SA_FIELDS,
            sa: SAConfig = SAConfig(), tech=None):
    """Build a jitted SA runner: (key, init_design, weights) -> (best design,
    best objective).  ``fields`` = the mutable subset.

    The workload arrays are passed as *traced arguments* so the compiled SA
    is shared by every spec with the same padded dims — the jit cache is
    keyed on (dims, fields, chains, steps, objective shape) only.
    """
    from .constants import DEFAULT_TECH
    tech = tech or DEFAULT_TECH
    from .evaluate import evaluate_arrays
    dims = (spec.W, spec.CH, spec.E)

    cache_key = (dims, tuple(fields), sa, tech, space.max_shape,
                 space.max_logB, space.max_total_pes, space.fixed_packaging,
                 space.fixed_family, space.allow_pipeline)
    if cache_key in _SA_CACHE:
        jitted = _SA_CACHE[cache_key]

        def runner(key, d0, weights, arrays=None):
            arr = {k: jnp.asarray(v)
                   for k, v in (arrays or spec.arrays).items()}
            return jitted(key, d0, weights, arr)
        return runner

    def obj(design, weights, arr):
        m = evaluate_arrays(arr, design, dims, tech,
                            node_bound=space.node_bound)
        return objective_from_metrics(space, design, m, weights)

    def chain(key, d0, weights, arr):
        o0 = obj(d0, weights, arr)
        nl = jnp.sum(arr["loopmask"], axis=1).astype(jnp.int32)

        def step(carry, xs):
            d_cur, o_cur, d_best, o_best = carry
            k, t = xs
            k1, k2 = jax.random.split(k)
            d_new = mutate(k1, d_cur, space, fields,
                           nl=nl, bounds=arr["bounds"])
            o_new = obj(d_new, weights, arr)
            accept = (o_new < o_cur) | (
                jax.random.uniform(k2) < jnp.exp((o_cur - o_new) / t))
            d_cur = jax.tree.map(
                lambda a, b: jnp.where(accept, b, a), d_cur, d_new)
            o_cur = jnp.where(accept, o_new, o_cur)
            better = o_new < o_best
            d_best = jax.tree.map(
                lambda a, b: jnp.where(better, b, a), d_best, d_new)
            o_best = jnp.where(better, o_new, o_best)
            return (d_cur, o_cur, d_best, o_best), None

        keys = jax.random.split(key, sa.steps)
        temps = jnp.exp(jnp.linspace(math.log(sa.t0), math.log(sa.t1),
                                     sa.steps)).astype(F)
        (_, _, d_best, o_best), _ = jax.lax.scan(
            step, (d0, o0, d0, o0), (keys, temps))
        return d_best, o_best

    def run(key, d0, weights, arr):
        keys = jax.random.split(key, sa.chains)
        d0s = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (sa.chains,) + x.shape), d0)
        db, ob = jax.vmap(chain, in_axes=(0, 0, None, None))(
            keys, d0s, weights, arr)
        i = jnp.argmin(ob)
        return jax.tree.map(lambda x: x[i], db), ob[i]

    jitted = jax.jit(run)
    _SA_CACHE[cache_key] = jitted

    def runner(key, d0, weights, arrays=None):
        arr = {k: jnp.asarray(v)
               for k, v in (arrays or spec.arrays).items()}
        return jitted(key, d0, weights, arr)

    return runner


# ---------------------------------------------------------------------------
# Gaussian process + probability of improvement (from scratch, in jnp; the
# Pallas Matern kernel in repro.kernels.gp_cov is not on this path)
# ---------------------------------------------------------------------------
def matern52(X1, X2, lengthscale):
    d2 = jnp.sum((X1[:, None, :] - X2[None, :, :]) ** 2, -1)
    r = jnp.sqrt(jnp.maximum(d2, 1e-12)) / lengthscale
    s5 = math.sqrt(5.0)
    return (1.0 + s5 * r + 5.0 * r * r / 3.0) * jnp.exp(-s5 * r)


def gp_posterior(X, y, Xq, lengthscale=0.3, noise=1e-4, cov_fn=None):
    """GP posterior mean/std at query points (standardized y)."""
    cov = cov_fn or matern52
    mu0, sd = jnp.mean(y), jnp.maximum(jnp.std(y), 1e-9)
    yn = (y - mu0) / sd
    K = cov(X, X, lengthscale) + noise * jnp.eye(X.shape[0])
    L = jnp.linalg.cholesky(K)
    a = jax.scipy.linalg.cho_solve((L, True), yn)
    Kq = cov(Xq, X, lengthscale)
    mu = Kq @ a
    v = jax.scipy.linalg.solve_triangular(L, Kq.T, lower=True)
    var = jnp.clip(1.0 - jnp.sum(v * v, axis=0), 1e-10, None)
    return mu * sd + mu0, jnp.sqrt(var) * sd


def prob_improvement(mu, sigma, best, xi=0.01):
    z = (best - xi - mu) / jnp.maximum(sigma, 1e-9)
    return jax.scipy.stats.norm.cdf(z)


# ---------------------------------------------------------------------------
# low-dim field <-> unit-cube vector codec for the BO surrogate
# ---------------------------------------------------------------------------
def _bo_dims(space: DesignSpace, fields) -> int:
    W = space.W
    n = 0
    for f in fields:
        if f == "shape":
            n += 6 * W
        elif f == "spatial":
            n += 6 * W
        elif f == "packaging":
            n += 1
        elif f == "family":
            n += 1
    return n


def encode_bo(space: DesignSpace, design: Dict, fields) -> np.ndarray:
    out = []
    mx = np.asarray(space.max_shape, np.float64)
    nl = np.maximum(space.n_loops.astype(np.float64), 1)
    for f in fields:
        if f == "shape":
            out.append((np.asarray(design["shape"]) - 1) / np.maximum(mx - 1, 1))
        elif f == "spatial":
            out.append(np.asarray(design["spatial"]) / nl[:, None])
        elif f == "packaging":
            out.append(np.asarray(design["packaging"]).reshape(1) / 2.0)
        elif f == "family":
            out.append(np.asarray(design["family"]).reshape(1)
                       / (N_FAMILIES - 1))
    return np.concatenate([np.ravel(o) for o in out]).astype(np.float64)


def decode_bo(space: DesignSpace, z: np.ndarray, base: Dict, fields) -> Dict:
    d = {k: np.asarray(v).copy() for k, v in base.items()}
    W = space.W
    mx = np.asarray(space.max_shape, np.float64)
    nl = np.maximum(space.n_loops.astype(np.float64), 1)
    i = 0
    for f in fields:
        if f == "shape":
            blk = z[i:i + 6 * W].reshape(W, 6)
            d["shape"] = np.clip(
                np.rint(blk * np.maximum(mx - 1, 1) + 1), 1, mx
            ).astype(np.int32)
            i += 6 * W
        elif f == "spatial":
            blk = z[i:i + 6 * W].reshape(W, 6)
            d["spatial"] = np.clip(np.rint(blk * nl[:, None]), 0,
                                   nl[:, None] - 1).astype(np.int32)
            i += 6 * W
        elif f == "packaging":
            if space.fixed_packaging < 0:
                d["packaging"] = np.int32(np.clip(np.rint(z[i] * 2), 0, 2))
            i += 1
        elif f == "family":
            if space.fixed_family < 0:
                d["family"] = np.int32(np.clip(
                    np.rint(z[i] * (N_FAMILIES - 1)), 0, N_FAMILIES - 1))
            i += 1
    return {k: jnp.asarray(v) for k, v in d.items()}


# ---------------------------------------------------------------------------
# the full nested engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class SearchResult:
    design: Dict
    objective: float
    metrics: Dict
    history: list                 # (iteration, best objective) trace
    trace: Optional["ConvergenceTrace"] = None   # the shared convergence
    #                               telemetry type (repro.explore.archive):
    #                               the scalarized loop's running-best
    #                               objective + cumulative SA evaluations,
    #                               comparable against ExploreResult.trace


def optimize(spec: SystemSpec, space: DesignSpace, key,
             weights=OBJ_EDP,
             bo_fields: Tuple[str, ...] = BO_FIELDS,
             sa_fields: Tuple[str, ...] = SA_FIELDS,
             n_init: int = 8, n_iter: int = 24,
             sa: SAConfig = SAConfig(), tech=None,
             init_design: Optional[Dict] = None,
             seed_designs: Optional[Sequence[Dict]] = None,
             archive=None) -> SearchResult:
    """DEPRECATED shim over the ``bo_sa`` engine backend — routes through
    ``repro.api.Session.submit`` (``Query(Problem.from_spec(spec, space),
    engine="bo_sa", ...)``) and returns the backend's ``SearchResult``
    unchanged.  See ``_optimize_impl`` for the engine itself."""
    warnings.warn(
        "legacy entry point repro.core.optimizer.optimize() is "
        "deprecated; use repro.api: Session(tech=...).submit(Query("
        "Problem.from_spec(spec, space), engine=\"bo_sa\", weights=..., "
        "engine_opts=dict(n_init=..., n_iter=..., sa=...)))",
        DeprecationWarning, stacklevel=2)
    from ..explore.api import Problem, Query, Session
    q = Query(Problem.from_spec(spec, space), engine="bo_sa",
              weights=tuple(float(w) for w in weights),
              seed_designs=seed_designs, archive=archive,
              engine_opts=dict(bo_fields=bo_fields, sa_fields=sa_fields,
                               n_init=n_init, n_iter=n_iter, sa=sa,
                               init_design=init_design))
    return Session(tech=tech).submit(q, key=key).raw


def _optimize_impl(spec: SystemSpec, space: DesignSpace, key,
                   weights=OBJ_EDP,
                   bo_fields: Tuple[str, ...] = BO_FIELDS,
                   sa_fields: Tuple[str, ...] = SA_FIELDS,
                   n_init: int = 8, n_iter: int = 24,
                   sa: SAConfig = SAConfig(), tech=None,
                   init_design: Optional[Dict] = None,
                   seed_designs: Optional[Sequence[Dict]] = None,
                   archive=None) -> SearchResult:
    """Nested BO(low-dim) x SA(high-dim) search (paper Fig. 6b).

    Setting ``bo_fields=()`` degenerates to pure SA over ``sa_fields`` —
    used by the Fig.-8 ablation ladder and the baseline mapping searches.

    ``seed_designs`` (e.g. a transferred population migrated out of a
    neighbor spec's archive via ``encoding.migrate``) replaces the leading
    random restarts of the init phase; each seed is ``repair``-ed into
    this space's feasible set first.  ``init_design`` keeps its historic
    slot-0 meaning and precedes any seeds.  At most ``n_init`` entries are
    consumed (one SA refinement each) — pass a larger ``n_init`` to spend
    budget on a bigger transferred population.

    ``archive`` (a ``repro.explore.archive.ParetoArchive``) optionally
    records every SA-refined design with its raw metric vector, so
    scalarized runs feed the same Pareto cache the exploration service
    serves fronts from.
    """
    from .constants import DEFAULT_TECH
    tech = tech or DEFAULT_TECH
    sa_run = make_sa(spec, space, sa_fields, sa, tech)
    rng = np.random.default_rng(np.asarray(jax.random.key_data(key))[-1])

    X, Y, designs = [], [], []
    history = []
    inits = ([] if init_design is None else [init_design]) + [
        {k: jnp.asarray(v) for k, v in repair(d, space).items()}
        for d in (seed_designs or [])]
    metrics_fn = jax.jit(lambda d: evaluate_system(spec, d, tech))

    def eval_point(d0, i):
        kd = jax.random.PRNGKey(int(rng.integers(2 ** 31)))
        d_best, o_best = sa_run(kd, d0, jnp.asarray(weights, F))
        return d_best, float(o_best)

    n_bo = _bo_dims(space, bo_fields)
    total = n_init + (n_iter if n_bo > 0 else 0)
    for i in range(n_init):
        d0 = random_design(jax.random.PRNGKey(int(rng.integers(2 ** 31))),
                           space)
        if i < len(inits):
            d0 = inits[i]
        db, ob = eval_point(d0, i)
        designs.append(db)
        Y.append(ob)
        if n_bo > 0:
            X.append(encode_bo(space, db, bo_fields))
        history.append((i, float(np.min(Y))))

    if n_bo > 0:
        for i in range(n_iter):
            Xa = jnp.asarray(np.stack(X))
            Ya = jnp.asarray(np.asarray(Y, np.float64), F)
            # acquisition: PI over random candidates + perturbations of best
            cand = rng.random((384, n_bo))
            zb = X[int(np.argmin(Y))]
            pert = np.clip(zb[None, :] + rng.normal(0, 0.15, (128, n_bo)),
                           0, 1)
            Z = np.vstack([cand, pert])
            mu, sg = gp_posterior(Xa, Ya, jnp.asarray(Z, F))
            pi = prob_improvement(mu, sg, float(np.min(Y)))
            z = Z[int(jnp.argmax(pi))]
            d0 = decode_bo(space, z, designs[int(np.argmin(Y))], bo_fields)
            db, ob = eval_point(d0, n_init + i)
            designs.append(db)
            Y.append(ob)
            X.append(encode_bo(space, db, bo_fields))
            history.append((n_init + i, float(np.min(Y))))

    ib = int(np.argmin(Y))
    best = designs[ib]
    metrics = metrics_fn(best)
    if archive is not None and designs:
        # one batched (vmapped) evaluation + insert for every SA-refined
        # design of the run — no per-iteration device round-trips, one
        # compilation shared across runs with equal padded dims
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *designs)
        mb = _batch_metrics(spec, tech)(stacked)
        raw = jnp.stack([jnp.asarray(mb[k], F) for k in METRIC_KEYS],
                        axis=-1)
        feas = jax.vmap(
            lambda d, m: feasibility_penalty(space, d, m))(stacked, mb) \
            <= 1.0 + 1e-6
        archive.insert(stacked, raw, mask=feas)
    return SearchResult(design=best, objective=float(Y[ib]),
                        metrics={k: np.asarray(v) for k, v in metrics.items()},
                        history=history,
                        trace=ConvergenceTrace.from_history(
                            history, evals_per_step=sa.steps * sa.chains))


# ---------------------------------------------------------------------------
# the paper's two-stage flow (Sec. IV-A): the architecture stage keeps a
# Pareto set; the integration stage's design-selector picks from it.
# The dominance convention AND the convergence-telemetry type live in ONE
# place — repro.explore.archive — and are re-exported here for the engine
# and its tests.
# ---------------------------------------------------------------------------
from ..explore.archive import (ConvergenceTrace,  # noqa: E402  (canonical)
                               pareto_front)


def two_stage_optimize(spec: SystemSpec, space: DesignSpace, key,
                       n_candidates: int = 3,
                       sa: SAConfig = SAConfig(steps=250, chains=4),
                       tech=None, archive=None,
                       seed_designs: Optional[Sequence[Dict]] = None
                       ) -> SearchResult:
    """DEPRECATED shim over the ``two_stage`` engine backend — routes
    through ``repro.api.Session.submit`` (``Query(..., engine=
    "two_stage")``) and returns the backend's ``SearchResult`` unchanged.
    See ``_two_stage_impl`` for the engine itself."""
    warnings.warn(
        "legacy entry point repro.core.optimizer.two_stage_optimize() is "
        "deprecated; use repro.api: Session(tech=...).submit(Query("
        "Problem.from_spec(spec, space), engine=\"two_stage\", "
        "engine_opts=dict(n_candidates=..., sa=...)))",
        DeprecationWarning, stacklevel=2)
    from ..explore.api import Problem, Query, Session
    q = Query(Problem.from_spec(spec, space), engine="two_stage",
              seed_designs=seed_designs, archive=archive,
              engine_opts=dict(n_candidates=n_candidates, sa=sa))
    return Session(tech=tech).submit(q, key=key).raw


def _two_stage_impl(spec: SystemSpec, space: DesignSpace, key,
                    n_candidates: int = 3,
                    sa: SAConfig = SAConfig(steps=250, chains=4),
                    tech=None, archive=None,
                    seed_designs: Optional[Sequence[Dict]] = None
                    ) -> SearchResult:
    """Stage 1 (architecture): search arch fields under several objective
    scalarizations, keep the Pareto-optimal candidates over
    (latency, energy, area).  Stage 2 (integration): for each kept
    candidate, open the integration fields (packaging/network/placement)
    and optimize EDP; the best pair wins — the selector made explicit.

    Both stages run through the same evaluation/objective path as the
    ``repro.explore`` front explorer (``log_metric_stack`` + penalty), and
    an optional ``archive`` records every refined candidate.
    ``seed_designs`` (a transferred population) warm-starts every stage-1
    scalarization's init phase."""
    from .constants import DEFAULT_TECH
    tech = tech or DEFAULT_TECH
    keys = jax.random.split(key, 8)

    cands, objs = [], []
    weights_list = [OBJ_LATENCY, OBJ_ENERGY, OBJ_EDP,
                    (1.0, 1.0, 0.0, 1.0)][:max(n_candidates, 2)]
    for i, w in enumerate(weights_list):
        r = _optimize_impl(spec, space, keys[i], weights=w,
                           bo_fields=("shape", "spatial"),
                           sa_fields=("order", "tiling", "pipe"),
                           n_init=4, n_iter=6, sa=sa, tech=tech,
                           archive=archive, seed_designs=seed_designs)
        cands.append(r.design)
        m = r.metrics
        objs.append([float(m["latency_ns"]), float(m["energy_pj"]),
                     float(m["area_mm2"])])
    keep = pareto_front(objs)

    best = None
    for ki, ci in enumerate(keep):
        r = _optimize_impl(spec, space, keys[4 + (ki % 4)], weights=OBJ_EDP,
                           bo_fields=("packaging", "family"),
                           sa_fields=("placement",),
                           n_init=2, n_iter=4, sa=sa, tech=tech,
                           init_design=cands[ci], archive=archive)
        if best is None or r.objective < best.objective:
            best = r
    best.history.append(("pareto_kept", len(keep)))
    return best
