"""NSGA-II-style evolutionary front explorer (Gemini-style co-exploration).

Where ``repro.core.optimizer`` scalarizes the four objectives into one
number, this engine keeps the whole population nondominated-ranked and
returns a *front*.  The entire evolution is a single jitted ``lax.scan``
over vmapped populations:

    generation = variate (field crossover + ``encoding.mutate`` moves)
               -> evaluate (vmapped ``evaluate_arrays``)
               -> environmental selection over parents+children
                  (dominance counts, crowding-distance tie-break)

Evaluation and objectives are the same path the scalarized engines use
(``log_metric_stack`` + ``feasibility_penalty``), so a design judged good
here is good there and vice versa.  Compiled runners are cached on the
padded workload dims exactly like ``make_sa`` — every graph with equal
(W, CH, E) shares one compilation.

Two scaling layers sit on top of the single scan:

* **island sharding** (``make_nsga(..., mesh=...)``) — the population axis
  is sharded across a device mesh with ``shard_map``; each device evolves
  an island and a ``lax.ppermute`` ring exchanges elite migrants every
  ``cfg.migration_interval`` generations.  On a 1-device mesh the body
  statically reduces to the unsharded step, so results are bit-identical
  to the plain scan.
* **cross-problem lanes** (``make_nsga_fused(..., lanes=L)``) — the whole
  run is vmapped over a stacked lane axis so ``L`` *distinct* problems
  (same padded dims / space statics / schedule) evaluate in one compiled
  dispatch; per-lane keys, populations, spec arrays and immigrants ride
  the lane axis.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from .. import obs
from ..core.encoding import (ALL_FIELDS, DesignSpace, feasibility_penalty,
                             mutate, random_design)
from ..core.evaluate import SystemSpec, evaluate_arrays
from ..core.optimizer import METRIC_KEYS, log_metric_stack, metric_stack
from .archive import (BIG, HV_LOG_REF, crowding_distance, dominance_counts,
                      flatten_design, hypervolume_2d_jit, objective_pairs)

F = jnp.float32

# design fields, in a fixed order, for the field-level crossover
_DESIGN_KEYS = ("shape", "spatial", "order", "tiling", "pipe", "logB",
                "packaging", "family", "placement")

# the mesh axis the island model shards the population over
ISLAND_AXIS = "islands"


@dataclasses.dataclass(frozen=True)
class NSGAConfig:
    pop: int = 64                 # population size (vmapped width)
    generations: int = 32         # scan length; evals = pop * generations
    fields: Tuple[str, ...] = ALL_FIELDS
    crossover_rate: float = 0.35  # per-field probability of taking the mate
    mutations: int = 2            # chained encoding.mutate moves per child
    immigrants: float = 0.125     # fraction of children replaced by fresh
    #                               random designs (keeps the front spread)
    pmx_placement: bool = False   # placement crossover MIXES both parents'
    #                               permutations (PMX) instead of taking one
    #                               wholesale — permutation validity kept
    # --- island mode (only active under make_nsga(..., mesh=...)) -------
    migration_interval: int = 4   # ppermute a migrant ring every K
    #                               generations
    migration_frac: float = 0.125  # fraction of each island's population
    #                                sent around the ring (its elite head,
    #                                replacing the neighbor's worst tail)


def pmx(key, a, b):
    """Partially-mapped crossover of two permutations (jit/vmap-safe).

    A random segment ``[lo, hi)`` of ``b`` is worked into a child that
    otherwise inherits ``a``: walking the segment, ``b[k]`` is swapped into
    position ``k`` (the classic in-place PMX formulation), so the result
    is always a valid permutation carrying ``b``'s segment and ``a``'s
    relative order elsewhere."""
    n = a.shape[0]
    k1, k2 = jax.random.split(jnp.asarray(key))
    i = jax.random.randint(k1, (), 0, n)
    j = jax.random.randint(k2, (), 0, n + 1)
    lo, hi = jnp.minimum(i, j), jnp.maximum(i, j)

    def body(k, child):
        def swap(c):
            v = b[k]
            pos = jnp.argmax(c == v)
            return c.at[pos].set(c[k]).at[k].set(v)
        return jax.lax.cond((k >= lo) & (k < hi), swap, lambda c: c, child)

    return jax.lax.fori_loop(0, n, body, a)


# compiled runners keyed like the SA cache: padded dims + static config
_NSGA_CACHE: dict = {}

# compiled design samplers and design leaf shapes, keyed by _sampler_key
_SAMPLERS: dict = {}
_TEMPLATES: dict = {}


def _sampler_key(space: DesignSpace):
    """The statics ``random_design`` reads when ``nl``/``bounds`` come in
    as runtime arrays — and nothing else: no ``space.spec`` content, so
    problems that differ only in their workload share one program."""
    return (space.W, space.CH, space.max_shape, space.max_logB,
            space.max_total_pes, space.fixed_packaging, space.fixed_family,
            space.allow_pipeline)


def sample_designs(key, space: DesignSpace, n, nl=None, bounds=None) -> Dict:
    """``n`` uniform random designs of ``space`` drawn by ONE compiled
    program: bit for bit ``jax.vmap(lambda k: random_design(k, space))(
    jax.random.split(key, prod(n)))``, each leaf reshaped to lead with
    ``n`` (an int, or a tuple of batch dims).  Run eagerly that vmap is
    dozens of single-op dispatches (one per permutation row); here it is
    one, cached by ``_sampler_key`` and ``n``.

    ``nl``/``bounds`` (default: ``space``'s own) are runtime operands —
    the contract of ``mutate`` and of ``_static_key`` — so a cache hit for
    a statics-equal but different problem draws from that problem's
    bounds.  Counts ``explore.sampler.calls`` on every call and
    ``explore.sampler.compiles`` on every program built."""
    shape = (n,) if isinstance(n, int) else tuple(n)
    ck = _sampler_key(space) + (shape,)
    obs.inc("explore.sampler.calls")
    fn = _SAMPLERS.get(ck)
    if fn is None:
        obs.inc("explore.sampler.compiles")

        def draw(k, nl, b):
            ks = jax.random.split(k, int(np.prod(shape)))
            d = jax.vmap(lambda kk: random_design(kk, space, nl=nl,
                                                  bounds=b))(ks)
            return {f: v.reshape(shape + v.shape[1:]) for f, v in d.items()}

        fn = _SAMPLERS[ck] = jax.jit(draw)
    return fn(key, space.n_loops if nl is None else nl,
              space.bounds if bounds is None else bounds)


def design_template(space: DesignSpace) -> Dict:
    """One all-zero design with the leaf shapes and dtypes
    ``random_design`` gives in ``space`` — what a ``ParetoArchive``
    template is read for.  Traced once per ``_sampler_key`` (no device
    work, no retrace per problem); a fresh zero copy per call."""
    ck = _sampler_key(space)
    avals = _TEMPLATES.get(ck)
    if avals is None:
        avals = _TEMPLATES[ck] = jax.eval_shape(
            lambda k: random_design(k, space),
            jax.eval_shape(jax.random.PRNGKey, 0))
    return {f: np.zeros(a.shape, a.dtype) for f, a in avals.items()}


def _static_key(dims, idx, cfg, tech, space):
    """Everything compile-relevant about one scan variant EXCEPT how it is
    laid out over devices (mesh) or lanes — the shared stem of the
    single-run, island and fused cache keys.

    Workload CONTENT (bounds/loopmask/...) is deliberately absent: every
    cached closure takes it at runtime via the arrays dict (evaluation,
    mutation, and immigrant sampling alike), so a cache hit for a
    statics-equal but different problem is content-correct.  Keep it that
    way — baking any ``space.spec`` array into a closure here would make
    results depend on which problem first populated the cache."""
    return (dims, idx, cfg, tech, space.max_shape, space.max_logB,
            space.max_total_pes, space.fixed_packaging,
            space.fixed_family, space.allow_pipeline)


def _immigrants(k_imm, space, cfg, n_imm, loopmask, bounds):
    """One run's fresh random designs, stacked (generations, n_imm, ...),
    or ``None`` without immigrants.  ``n_loops``/``bounds`` come from the
    run's workload arrays, not from ``space``: the cached sampler carries
    NO workload content, so a cache hit for a statics-equal but different
    problem stays content-correct."""
    if not n_imm:
        return None
    nl = jnp.sum(loopmask, axis=1).astype(jnp.int32)
    return sample_designs(k_imm, space, (cfg.generations, n_imm), nl, bounds)


def make_nsga(spec: SystemSpec, space: DesignSpace,
              objectives: Tuple[str, ...] = METRIC_KEYS,
              cfg: NSGAConfig = NSGAConfig(), tech=None, mesh=None):
    """Build a jitted front explorer.

    Returns ``run(key, pop0, arrays=None) ->
    (pop, raw, sel, ev_designs, ev_raw, ev_feas, trace)`` where ``pop0``
    is a stacked design pytree of width ``cfg.pop``; ``raw`` is the
    (pop, 4) matrix of raw metrics in ``METRIC_KEYS`` order and ``sel``
    the (pop, n_obj) penalized log-objectives selection ranked on.
    ``ev_designs`` / ``ev_raw`` / ``ev_feas`` are EVERY evaluated design
    of the run, stacked (generations, pop, ...) — the archive fodder:
    nothing the explorer paid for is thrown away.  ``ev_feas`` marks
    designs with no feasibility penalty; infeasible points may stay in
    the evolving population (the penalty steers them out) but must not be
    archived or served.  The population is elitist (nondominated parents
    survive unless crowd-pruned), so ``pop`` carries the running front;
    total evaluations = ``cfg.pop * cfg.generations``.

    ``trace`` is the per-generation convergence telemetry, scanned out of
    the same ``lax.scan`` with ZERO extra evaluations (pure dominance
    math over objective vectors the run already paid for): a dict of
    stacked arrays — ``front_size`` (G,) feasible nondominated count of
    the post-selection population, ``hypervolume`` (G, P) running
    (cumulative-best) 2-D hypervolume per objective pair over clipped
    log-metrics w.r.t. ``HV_LOG_REF`` (monotone non-decreasing by
    construction), ``best`` (G,) running best penalized scalarized
    objective (monotone non-increasing), and ``feasible_frac`` (G,) the
    feasible fraction of each generation's children.  Feed it to
    ``ConvergenceTrace.from_scan`` for the host-side view.

    ``mesh`` (a ``jax.sharding.Mesh`` with an ``"islands"`` axis) turns on
    the island model: the population axis is sharded across the mesh with
    ``shard_map``, each device evolves its own island (per-island PRNG
    streams fold in the island index) and every ``cfg.migration_interval``
    generations each island's ``cfg.migration_frac`` elite head rotates
    one hop around a ``lax.ppermute`` ring, replacing the receiver's worst
    tail.  Telemetry stays GLOBAL (the trace is computed over the
    all-gathered population, so front size / hypervolume mean the same
    thing sharded or not).  On a 1-device mesh every island construct is
    statically skipped and the result is bit-identical to ``mesh=None``.
    """
    from ..core.constants import DEFAULT_TECH
    tech = tech or DEFAULT_TECH
    dims = (spec.W, spec.CH, spec.E)
    idx = tuple(METRIC_KEYS.index(o) for o in objectives)
    if not idx:
        raise ValueError("objectives must name at least one metric")

    n_isl = 1
    if mesh is not None:
        if ISLAND_AXIS not in mesh.shape:
            raise ValueError(f"island mesh must name a {ISLAND_AXIS!r} "
                             f"axis; got {tuple(mesh.shape)}")
        n_isl = int(mesh.shape[ISLAND_AXIS])
        if cfg.pop % n_isl or cfg.pop // n_isl < 2:
            raise ValueError(f"pop={cfg.pop} cannot shard into {n_isl} "
                             f"islands of at least 2 designs")

    cache_key = _static_key(dims, idx, cfg, tech, space) + (mesh,)
    if cache_key not in _NSGA_CACHE:
        # immigrants are drawn OUTSIDE the scanned/jitted evolution (as a
        # scan input, by ``sample_designs``) — random_design's permutation
        # sorts are expensive to compile and belong in one small vmapped
        # kernel, not in the body
        n_imm = int(round((cfg.pop // n_isl) * cfg.immigrants)) * n_isl
        body = _build_run(space, dims, idx, cfg, tech, n_isl=n_isl)
        if mesh is not None:
            P = PartitionSpec
            body = jax.shard_map(
                body, mesh=mesh,
                # (key, pop0, arr, imm): key + spec arrays replicated,
                # population sharded on its leading axis, immigrants on
                # their per-generation axis 1
                in_specs=(P(), P(ISLAND_AXIS), P(),
                          P(None, ISLAND_AXIS) if n_imm else P()),
                # (pop, raw, sel, ev_designs, ev_raw, ev_feas, trace):
                # per-generation stacks shard on axis 1 (axis 0 is the
                # scan); the trace is computed over the gathered global
                # population, hence replicated
                out_specs=(P(ISLAND_AXIS), P(ISLAND_AXIS), P(ISLAND_AXIS),
                           P(None, ISLAND_AXIS), P(None, ISLAND_AXIS),
                           P(None, ISLAND_AXIS), P()),
                check_vma=False)
        _NSGA_CACHE[cache_key] = (jax.jit(body), n_imm,
                                  dict(executed=False))
    jitted, n_imm, state = _NSGA_CACHE[cache_key]

    def runner(key, pop0, arrays=None):
        arr = {k: jnp.asarray(v) for k, v in (arrays or spec.arrays).items()}
        k_run, k_imm = jax.random.split(jnp.asarray(key))
        imm = _immigrants(k_imm, space, cfg, n_imm, arr["loopmask"],
                          arr["bounds"])
        out = jitted(k_run, pop0, arr, imm)
        state["executed"] = True
        return out

    # first-call attribution for the observability layer: a scan variant
    # that has never executed in this process pays XLA lowering on its
    # first call, which per-segment wall-clock must attribute separately
    # (the raw material for plan-cost estimates)
    runner.compile_state = state
    return runner


def make_nsga_fused(spec: SystemSpec, space: DesignSpace,
                    objectives: Tuple[str, ...] = METRIC_KEYS,
                    cfg: NSGAConfig = NSGAConfig(), tech=None,
                    lanes: int = 1):
    """Build a jitted MULTI-PROBLEM front explorer: the whole ``make_nsga``
    run vmapped over a stacked lane axis, so ``lanes`` independent
    populations — typically *different* problems whose spec arrays share
    one padded shape — evolve in one compiled dispatch.

    Returns ``run(keys, pops, arrays_seq)`` where ``keys`` is a sequence
    of ``lanes`` PRNG keys, ``pops`` a stacked design pytree of shape
    ``(lanes, cfg.pop, ...)`` and ``arrays_seq`` a sequence of ``lanes``
    spec-array dicts (equal shapes; e.g. each problem's ``spec.arrays``).
    Outputs match ``make_nsga`` with a leading lane axis.  Per-lane PRNG
    handling is identical to the single-lane runner (same split/fold
    chain), so lane ``i``'s results correspond exactly to an unbatched
    ``make_nsga(...)(keys[i], pops[i], arrays_seq[i])`` run.

    Compiled variants are cached per (statics, lanes); callers should
    pow2-pad the lane count (``quantize.bucket_lanes``) and discard the
    padding lanes' outputs, so a long-lived service compiles O(log(max
    batch)) fused variants.  Mutually exclusive with island sharding.
    """
    from ..core.constants import DEFAULT_TECH
    tech = tech or DEFAULT_TECH
    dims = (spec.W, spec.CH, spec.E)
    idx = tuple(METRIC_KEYS.index(o) for o in objectives)
    if not idx:
        raise ValueError("objectives must name at least one metric")
    if lanes < 1:
        raise ValueError("lanes must be >= 1")

    cache_key = _static_key(dims, idx, cfg, tech, space) + ("lanes", lanes)
    if cache_key not in _NSGA_CACHE:
        _NSGA_CACHE[cache_key] = (
            jax.jit(jax.vmap(_build_run(space, dims, idx, cfg, tech))),
            int(round(cfg.pop * cfg.immigrants)), dict(executed=False))
    jitted, n_imm, state = _NSGA_CACHE[cache_key]

    def runner(keys, pops, arrays_seq):
        if len(keys) != lanes or len(arrays_seq) != lanes:
            raise ValueError(f"expected {lanes} keys/array dicts")
        arr = {k: jnp.stack([jnp.asarray(a[k]) for a in arrays_seq])
               for k in arrays_seq[0]}
        k_runs, imms = [], []
        for i, key in enumerate(keys):
            # the exact single-lane key chain, per lane; immigrants are
            # drawn from lane i's OWN workload arrays, matching what an
            # unbatched run of that lane's problem would draw
            k_run, k_imm = jax.random.split(jnp.asarray(key))
            k_runs.append(k_run)
            if n_imm:
                imms.append(_immigrants(k_imm, space, cfg, n_imm,
                                        arr["loopmask"][i], arr["bounds"][i]))
        imm = jax.tree.map(lambda *xs: jnp.stack(xs), *imms) \
            if n_imm else None
        out = jitted(jnp.stack(k_runs), pops, arr, imm)
        state["executed"] = True
        return out

    runner.compile_state = state
    return runner


def make_nsga_gated(spec: SystemSpec, space: DesignSpace,
                    objectives: Tuple[str, ...] = METRIC_KEYS,
                    cfg: NSGAConfig = NSGAConfig(), tech=None,
                    n_exact: int = 1, beta: float = 1.0,
                    tau: float = 1.0):
    """Build a SURROGATE-GATED front explorer: each generation produces
    the same ``cfg.pop`` candidate children as the plain scan (identical
    variation PRNG chain), but only the ``n_exact`` most promising —
    ranked by predicted-Pareto optimism over the surrogate ensemble's
    lower-confidence-bound objectives (mean − ``beta``·ensemble std,
    dominance-counted + crowding tie-broken) — get exact evaluations.
    Candidates whose normalized ensemble disagreement exceeds ``tau``
    are FORCED into the exact slots whatever their rank: the surrogate
    never silently decides where it is least sure.

    Returns ``run(key, pop0, sur, arrays=None)`` shaped like the
    ``make_nsga`` runner except ``ev_designs``/``ev_raw``/``ev_feas``
    stack (generations, n_exact, ...) — only exact evaluations are
    archive fodder — and ``trace`` gains ``forced_exact`` (G,) and
    ``disagreement`` (G,) gate telemetry.  ``sur`` is
    ``Surrogate.scan_arrays(embedding)``: ensemble weights ride as
    RUNTIME operands, so refitting the surrogate reuses the compiled
    scan (a new static_shape merely retraces).  Ranking happens in the
    surrogate's normalized output space (dominance is invariant under
    per-column positive affine maps) and knows nothing of feasibility
    penalties — infeasible optimists cost one exact evaluation and are
    then selected out exactly as in the plain scan.  Mutually exclusive
    with island sharding and megabatch fusion; ``surrogate=off`` paths
    never construct this runner."""
    from ..core.constants import DEFAULT_TECH
    tech = tech or DEFAULT_TECH
    dims = (spec.W, spec.CH, spec.E)
    idx = tuple(METRIC_KEYS.index(o) for o in objectives)
    if not idx:
        raise ValueError("objectives must name at least one metric")
    n_exact = min(max(int(n_exact), 1), cfg.pop)

    cache_key = _static_key(dims, idx, cfg, tech, space) + (
        "gate", n_exact, float(beta), float(tau))
    if cache_key not in _NSGA_CACHE:
        body = _build_run_gated(space, dims, idx, cfg, tech, n_exact,
                                float(beta), float(tau))
        _NSGA_CACHE[cache_key] = (
            jax.jit(body), int(round(cfg.pop * cfg.immigrants)),
            dict(executed=False))
    jitted, n_imm, state = _NSGA_CACHE[cache_key]

    def runner(key, pop0, sur, arrays=None):
        # the exact make_nsga key chain: gating changes WHICH children
        # get exact evaluations, never which children are generated
        arr = {k: jnp.asarray(v) for k, v in (arrays or spec.arrays).items()}
        k_run, k_imm = jax.random.split(jnp.asarray(key))
        imm = _immigrants(k_imm, space, cfg, n_imm, arr["loopmask"],
                          arr["bounds"])
        out = jitted(k_run, pop0, arr, imm,
                     {k: jnp.asarray(v) for k, v in sur.items()})
        state["executed"] = True
        return out

    runner.compile_state = state
    runner.n_exact = n_exact
    return runner


_SUR_WEIGHT_KEYS = ("W1", "b1", "W2", "b2", "W3", "b3")


def _build_run_gated(space, dims, idx, cfg, tech, n_exact: int,
                     beta: float, tau: float):
    """The gated twin of ``_build_run`` (no islands, no migration): same
    variation and environmental-selection math, with the surrogate
    pre-filter between them."""
    N = cfg.pop
    obj_idx = jnp.asarray(idx, jnp.int32)
    pairs = objective_pairs(len(idx))
    hv_ref = jnp.asarray([HV_LOG_REF, HV_LOG_REF], F)

    def eval_one(d, arr):
        m = evaluate_arrays(arr, d, dims, tech)
        with jax.named_scope("selection"):
            raw = metric_stack(m)
            p = feasibility_penalty(space, d, m)
            sel = log_metric_stack(m)[obj_idx] + 8.0 * jnp.log(p)
            return raw, sel, p <= 1.0 + 1e-6

    def eval_pop(pop, arr):
        return jax.vmap(lambda d: eval_one(d, arr))(pop)

    def crossover(key, a, b):
        ks = jax.random.split(key, len(_DESIGN_KEYS) + 1)
        out = {}
        for i, f in enumerate(_DESIGN_KEYS):
            take = jax.random.uniform(ks[i]) < cfg.crossover_rate
            if f == "placement" and cfg.pmx_placement:
                out[f] = jnp.where(take, pmx(ks[-1], a[f], b[f]), a[f])
            else:
                out[f] = jnp.where(take, b[f], a[f])
        return out

    n_imm = int(round(N * cfg.immigrants))

    def gate(sur, children):
        """Rank all N candidates on the surrogate, pick the ``n_exact``
        exact-evaluation slots: forced-by-disagreement first, then
        predicted-Pareto optimists."""
        X = jax.vmap(flatten_design)(children)              # (N, Dd)
        X = jnp.concatenate(
            [X, jnp.broadcast_to(sur["emb"], (N,) + sur["emb"].shape)],
            axis=1)
        Xn = (X - sur["x_mean"]) / sur["x_std"]

        def member(p):
            h = jnp.tanh(Xn @ p["W1"] + p["b1"])
            h = jnp.tanh(h @ p["W2"] + p["b2"])
            return h @ p["W3"] + p["b3"]

        out = jax.vmap(member)(
            {k: sur[k] for k in _SUR_WEIGHT_KEYS})          # (M, N, 4)
        mean_n = jnp.mean(out, 0)
        std_n = jnp.std(out, 0)
        dis = jnp.mean(std_n, axis=1)                       # (N,)
        # optimism: LCB dominance rank in normalized output space
        # (dominance is invariant under per-column positive affine maps)
        lcb = (mean_n - F(beta) * std_n)[:, obj_idx]
        ones = jnp.ones((N,), bool)
        nd = dominance_counts(lcb, ones)
        crowd = crowding_distance(lcb, ones)
        score = nd.astype(F) * F(1e6) - jnp.minimum(crowd, F(1e5))
        forced = dis > F(tau)
        score = jnp.where(forced, -F(BIG), score)
        order = jnp.argsort(score)[:n_exact]
        return order, jnp.sum(forced).astype(jnp.int32), jnp.mean(dis)

    def telemetry(sel_n, feas_n, cfeas, hv_run, best_run):
        finite = jnp.all(jnp.isfinite(sel_n), axis=-1)
        ok = finite & feas_n
        sane = jnp.where(jnp.isfinite(sel_n), sel_n, F(BIG))
        nd = dominance_counts(sane, ok)
        front_size = jnp.sum((nd == 0) & ok).astype(jnp.int32)
        hv_now = hv_run
        if pairs:
            hv_now = jnp.stack([
                hypervolume_2d_jit(sel_n[:, [i, j]], hv_ref, valid=ok)
                for i, j in pairs])
            hv_run = jnp.maximum(hv_run, hv_now)
        scal = jnp.where(finite, jnp.sum(sane, axis=-1), F(BIG))
        best_run = jnp.minimum(best_run, jnp.min(scal))
        tr = dict(front_size=front_size, hypervolume=hv_run, hv_now=hv_now,
                  best=best_run, feasible_frac=jnp.mean(cfeas.astype(F)))
        return hv_run, best_run, tr

    def step(arr, sur, carry, k, imm_g):
        pop, raw, sel, feas, hv_run, best_run = carry
        with jax.named_scope("variation"):
            k_mate, k_cx, k_mut = jax.random.split(k, 3)
            nl = jnp.sum(arr["loopmask"], axis=1).astype(jnp.int32)

            # --- variation: IDENTICAL to the ungated scan (same PRNG uses)
            partners = jax.random.randint(k_mate, (N,), 0, N)
            mates = jax.tree.map(lambda x: x[partners], pop)
            children = jax.vmap(crossover)(jax.random.split(k_cx, N), pop,
                                           mates)
            for r in range(cfg.mutations):
                kr = jax.random.split(jax.random.fold_in(k_mut, r), N)
                children = jax.vmap(
                    lambda kk, d: mutate(kk, d, space, cfg.fields,
                                         nl=nl, bounds=arr["bounds"]))(
                    kr, children)
            if n_imm:
                children = jax.tree.map(
                    lambda c, f: c.at[:n_imm].set(f), children, imm_g)

        # --- surrogate pre-filter: exact-evaluate only the chosen slots
        with jax.named_scope("selection"):
            order, n_forced, dis_mean = gate(sur, children)
            picked = jax.tree.map(lambda x: x[order], children)
        craw, csel, cfeas = eval_pop(picked, arr)

        # --- environmental selection over the N + n_exact pool
        with jax.named_scope("selection"):
            a_pop = jax.tree.map(lambda x, y: jnp.concatenate([x, y]),
                                 pop, picked)
            a_raw = jnp.concatenate([raw, craw])
            a_sel = jnp.concatenate([sel, csel])
            a_feas = jnp.concatenate([feas, cfeas])
            finite = jnp.all(jnp.isfinite(a_sel), axis=-1)
            a_sane = jnp.where(jnp.isfinite(a_sel), a_sel, F(BIG))
            nd = dominance_counts(a_sane, finite)
            crowd = crowding_distance(a_sane, finite)
            keyv = jnp.where(
                finite, nd.astype(F) * F(1e6) - jnp.minimum(crowd, F(1e5)),
                F(BIG))
            order_s = jnp.argsort(keyv)[:N]
            pop_n = jax.tree.map(lambda x: x[order_s], a_pop)
            raw_n = a_raw[order_s]
            sel_n, feas_n = a_sel[order_s], a_feas[order_s]
            hv_run, best_run, tr = telemetry(sel_n, feas_n, cfeas,
                                             hv_run, best_run)
            tr["forced_exact"] = n_forced
            tr["disagreement"] = dis_mean
        return ((pop_n, raw_n, sel_n, feas_n, hv_run, best_run),
                (picked, craw, cfeas, tr))

    def run(key, pop0, arr, imm, sur):
        raw0 = jnp.full((N, len(METRIC_KEYS)), jnp.inf, F)
        sel0 = jnp.full((N, len(idx)), jnp.inf, F)
        feas0 = jnp.zeros((N,), bool)
        hv0 = jnp.zeros((len(pairs),), F)
        best0 = jnp.asarray(jnp.inf, F)
        keys = jax.random.split(key, cfg.generations)
        carry0 = (pop0, raw0, sel0, feas0, hv0, best0)
        ((pop, raw, sel, _feas, _hv, _best),
         (ev_designs, ev_raw, ev_feas, trace)) = jax.lax.scan(
            lambda c, xs: step(arr, sur, c, *xs), carry0, (keys, imm))
        return pop, raw, sel, ev_designs, ev_raw, ev_feas, trace

    return run


def _build_run(space, dims, idx, cfg, tech, n_isl: int = 1):
    # per-island population width; with n_isl == 1 (the unsharded path and
    # the 1-device mesh) every island construct below is STATICALLY
    # elided, so the built computation is exactly the historical one
    N = cfg.pop // n_isl
    n_mig = min(int(round(N * cfg.migration_frac)), N - 1) if n_isl > 1 \
        else 0
    mig_k = max(1, int(cfg.migration_interval))
    obj_idx = jnp.asarray(idx, jnp.int32)
    pairs = objective_pairs(len(idx))
    hv_ref = jnp.asarray([HV_LOG_REF, HV_LOG_REF], F)

    def eval_one(d, arr):
        m = evaluate_arrays(arr, d, dims, tech)
        with jax.named_scope("selection"):
            # the penalized log-objectives selection ranks on
            raw = metric_stack(m)
            p = feasibility_penalty(space, d, m)
            sel = log_metric_stack(m)[obj_idx] + 8.0 * jnp.log(p)
            return raw, sel, p <= 1.0 + 1e-6   # feasible <=> no penalty

    def eval_pop(pop, arr):
        return jax.vmap(lambda d: eval_one(d, arr))(pop)

    def crossover(key, a, b):
        ks = jax.random.split(key, len(_DESIGN_KEYS) + 1)
        out = {}
        for i, f in enumerate(_DESIGN_KEYS):
            take = jax.random.uniform(ks[i]) < cfg.crossover_rate
            if f == "placement" and cfg.pmx_placement:
                # PMX keeps the child a valid permutation while actually
                # mixing both parents' placements (whole-field take can
                # only copy one of them)
                out[f] = jnp.where(take, pmx(ks[-1], a[f], b[f]), a[f])
            else:
                out[f] = jnp.where(take, b[f], a[f])
        return out

    n_imm = int(round(N * cfg.immigrants))

    def telemetry(sel_n, feas_n, cfeas, hv_run, best_run):
        """Per-generation convergence stats over the selected population —
        dominance/staircase math only, no design evaluations.  ``hv_now``
        (the instantaneous, non-running front hypervolume) is traced
        alongside the running max: it resolves WHEN quality arrived, the
        signal the transfer trust calibration regresses on.  Under island
        sharding the stats are computed over the all-gathered GLOBAL
        population (replicated on every device), so the trace means the
        same thing at any island count."""
        if n_isl > 1:
            sel_n = jax.lax.all_gather(sel_n, ISLAND_AXIS, tiled=True)
            feas_n = jax.lax.all_gather(feas_n, ISLAND_AXIS, tiled=True)
            cfeas = jax.lax.all_gather(cfeas, ISLAND_AXIS, tiled=True)
        finite = jnp.all(jnp.isfinite(sel_n), axis=-1)
        ok = finite & feas_n
        sane = jnp.where(jnp.isfinite(sel_n), sel_n, F(BIG))
        nd = dominance_counts(sane, ok)
        front_size = jnp.sum((nd == 0) & ok).astype(jnp.int32)
        hv_now = hv_run
        if pairs:
            hv_now = jnp.stack([
                hypervolume_2d_jit(sel_n[:, [i, j]], hv_ref, valid=ok)
                for i, j in pairs])
            hv_run = jnp.maximum(hv_run, hv_now)
        scal = jnp.where(finite, jnp.sum(sane, axis=-1), F(BIG))
        best_run = jnp.minimum(best_run, jnp.min(scal))
        tr = dict(front_size=front_size, hypervolume=hv_run, hv_now=hv_now,
                  best=best_run, feasible_frac=jnp.mean(cfeas.astype(F)))
        return hv_run, best_run, tr

    def step(arr, carry, k, imm_g, g):
        pop, raw, sel, feas, hv_run, best_run = carry
        with jax.named_scope("variation"):
            k_mate, k_cx, k_mut = jax.random.split(k, 3)
            nl = jnp.sum(arr["loopmask"], axis=1).astype(jnp.int32)

            # --- variation: whole-field crossover with a random mate,
            # then a few chained single-field mutate moves (the SA
            # neighborhood)
            partners = jax.random.randint(k_mate, (N,), 0, N)
            mates = jax.tree.map(lambda x: x[partners], pop)
            children = jax.vmap(crossover)(jax.random.split(k_cx, N), pop,
                                           mates)
            for r in range(cfg.mutations):
                kr = jax.random.split(jax.random.fold_in(k_mut, r), N)
                children = jax.vmap(
                    lambda kk, d: mutate(kk, d, space, cfg.fields,
                                         nl=nl, bounds=arr["bounds"]))(
                    kr, children)
            if n_imm:
                # random immigrants fight convergence collapse of the
                # front
                children = jax.tree.map(
                    lambda c, f: c.at[:n_imm].set(f), children, imm_g)
        craw, csel, cfeas = eval_pop(children, arr)
        with jax.named_scope("selection"):
            return select(pop, raw, sel, feas, hv_run, best_run,
                          children, craw, csel, cfeas, g)

    def select(pop, raw, sel, feas, hv_run, best_run, children, craw, csel,
               cfeas, g):
        # --- environmental selection over the 2N parent+child pool
        a_pop = jax.tree.map(lambda x, y: jnp.concatenate([x, y]),
                             pop, children)
        a_raw = jnp.concatenate([raw, craw])
        a_sel = jnp.concatenate([sel, csel])
        a_feas = jnp.concatenate([feas, cfeas])
        finite = jnp.all(jnp.isfinite(a_sel), axis=-1)
        a_sane = jnp.where(jnp.isfinite(a_sel), a_sel, F(BIG))
        nd = dominance_counts(a_sane, finite)
        crowd = crowding_distance(a_sane, finite)
        # ascending rank: fewer dominators first, crowding breaks ties;
        # non-finite rows sort last
        keyv = jnp.where(finite,
                         nd.astype(F) * F(1e6) - jnp.minimum(crowd, F(1e5)),
                         F(BIG))
        order = jnp.argsort(keyv)[:N]
        pop_n = jax.tree.map(lambda x: x[order], a_pop)
        raw_n = raw_n0 = a_raw[order]
        sel_n, feas_n = a_sel[order], a_feas[order]
        if n_mig:
            # --- island migration: the rank-sorted population's elite
            # head rotates one hop around the device ring; it replaces
            # the receiver's worst tail, but only on migration
            # generations (the ppermute itself runs unconditionally —
            # collectives must not hide inside lax.cond — and jnp.where
            # keeps or discards the migrants)
            do_mig = (g % mig_k) == (mig_k - 1)
            ring = [(i, (i + 1) % n_isl) for i in range(n_isl)]
            head = (jax.tree.map(lambda x: x[:n_mig], pop_n),
                    raw_n[:n_mig], sel_n[:n_mig], feas_n[:n_mig])
            r_pop, r_raw, r_sel, r_feas = jax.lax.ppermute(
                head, ISLAND_AXIS, ring)

            def splice(x, r):
                return jnp.concatenate(
                    [x[:N - n_mig], jnp.where(do_mig, r, x[N - n_mig:])])

            pop_n = jax.tree.map(splice, pop_n, r_pop)
            raw_n = splice(raw_n0, r_raw)
            sel_n = splice(sel_n, r_sel)
            feas_n = splice(feas_n, r_feas)
        hv_run, best_run, tr = telemetry(sel_n, feas_n, cfeas,
                                         hv_run, best_run)
        return ((pop_n, raw_n, sel_n, feas_n, hv_run, best_run),
                (children, craw, cfeas, tr))

    def run(key, pop0, arr, imm):
        # the initial population carries +inf objectives: its (variated)
        # offspring are evaluated in generation 0 and unevaluated parents
        # rank last.  Keeping ALL evaluation inside the scan body means the
        # (large) evaluate_arrays graph is compiled exactly once.
        raw0 = jnp.full((N, len(METRIC_KEYS)), jnp.inf, F)
        sel0 = jnp.full((N, len(idx)), jnp.inf, F)
        feas0 = jnp.zeros((N,), bool)
        hv0 = jnp.zeros((len(pairs),), F)
        best0 = jnp.asarray(jnp.inf, F)
        if n_isl > 1:
            # islands draw from diverged PRNG streams; skipped statically
            # at n_isl == 1 so the 1-device mesh replays the plain chain
            key = jax.random.fold_in(key, jax.lax.axis_index(ISLAND_AXIS))
        keys = jax.random.split(key, cfg.generations)
        gens = jnp.arange(cfg.generations, dtype=jnp.int32)
        carry0 = (pop0, raw0, sel0, feas0, hv0, best0)
        ((pop, raw, sel, _feas, _hv, _best),
         (ev_designs, ev_raw, ev_feas, trace)) = jax.lax.scan(
            lambda c, xs: step(arr, c, *xs), carry0, (keys, imm, gens))
        return pop, raw, sel, ev_designs, ev_raw, ev_feas, trace

    return run
