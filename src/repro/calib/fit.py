"""Gradient-descent calibration of TechConstants against measurements.

``fit(measurements, free=...)`` reparameterizes a whitelisted subset of
:class:`TechConstants` fields in log-space (positivity is structural), then
minimizes mean squared *log* error — smooth, scale-free, equivalent to
relative error for small residuals — with full-batch Adam in one jitted
``lax.scan`` (the ``explore/surrogate.py`` training idiom).  The model side
of every residual is computed through the existing differentiable pure-JAX
evaluation path: ``analyze_chiplet`` for ``chiplet_matmul`` measurements,
``evaluate_system`` for ``system`` ones.

CLI::

    PYTHONPATH=src python -m repro.calib.fit --source simulator \
        --free t_tile_overhead_ns,corr_latency --name sim28 --out artifacts/calib

Obs surface: ``calib.fit_loss``, ``calib.error_before`` / ``calib.error_after``
histograms, a ``calib.fits`` counter, and a ``type="calib_fit"`` journal
record per fit.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.constants import (DEFAULT_TECH, FITTABLE_FIELDS,
                                  TechConstants, tech_key)
from repro.core.dataflow import analyze_chiplet
from repro.core.workload import MAX_LOOPS, matmul

from .measurements import Measurement, measurements_digest

F = jnp.float32

#: default free set: the additive per-tile overhead the pure pipeline model
#: omits plus the four per-metric corrections — enough to absorb systematic
#: scale error in every metric without disturbing model structure.
DEFAULT_FREE = ("t_tile_overhead_ns", "corr_latency", "corr_energy",
                "corr_area", "corr_cost")

#: log-space floor: fields whose current value is 0 (e.g. the overhead's
#: neutral default) start here instead of log(0).
_FLOOR = 1e-3


# ---------------------------------------------------------------------------
# measurement -> differentiable model prediction
# ---------------------------------------------------------------------------
def _chiplet_predictor(ms: Sequence[Measurement], idx: List[int]):
    """Batched ``analyze_chiplet`` predictor for ``chiplet_matmul`` rows.

    All rows share padded array shapes, so one vmapped call covers every
    (M, N, K, bw) regardless of shape — a single compile for the whole
    sweep.  Configuration matches ``benchmarks/bench_validation``: one
    ax x ay core, chiplet tile = one output fold.
    """
    wls, tis, bws, shs = [], [], [], []
    for m in ms:
        info = m.info
        if m.metric != "latency_ns":
            raise ValueError(
                f"chiplet_matmul supports latency_ns only, got {m.metric!r}")
        M_, N_, K_ = int(info["M"]), int(info["N"]), int(info["K"])
        ax, ay = int(info.get("ax", 8)), int(info.get("ay", 8))
        wls.append(matmul("mm", M_, N_, K_).to_arrays())
        tis.append([[ax, ay, K_] + [1] * (MAX_LOOPS - 3)] * 2)
        bws.append(float(info.get("bw", 128.0)))
        shs.append([ax, ay, 1, 1, 1, 1])
    wl_b = {k: jnp.asarray(np.stack([w[k] for w in wls])) for k in wls[0]}
    ti_b = jnp.asarray(np.asarray(tis), jnp.int32)
    sh_b = jnp.asarray(np.asarray(shs), jnp.int32)
    bw_b = jnp.asarray(np.asarray(bws), F)
    sp = jnp.asarray([0, 1, 0, 1, 0, 1], jnp.int32)
    od = jnp.asarray([list(range(MAX_LOOPS))] * 3, jnp.int32)
    idx_b = jnp.asarray(np.asarray(idx), jnp.int32)

    def predict(tech):
        def one(wl, sh, ti, bw):
            an = analyze_chiplet(wl, sh, sp, od, ti, tech, ext_bw_gbps=bw)
            return an["delay_ns"] * F(tech.corr_latency)
        return idx_b, jax.vmap(one)(wl_b, sh_b, ti_b, bw_b)

    return predict


def _system_predictor(ms: Sequence[Measurement], idx: List[int]):
    """``evaluate_system`` predictor for ``system`` rows sharing one frozen
    baseline configuration (graph, baseline, pe_budget, ch_max, seed)."""
    from repro.core.baselines import make_baseline
    from repro.core.evaluate import SystemSpec, evaluate_system
    from repro.core.presets import fig7_suite

    info = ms[0].info
    graphs = fig7_suite()
    gname = str(info["graph"])
    if gname not in graphs:
        raise KeyError(f"unknown graph {gname!r}; known: {sorted(graphs)}")
    spec = SystemSpec.build(graphs[gname], ch_max=int(info.get("ch_max", 4)))
    bl = make_baseline(str(info.get("baseline", "monad")), spec,
                       jax.random.PRNGKey(int(info.get("seed", 0))),
                       pe_budget=int(info.get("pe_budget", 1024)))
    design = jax.tree.map(jnp.asarray, bl.init)
    metrics = [m.metric for m in ms]
    idx_b = jnp.asarray(np.asarray(idx), jnp.int32)

    def predict(tech):
        res = evaluate_system(spec, design, tech)
        return idx_b, jnp.stack([res[k] for k in metrics])

    return predict


def _system_group_key(m: Measurement) -> tuple:
    info = m.info
    return ("system", str(info.get("graph")), str(info.get("baseline")),
            int(info.get("pe_budget", 1024)), int(info.get("ch_max", 4)),
            int(info.get("seed", 0)))


def _build_predictor(ms: Sequence[Measurement]):
    """Compile-friendly predictor over a mixed measurement list: returns
    ``predict(tech) -> (n,) jnp array`` aligned with ``ms`` order."""
    groups: Dict[tuple, Tuple[List[Measurement], List[int]]] = {}
    for i, m in enumerate(ms):
        gk = (("chiplet",) if m.kind == "chiplet_matmul"
              else _system_group_key(m))
        groups.setdefault(gk, ([], []))
        groups[gk][0].append(m)
        groups[gk][1].append(i)
    preds = []
    for gk, (gms, idx) in groups.items():
        if gk[0] == "chiplet":
            preds.append(_chiplet_predictor(gms, idx))
        else:
            preds.append(_system_predictor(gms, idx))
    n = len(ms)

    def predict(tech):
        out = jnp.zeros((n,), F)
        for p in preds:
            ib, vb = p(tech)
            out = out.at[ib].set(vb)
        return out

    return predict


def _tech_with(tech0: TechConstants, theta: Dict[str, jnp.ndarray]
               ) -> TechConstants:
    return dataclasses.replace(
        tech0, **{k: jnp.exp(v) for k, v in theta.items()})


def predict(ms: Sequence[Measurement],
            tech: TechConstants = DEFAULT_TECH) -> np.ndarray:
    """Model predictions for a measurement list under ``tech`` (n,)."""
    return np.asarray(_build_predictor(ms)(tech))


def error_report(ms: Sequence[Measurement],
                 tech: TechConstants = DEFAULT_TECH,
                 pred: Optional[np.ndarray] = None) -> Dict[str, float]:
    """Per-metric mean relative error |pred - meas| / meas, plus ``mean``."""
    if not ms:
        return {}
    p = predict(ms, tech) if pred is None else np.asarray(pred)
    meas = np.asarray([m.value for m in ms])
    rel = np.abs(p - meas) / meas
    out = {}
    for metric in sorted({m.metric for m in ms}):
        sel = np.asarray([m.metric == metric for m in ms])
        out[metric] = float(np.mean(rel[sel]))
    out["mean"] = float(np.mean(rel))
    return out


# ---------------------------------------------------------------------------
# the fit
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FitResult:
    """A completed calibration fit: fitted constants + provenance + errors."""
    tech: TechConstants
    tech0: TechConstants
    free: Tuple[str, ...]
    fitted: Dict[str, float]           # field -> fitted value
    errors: Dict[str, Dict[str, float]]  # split -> per-metric relative error
    loss: Tuple[float, float]          # (initial, final) train loss
    n_train: int
    n_holdout: int
    steps: int
    lr: float
    seed: int
    source_digest: str
    sources: Tuple[str, ...]

    @property
    def digest(self) -> str:
        return tech_key(self.tech)


def fit(measurements: Sequence[Measurement],
        free: Sequence[str] = DEFAULT_FREE,
        holdout: Optional[Sequence[Measurement]] = None,
        holdout_frac: float = 0.25,
        steps: int = 400,
        lr: float = 0.05,
        seed: int = 0,
        tech0: TechConstants = DEFAULT_TECH) -> FitResult:
    """Fit ``free`` TechConstants fields to ``measurements``.

    ``holdout`` pins an explicit held-out set (the bench_validation gate
    splits by shape); otherwise a deterministic ``holdout_frac`` split of
    ``measurements`` is used.  Returns a :class:`FitResult` whose ``errors``
    dict reports per-metric mean relative error for ``train_before/after``
    and ``holdout_before/after``.
    """
    free = tuple(free)
    bad = set(free) - set(FITTABLE_FIELDS)
    if bad:
        raise ValueError(f"non-whitelisted fit fields: {sorted(bad)}; "
                         f"allowed: {FITTABLE_FIELDS}")
    if not measurements:
        raise ValueError("no measurements")

    if holdout is None:
        rng = np.random.default_rng(seed)
        perm = rng.permutation(len(measurements))
        n_hold = int(round(len(measurements) * holdout_frac))
        hold_i = set(perm[:n_hold].tolist())
        train = [m for i, m in enumerate(measurements) if i not in hold_i]
        hold = [m for i, m in enumerate(measurements) if i in hold_i]
    else:
        train, hold = list(measurements), list(holdout)
    if not train:
        raise ValueError("empty training split")

    all_ms = train + hold
    with obs.span("calib.fit", n_train=len(train), n_holdout=len(hold),
                  free=",".join(free), steps=steps):
        predict_fn = _build_predictor(all_ms)
        meas = jnp.asarray([m.value for m in all_ms], F)
        n_train = len(train)

        theta0 = {f: jnp.log(jnp.maximum(
            jnp.asarray(getattr(tech0, f), F), _FLOOR)) for f in free}

        def loss_fn(theta):
            pred = predict_fn(_tech_with(tech0, theta))
            r = jnp.log(jnp.maximum(pred[:n_train], 1e-9)) \
                - jnp.log(meas[:n_train])
            return jnp.mean(r * r)

        b1, b2, eps = 0.9, 0.999, 1e-8
        m0 = jax.tree.map(jnp.zeros_like, theta0)
        v0 = jax.tree.map(jnp.zeros_like, theta0)

        def step(carry, t):
            th, m, v = carry
            lval, g = jax.value_and_grad(loss_fn)(th)
            m = jax.tree.map(lambda a, b_: b1 * a + (1 - b1) * b_, m, g)
            v = jax.tree.map(lambda a, b_: b2 * a + (1 - b2) * b_ ** 2, v, g)
            c1 = 1 - b1 ** (t + 1)
            c2 = 1 - b2 ** (t + 1)
            th = jax.tree.map(
                lambda w, mm, vv: w - lr * (mm / c1)
                / (jnp.sqrt(vv / c2) + eps), th, m, v)
            return (th, m, v), lval

        (theta, _, _), losses = jax.jit(lambda c: jax.lax.scan(
            step, c, jnp.arange(steps, dtype=F)))((theta0, m0, v0))

        fitted = {f: float(np.exp(np.asarray(theta[f]))) for f in free}
        tech_fit = dataclasses.replace(tech0, **fitted)

        pred0 = np.asarray(predict_fn(tech0))
        pred1 = np.asarray(predict_fn(tech_fit))
        errors = {
            "train_before": error_report(train, tech0, pred0[:n_train]),
            "train_after": error_report(train, tech_fit, pred1[:n_train]),
            "holdout_before": error_report(hold, tech0, pred0[n_train:]),
            "holdout_after": error_report(hold, tech_fit, pred1[n_train:]),
        }
        loss_i, loss_f = float(losses[0]), float(losses[-1])

        obs.inc("calib.fits")
        obs.observe("calib.fit_loss", loss_f)
        obs.observe("calib.error_before",
                    errors["train_before"].get("mean", 0.0))
        obs.observe("calib.error_after",
                    errors["train_after"].get("mean", 0.0))
        result = FitResult(
            tech=tech_fit, tech0=tech0, free=free, fitted=fitted,
            errors=errors, loss=(loss_i, loss_f),
            n_train=n_train, n_holdout=len(hold), steps=steps, lr=lr,
            seed=seed, source_digest=measurements_digest(all_ms),
            sources=tuple(sorted({m.source for m in all_ms})))
        obs.emit({"type": "calib_fit", "free": list(free),
                  "fitted": fitted, "errors": errors,
                  "loss": [loss_i, loss_f], "n_train": n_train,
                  "n_holdout": len(hold), "steps": steps, "lr": lr,
                  "seed": seed, "source_digest": result.source_digest,
                  "tech_digest": result.digest})
    return result


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    import os

    from ..compile_cache import enable as enable_compile_cache
    from .measurements import (baseline_measurements, load_report,
                               simulator_sweep)
    from .preset import CalibratedTech

    ap = argparse.ArgumentParser(
        prog="python -m repro.calib.fit",
        description="Fit TechConstants to measured ground truth.")
    ap.add_argument("--source", action="append", default=[],
                    help="'simulator', 'baselines', or a report path "
                         "(.csv/.json); repeatable; default: simulator")
    ap.add_argument("--free", action="append", default=[],
                    help="TechConstants field to fit; repeatable, each "
                         "occurrence may also be comma-separated "
                         f"(default: {','.join(DEFAULT_FREE)})")
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--holdout-frac", type=float, default=0.25)
    ap.add_argument("--name", default="calibrated",
                    help="preset name for the saved artifact")
    ap.add_argument("--out", default=os.environ.get("REPRO_CALIB_DIR",
                                                    "artifacts/calib"),
                    help="output directory for the CalibratedTech JSON")
    args = ap.parse_args(argv)

    enable_compile_cache()
    ms: List[Measurement] = []
    for src in (args.source or ["simulator"]):
        if src == "simulator":
            ms += simulator_sweep()
        elif src == "baselines":
            ms += baseline_measurements()
        else:
            ms += load_report(src)
    free = tuple(f.strip() for part in (args.free or [",".join(DEFAULT_FREE)])
                 for f in part.split(",") if f.strip())

    res = fit(ms, free=free, holdout_frac=args.holdout_frac,
              steps=args.steps, lr=args.lr, seed=args.seed)

    art = CalibratedTech.from_fit(args.name, res)
    path = art.save(args.out)

    print(f"fit: {len(ms)} measurements "
          f"({res.n_train} train / {res.n_holdout} held out), "
          f"free={','.join(free)}")
    for f, v in res.fitted.items():
        print(f"  {f}: {getattr(res.tech0, f)} -> {v:.6g}")
    for split in ("train", "holdout"):
        b = res.errors[f"{split}_before"].get("mean")
        a = res.errors[f"{split}_after"].get("mean")
        if b is not None:
            print(f"  {split}: mean rel err {b*100:.2f}% -> {a*100:.2f}%")
    print(f"saved: {path} (preset '{args.name}', digest "
          f"{art.digest[:12]})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
