"""The plain reference model agrees with the system's evaluator on random
designs, and its lower-precision control (bfloat16) does not: at every
configuration's own widths the control's gap exceeds the configuration's
``eval_rel_err`` limit, and the program's stays under it."""

import sys
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import check  # noqa: E402
from harness import reference as R  # noqa: E402
from harness.layout import Layout  # noqa: E402
from harness.system import problem  # noqa: E402

LAYOUT = Layout(BENCH.parent)
CONFIGS = [c["name"] for c in LAYOUT.spec["configs"]]


def _designs_and_metrics(p, n, seed):
    import jax
    from repro.core.encoding import random_design
    from repro.core.evaluate import evaluate_system
    from repro.core.optimizer import metric_stack
    ds = jax.vmap(lambda k: random_design(k, p.space))(
        jax.random.split(jax.random.PRNGKey(seed), n))
    m = jax.jit(jax.vmap(lambda d: metric_stack(
        evaluate_system(p.spec, d))))(ds)
    ds = [{k: np.asarray(v[i]) for k, v in ds.items()} for i in range(n)]
    return ds, np.asarray(m, np.float64)


def _gap(a, b):
    return float(np.max(np.abs(a - b) / np.abs(b)))


@pytest.mark.parametrize("name", CONFIGS)
def test_program_within_limit_control_beyond(name):
    config = LAYOUT.config(name)
    seq = 1000
    p = problem(config, seq)
    spec = R.build_spec(*R.build_graph(config["graph"], seq),
                        ch_max=int(config["ch_max"]))
    designs, served = _designs_and_metrics(p, 12, 3)
    ref, low = R.Model(np.float64), R.Model(ml_dtypes.bfloat16)
    limit = float(config["limits"]["eval_rel_err"])
    prog = max(_gap(s, ref.evaluate(spec, d))
               for d, s in zip(designs, served))
    ctl = max(_gap(low.evaluate(spec, d), ref.evaluate(spec, d))
              for d in designs)
    assert prog < limit < ctl, (prog, limit, ctl)


def _rank_live(d):
    """``d`` with its live chiplets' placement entries replaced by their
    ranks: the one placement on which the system's network (node id =
    raw entry) and the paper's (node id = rank) agree."""
    n = int(np.sum(R.chiplets(d)))
    p = np.asarray(d["placement"]).copy()
    p[:n] = np.argsort(np.argsort(p[:n], kind="stable"), kind="stable")
    return dict(d, placement=p)


def _within_ch_max(d, ch_max):
    """``d`` with each chiplet array cut to fit ``ch_max`` per workload
    and the 36 network nodes in all."""
    s = np.asarray(d["shape"]).copy()
    per = min(ch_max, R.MAX_NODES // len(s))
    s[:, 4] = np.minimum(s[:, 4], 2)
    s[:, 5] = np.minimum(s[:, 5], max(per // 2, 1))
    return dict(d, shape=s)


@pytest.mark.parametrize("ch_max", [4, 36])
@pytest.mark.parametrize("builder", ["attention_block", "mlp_stack"])
def test_multichiplet_networks_agree_once_placed_by_rank(builder, ch_max):
    """Over many chiplets per workload the reference routes by the same
    network as the system wherever the two node numberings agree."""
    import jax
    from repro.core.evaluate import evaluate_system
    from repro.core.optimizer import metric_stack
    graph = next(LAYOUT.config(c)["graph"] for c in CONFIGS) \
        if builder == "attention_block" else \
        {"builder": "mlp_stack", "d_model": 5120, "d_ff": 1536}
    config = dict(graph=graph, ch_max=ch_max, max_total_pes=0,
                  objectives=["latency_ns", "energy_pj", "cost_usd"])
    p = problem(config, 700)
    spec = R.build_spec(*R.build_graph(graph, 700), ch_max=ch_max)
    designs, _ = _designs_and_metrics(p, 16, 9)
    designs = [_rank_live(_within_ch_max(d, ch_max)) for d in designs]
    f = jax.jit(lambda d: metric_stack(evaluate_system(p.spec, d)))
    ref = R.Model()
    gaps = [_gap(np.asarray(f(jax.tree.map(jax.numpy.asarray, d)),
                            np.float64), ref.evaluate(spec, d))
            for d in designs]
    assert max(gaps) < 1e-5, gaps


def test_outside_the_space_is_infeasible_and_unevaluated():
    config = dict(graph={"builder": "mlp_stack", "d_model": 64,
                         "d_ff": 128}, ch_max=4, max_total_pes=0,
                  objectives=["latency_ns"])
    p = problem(config, 64)
    spec = R.build_spec(*R.build_graph(config["graph"], 64), ch_max=4)
    (d,), _ = _designs_and_metrics(p, 1, 2)
    s = np.ones_like(d["shape"])
    s[0, 4:6] = (2, 3)                      # 6 chiplets, ch_max 4
    d = dict(d, shape=s)
    assert not R.feasible(spec, d, 0)
    with pytest.raises(R.OutsideSpace):
        R.Model().evaluate(spec, d)
    s[0, 4:6] = (2, 2)
    assert R.feasible(spec, dict(d, shape=s), 0)


def test_feasibility_matches_the_program():
    import jax
    from repro.core.encoding import feasibility_penalty
    config = LAYOUT.config(CONFIGS[0])
    p = problem(config, 300)
    spec = R.build_spec(*R.build_graph(config["graph"], 300),
                        ch_max=int(config["ch_max"]))
    designs, _ = _designs_and_metrics(p, 32, 5)
    for d in designs:
        pen = float(feasibility_penalty(
            p.space, jax.tree.map(jax.numpy.asarray, d), {}))
        assert R.feasible(spec, d, int(config["max_total_pes"])) == \
            (pen <= 1 + 1e-6)


def test_nondominated_rows():
    pts = np.asarray([[1, 3], [2, 2], [3, 1], [2, 3], [1, 3], [4, 4]],
                     float)
    nd = check.nondominated_rows(pts)
    assert sorted(map(tuple, nd)) == [(1, 3), (1, 3), (2, 2), (3, 1)]
    assert list(R.nondominated(pts)) == [True, True, True, False, True,
                                         False]
