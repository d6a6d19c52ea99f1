"""The traffic generator: one seed, one stream; no problem repeats; every
query of a cell compiles to the one scan variant the warm-up built."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import traffic as gen  # noqa: E402
from harness.layout import Layout  # noqa: E402
from harness.system import problem  # noqa: E402

LAYOUT = Layout(BENCH.parent)
CELLS = [w["name"] for w in LAYOUT.spec["workloads"]]


def _cell(name):
    w = LAYOUT.cell(name)
    return LAYOUT.config(w["config"]), LAYOUT.traffic(w["traffic"])


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_stream(cell):
    config, traffic = _cell(cell)
    seed = 2 ** 31 + 12345          # past 32 signed bits
    a, b = gen.stream(config, traffic, seed), gen.stream(config, traffic,
                                                         seed)
    assert a == b
    assert gen.stream(config, traffic, seed + 1) != a
    lo, hi = config["seq"]
    assert sorted(q.seq for q in a) == list(range(lo, hi + 1))


@pytest.mark.parametrize("cell", CELLS)
def test_no_problem_repeats(cell):
    config, traffic = _cell(cell)
    qs = gen.stream(config, traffic, 7)[:64]
    keys = {problem(config, q.seq).key() for q in qs}
    assert len(keys) == len(qs)


@pytest.mark.parametrize("cell", CELLS)
def test_one_scan_variant_per_cell(cell):
    from repro.core.constants import DEFAULT_TECH
    from repro.core.optimizer import METRIC_KEYS
    from repro.explore.nsga import _static_key
    config, traffic = _cell(cell)
    statics = set()
    for q in gen.stream(config, traffic, 11)[:24]:
        p = problem(config, q.seq)
        dims = (p.spec.W, p.spec.CH, p.spec.E)
        idx = tuple(METRIC_KEYS.index(o) for o in p.objectives)
        statics.add(_static_key(dims, idx, None, DEFAULT_TECH, p.space))
    assert len(statics) == 1


def test_check_sample_holds_the_longest():
    lat = [1.0] * 20
    lat[13] = 5.0
    s = gen.check_sample(lat, 6, 99)
    assert 13 in s and len(s) == 6 == len(set(s))
    assert s == gen.check_sample(lat, 6, 99)
    assert gen.check_sample([2.0], 6, 99) == [0]
    assert gen.check_sample([], 6, 99) == []
