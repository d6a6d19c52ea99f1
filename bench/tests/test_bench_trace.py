"""The reduction from trace rows to device busy time, idle share and
device time per compiled program, on hand-made rows and on a slice of a
trace recorded on a TPU v5 lite."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import devtrace  # noqa: E402

TABLE = devtrace.load_table()
DEV, HOST = "/device:TPU:0", "/host:CPU"
MOD, OP = TABLE["module_line"], TABLE["op_line"]
RECORDED = BENCH / "tests" / "data" / "trace_slice_v5e.json"


def _rows():
    ms = 1_000_000
    return [
        (DEV, MOD, "jit_run(1)", 0, 4 * ms),
        (DEV, OP, "fusion.1", 0, 3 * ms),
        (DEV, OP, "fusion.2", 2 * ms, 2 * ms),          # overlaps fusion.1
        (DEV, MOD, "jit__archive_update(2)", 6 * ms, 1 * ms),
        (DEV, OP, "fusion.3", 6 * ms, 1 * ms),
        (DEV, MOD, "jit_run(1)", 10 * ms, 2 * ms),
        (DEV, OP, "fusion.1", 10 * ms, 2 * ms),
        (HOST, "python", "bench.submit", 0, 12 * ms),
        (HOST, "python", "archive.save", 7 * ms, 2 * ms),
    ]


def test_busy_union_idle_and_programs():
    red = devtrace.reduce(_rows(), 0.012, TABLE)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(0.007)     # 0-4, 6-7, 10-12 ms
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(5 / 12)
    assert red["layer_s"]["scan"] == pytest.approx(0.006)
    assert red["layer_s"]["insert"] == pytest.approx(0.001)
    assert red["op_s"]["fusion.1"] == pytest.approx(0.005)
    assert [round(g * 1e-6) for g, _a, _b in red["gaps"]] == [3, 2]


def test_gaps_are_named_by_the_host():
    red = devtrace.reduce(_rows(), 0.012, TABLE)
    bd = devtrace.breakdown(_rows(), red, TABLE)
    assert bd["idle_gaps"][0] == ["archive.save", pytest.approx(0.003)]
    assert bd["idle_gaps"][1][0] == "bench.submit"
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(0.005)]


def test_means_over_devices():
    rows = _rows() + [("/device:TPU:1", OP, "fusion.9", 0, 1_000_000)]
    red = devtrace.reduce(rows, 0.012, TABLE)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((0.007 + 0.001) / 2)


def test_no_device_plane_reads_nothing():
    rows = [r for r in _rows() if r[0] == HOST]
    red = devtrace.reduce(rows, 0.012, TABLE)
    assert red["devices"] == 0 and red["busy_s"] == 0.0


def test_recorded_v5e_slice():
    """30 ms of a ``internlm2_attn.c4.sweep`` trace on a TPU v5 lite, from
    the end of one query's scan: the archive insert, a few small programs
    and the host's work before the next query.  The expected values were
    taken from a nanosecond timeline of the op events."""
    d = json.loads(RECORDED.read_text())
    rows = [tuple(r) for r in d["rows"]]
    red = devtrace.reduce(rows, d["window_ns"] * 1e-9, TABLE)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(0.002352651, abs=1e-12)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.9215783)
    assert red["layer_s"]["insert"] == pytest.approx(0.001810362, abs=1e-12)
    assert "scan" not in red["layer_s"]
    assert red["gaps"][0][0] == 23146555
    bd = devtrace.breakdown(rows, red, TABLE)
    assert bd["idle_gaps"][0][1] == pytest.approx(0.023146555)
