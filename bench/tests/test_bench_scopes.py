"""Per-layer time from op scopes and program spans: self time of nested
rows, scope paths with transform wrappers, the split of a program's op
time by scope, program-span self time and coverage, and the new
per-layer readers, which read nothing where the trace holds no scope or
span."""

import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import devtrace, scopes  # noqa: E402
from harness.layout import Layout  # noqa: E402

TABLE = devtrace.load_table()
DEV, HOST = "/device:TPU:0", "/host:CPU"
MOD, OP = TABLE["module_line"], TABLE["op_line"]
US = 1_000
RECORDED = BENCH / "tests" / "data" / "trace_slice_v5e_scopes.json"
NEW = ("dataflow_us_per_eval", "network_us_per_eval",
       "energy_cost_us_per_eval", "variation_us_per_eval",
       "selection_us_per_eval", "search_host_ms_per_query",
       "fetch_wait_ms_per_query", "jobstore_ms_per_query")
SPANS = ("session.submit", "explore.run_queries", "explore.refine_group",
         "explore.dispatch", "explore.fetch", "archive.insert",
         "archive.save", "explore.book", "serve.store")


def _device_rows():
    scan = "jit(run)/while/body/closed_call"
    return [
        (DEV, MOD, "jit_run(1)", 0, 100 * US, ""),
        # the generation loop holds everything else of the program
        (DEV, OP, "while.60", 0, 100 * US, "jit(run)/while"),
        (DEV, OP, "fusion.1", 0, 10 * US,
         f"{scan}/variation/vmap()/mutate/add"),
        (DEV, OP, "while.61", 10 * US, 50 * US,
         f"{scan}/vmap(vmap(network))/while"),
        (DEV, OP, "fusion.2", 12 * US, 40 * US,
         f"{scan}/vmap(vmap(network))/while/body/gather"),
        (DEV, OP, "fusion.3", 60 * US, 20 * US,
         f"{scan}/vmap(vmap(dataflow))/reduce_prod"),
        (DEV, OP, "fusion.4", 80 * US, 5 * US,
         f"{scan}/vmap(energy_cost)/mul"),
        (DEV, OP, "fusion.5", 85 * US, 10 * US,
         f"{scan}/selection/pallas_call"),
        (DEV, MOD, "jit__archive_update(2)", 200 * US, 10 * US, ""),
        (DEV, OP, "fusion.6", 200 * US, 6 * US,
         "jit(_archive_update)/dominance/pallas_call"),
        (DEV, OP, "sort.1", 206 * US, 3 * US,
         "jit(_archive_update)/crowding/sort"),
        (DEV, OP, "copy.1", 209 * US, 1 * US, ""),
    ]


def _host_rows():
    ms = 1_000_000
    main, work = "python", "repro-serve_0"
    return [
        (HOST, main, "session.submit", 0, 100 * ms, ""),
        (HOST, main, "explore.run_queries", 1 * ms, 90 * ms, ""),
        (HOST, main, "explore.refine_group", 2 * ms, 80 * ms, ""),
        (HOST, main, "explore.dispatch", 3 * ms, 10 * ms, ""),
        (HOST, main, "archive.insert", 13 * ms, 5 * ms, ""),
        (HOST, main, "explore.fetch", 18 * ms, 40 * ms, ""),
        (HOST, main, "explore.book", 60 * ms, 20 * ms, ""),
        (HOST, main, "archive.save", 62 * ms, 8 * ms, ""),
        (HOST, work, "serve.store", 200 * ms, 3 * ms, ""),
    ]


def test_self_time_of_nested_rows():
    # a loop holding two ops, one of which holds another; a sibling after
    rows = [(0, 100), (0, 30), (5, 10), (40, 50), (100, 7)]
    assert scopes.self_times(rows) == [20, 20, 10, 50, 7]
    # an op running past its parent's end is charged only its inside part
    assert scopes.self_times([(0, 10), (8, 5)]) == [8, 5]


def test_scope_paths():
    sc = scopes.SCOPES["scan"]
    assert scopes.scope_of("jit(run)/while/body/vmap(vmap(network))/add",
                           sc) == "network"
    assert scopes.scope_of("jit(run)/selection/vmap(dataflow)/x",
                           sc) == "dataflow"      # innermost wins
    assert scopes.scope_of("jit(run)/while/networks/add", sc) == \
        scopes.OUTSIDE                             # whole components only
    assert scopes.scope_of("", sc) == scopes.OUTSIDE


def _key(field, wire):
    return _varint((field << 3) | wire)


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from ``(field, value)`` pairs: an int is a
    varint, bytes or str a length-delimited field."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _key(f, 0) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _key(f, 2) + _varint(len(v)) + v
    return out


def test_op_names_from_the_programs_hlo():
    """The HLO proto a trace keeps per program (``/host:metadata``) gives
    each instruction's ``op_name``; a fusion without one takes the scope
    of the instructions fused into it."""
    def instr(name, op_name="", calls=()):
        fields = [(1, name), (2, "fusion" if calls else "add")]
        if op_name:
            fields.append((7, _msg((2, op_name))))
        packed = b"".join(_varint(c) for c in calls)
        return _msg(*fields, *([(38, packed)] if calls else []))

    fused = _msg((1, "fused_computation.1"), (5, 2),
                 (2, instr("param.1")),
                 (2, instr("scatter.1", "jit(run)/vmap(network)/scatter")),
                 (2, instr("select.1", "jit(run)/vmap(network)/select_n")),
                 (2, instr("add.9", "jit(run)/variation/add")))
    entry = _msg((1, "main"), (5, 1),
                 (2, instr("fusion.1220", calls=[2])),
                 (2, instr("fusion.7", "jit(run)/selection/sort", [2])),
                 (2, instr("while.60", "jit(run)/while")))
    proto = _msg((1, _msg((1, "jit_run"), (3, entry), (3, fused))))
    names = scopes.hlo_op_names(proto, scopes.SCOPES["scan"])
    assert names["fusion.1220"] == "network"    # two of three fused
    assert names["fusion.7"] == "jit(run)/selection/sort"   # its own
    assert names["while.60"] == "jit(run)/while"
    assert names["scatter.1"] == "jit(run)/vmap(network)/scatter"
    # ... read from where a trace keeps it
    stat_meta = _msg((1, 1), (2, _msg((1, 3), (2, "Hlo Proto"))))
    ev_meta = _msg((1, 9), (2, _msg((1, 9), (2, "jit_run(42)"),
                                    (5, _msg((1, 3), (6, proto))))))
    space = _msg((1, _msg((1, 1), (2, "/device:TPU:0"))),
                 (1, _msg((1, 2), (2, "/host:metadata"), (4, ev_meta),
                          (5, stat_meta))))
    assert scopes.xspace_hlo(space) == {"jit_run(42)": proto}


def _recorded():
    d = json.loads(RECORDED.read_text())
    lines = d["lines"]
    rows = [(lines[r[0]][0], lines[r[0]][1], r[1], r[2], r[3], "")
            for r in d["rows"]]
    names = {tuple(k.split("|")): d["op_names"][v]
             for k, v in d["instructions"].items()}
    return d, scopes.resolve(rows, names, TABLE)


def test_recorded_v5e_slice_by_scope():
    """One query of the sweep cell traced on a TPU v5 lite: the scan and
    the archive insert split by scope.  The expected values were taken
    from a sweep over the op events' edges that gives each elementary
    interval to the latest-started op covering it."""
    d, rows = _recorded()
    dev = scopes.device_scopes(rows, TABLE)
    ns = {lay: {k: round(v * 1e9) for k, v in per.items()}
          for lay, per in dev.items()}
    assert ns["scan"] == {"network": 109894882, "variation": 3840363,
                          "selection": 2889215, "dataflow": 1346100,
                          "energy_cost": 27288, "outside": 731552}
    assert ns["insert"] == {"dominance": 1056026, "crowding": 607768,
                            "outside": 145530}
    # op self time adds up to the device time of each program layer
    red = devtrace.reduce([r[:5] for r in rows], d["window_ns"] * 1e-9,
                          TABLE)
    for layer in ("scan", "insert"):
        assert sum(dev[layer].values()) == pytest.approx(
            red["layer_s"][layer], abs=2e-6)
    assert dev["scan"]["outside"] / sum(dev["scan"].values()) < 0.10


def test_recorded_v5e_slice_host_spans():
    d, rows = _recorded()
    host = sorted({r[2] for r in rows if r[0].startswith("/host")})
    own = scopes.span_self(rows, host)
    assert own["explore.fetch"] == pytest.approx(0.120057868, abs=1e-9)
    assert own["explore.init_population"] == pytest.approx(0.101353918,
                                                           abs=1e-9)
    assert own["archive.save"] == pytest.approx(0.00700429, abs=1e-9)
    root, share = scopes.coverage(rows, host)
    assert root == "session.submit" and share == pytest.approx(
        0.98487055, abs=1e-8)
    # the longest idle gap of the device falls in a program span
    red = devtrace.reduce([r[:5] for r in rows], d["window_ns"] * 1e-9,
                          TABLE)
    bd = devtrace.breakdown([r[:5] for r in rows], red, TABLE)
    assert bd["idle_gaps"][0][0] == "archive.save"
    g, a, b = red["gaps"][0]
    assert scopes.gap_span(rows, host, a, b) == "archive.save"
    assert scopes.gap_span(rows, host, -10, -2) == "none"


def test_device_time_split_by_scope():
    dev = scopes.device_scopes(_device_rows(), TABLE)
    scan = {k: round(v * 1e9) for k, v in dev["scan"].items()}
    assert scan == {"outside": 5 * US, "variation": 10 * US,
                    "network": 50 * US, "dataflow": 20 * US,
                    "energy_cost": 5 * US, "selection": 10 * US}
    ins = {k: round(v * 1e9) for k, v in dev["insert"].items()}
    assert ins == {"dominance": 6 * US, "crowding": 3 * US,
                   "outside": 1 * US}
    # the scopes and what lies outside them add up to the busy time
    red = devtrace.reduce([r[:5] for r in _device_rows()], 1.0, TABLE)
    assert sum(dev["scan"].values()) + sum(dev["insert"].values()) == \
        pytest.approx(red["busy_s"])


def test_span_self_time_and_coverage():
    own = scopes.span_self(_host_rows(), SPANS)
    ms = {k: round(v * 1e3, 6) for k, v in own.items()}
    assert ms["explore.book"] == 12 and ms["archive.save"] == 8
    assert ms["explore.refine_group"] == 80 - 10 - 5 - 40 - 20
    assert ms["session.submit"] == 10 and ms["serve.store"] == 3
    root, share = scopes.coverage(_host_rows(), SPANS)
    # dispatch, insert, fetch, book (with its save): 75 of 100 ms
    assert root == "session.submit" and share == pytest.approx(0.75)
    assert scopes.coverage(_host_rows()[-1:], SPANS) is None


def _run(**kw):
    red = devtrace.reduce([r[:5] for r in _device_rows()], 1.0, TABLE)
    return types.SimpleNamespace(**dict(dict(
        trace=red, window_s=1.0, queries=2, evals=10, segments=2,
        compile_s=1.0, spans={}), **kw))


@pytest.fixture
def traced(monkeypatch):
    """Readers over hand-made rows in place of a trace file."""
    def use(rows):
        scopes._CACHE.clear()
        monkeypatch.setattr(scopes, "rows_of", lambda run: rows)
        monkeypatch.setattr(scopes, "program_spans", lambda: SPANS)
    yield use
    scopes._CACHE.clear()


def test_new_readers_read_scopes_and_spans(traced):
    traced(_device_rows() + _host_rows())
    lay = Layout()
    got = {m: lay.read_metric(m, _run()) for m in NEW}
    assert got["network_us_per_eval"] == pytest.approx(5.0)     # 50 us/10
    assert got["dataflow_us_per_eval"] == pytest.approx(2.0)
    assert got["selection_us_per_eval"] == pytest.approx(1.0)
    # dispatch 10 + insert 5 + book 12 ms over 2 queries
    assert got["search_host_ms_per_query"] == pytest.approx(13.5)
    assert got["fetch_wait_ms_per_query"] == pytest.approx(20.0)
    assert got["jobstore_ms_per_query"] == pytest.approx(1.5)


@pytest.mark.parametrize("metric", NEW)
def test_new_readers_read_nothing_without_input(traced, metric):
    lay = Layout()
    # a trace of a program without scopes or spans: ops, no op names
    traced([r[:5] + ("",) for r in _device_rows()])
    assert lay.read_metric(metric, _run()) is None
    traced([])                                  # no trace rows at all
    assert lay.read_metric(metric, _run()) is None


def test_untraced_run_reads_no_file():
    scopes._CACHE.clear()
    assert scopes.rows_of(_run(trace=None)) == []


def test_traced_tiny_run_reads_program_spans(tmp_path, monkeypatch, capsys):
    """A whole ``--trace 1`` run on the CPU at a tiny size: the trace has
    no TPU plane, so the scope readers read nothing, and the program
    spans on the host plane give the host metrics."""
    import importlib.util
    import json
    import shutil
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/configs/tiny.json").write_text(json.dumps(dict(
        graph={"builder": "mlp_stack", "d_model": 64, "d_ff": 128},
        seq=[32, 160], ch_max=1, max_shape=[16, 16, 4, 4, 1, 1],
        max_total_pes=0, objectives=["latency_ns", "energy_pj",
                                     "cost_usd"],
        pop=16, archive=32, precision="float32",
        limits={"eval_rel_err": 1e-04})))
    (root / "bench/traffic/tiny_jobs.json").write_text(json.dumps(dict(
        budget=256, check_queries=2, entry="executor", workers=1)))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"] = [dict(name="tiny", source="x", reduced=[], why="x",
                            file="bench/configs/tiny.json")]
    spec["workloads"] = [dict(name="tiny.jobs", config="tiny",
                              traffic="tiny_jobs", chips=1, why="x")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.jobs"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    mspec = importlib.util.spec_from_file_location(
        "bench_run_traced", root / "bench/run.py")
    bench = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(bench)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(bench.WORK / "jax_cache"))
    monkeypatch.setattr(scopes, "RUNS", bench.WORK / "runs")
    scopes._CACHE.clear()
    assert bench.main(["--workload", "tiny.jobs", "--seed", "7",
                       "--seconds", "2", "--trace", "1"],
                      require_tpu=False) == 0
    cap = capsys.readouterr()
    out = json.loads(cap.out.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    got = out["metrics"]
    for m in ("search_host_ms_per_query", "fetch_wait_ms_per_query",
              "jobstore_ms_per_query"):
        assert got.get(m, {}).get("value", 0) > 0, (m, cap.err[-3000:])
    assert not any(m in got for m in NEW[:5])
