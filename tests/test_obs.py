"""The flight recorder (``repro.obs``): metrics registry semantics, span
nesting and the zero-cost disabled path, crash-safe journal writes and
truncated-tail reads, the event-stream invariants of instrumented
``Session.submit`` runs (monotone per-phase segment indices, strictly
increasing ``seq``, reallocation top-ups), bit-identical fronts with
observability on or off, journal replay against the in-memory ``Result``,
and the plan-vs-actual report."""

import json
import os
import threading
import warnings
from pathlib import Path

import numpy as np
import pytest

import repro.core as C
from repro import obs
from repro.api import Problem, Query, Session
from repro.core.optimizer import SAConfig
from repro.explore.nsga import NSGAConfig
from repro.explore.service import BudgetPolicy, SegmentEvent
from repro.obs.report import render

TINY = dict(max_shape=(16, 16, 4, 4, 1, 2))
OBJ = ("latency_ns", "cost_usd")


@pytest.fixture(autouse=True)
def _obs_restored():
    """Module-level obs state (enable flag, sinks) must never leak
    between tests — the registry is process-wide by design, so tests
    assert on deltas, not absolutes."""
    yield
    obs.enable()
    for s in list(obs.trace._SINKS):
        obs.remove_sink(s)


def _graph(k=64):
    return C.WorkloadGraph([C.matmul("mm", 512, 512, k)], [])


def _session(tmp_path, journal=False, **policy_kw):
    policy = BudgetPolicy(**policy_kw) if policy_kw else BudgetPolicy()
    return Session(cache_dir=tmp_path / "cache", journal=journal,
                   nsga=NSGAConfig(pop=8, generations=2), policy=policy)


def _problem(k=64):
    return Problem(_graph(k), objectives=OBJ, ch_max=2, space_kwargs=TINY)


def _counter(name):
    return obs.REGISTRY.counter(name).value


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    r = obs.MetricsRegistry()
    r.counter("c").inc().inc(4)
    assert r.counter("c").value == 5
    r.gauge("g").set(2.5)
    assert r.gauge("g").value == 2.5
    h = r.histogram("h")
    for v in range(100):
        h.observe(float(v))
    # exact order statistics while within reservoir capacity
    assert h.quantile(0.5) == 50.0
    assert h.quantiles() == {"p50": 50.0, "p90": 90.0, "p99": 99.0}
    assert h.mean == pytest.approx(49.5)
    assert (h.vmin, h.vmax, h.count) == (0.0, 99.0, 100)
    snap = r.snapshot()
    assert snap["c"] == {"kind": "counter", "value": 5}
    assert snap["h"]["p99"] == 99.0 and snap["h"]["count"] == 100
    json.dumps(snap)                    # snapshot is JSON-clean
    r.reset()
    assert r.snapshot() == {}


def test_histogram_reservoir_stays_bounded():
    r = obs.MetricsRegistry()
    h = r.histogram("h", capacity=32)
    for v in range(1000):
        h.observe(float(v))
    assert len(h._res) == 32 and h.count == 1000
    q = h.quantile(0.5)                 # estimate from a uniform sample
    assert 0.0 <= q <= 999.0


def test_metric_name_bound_to_kind():
    r = obs.MetricsRegistry()
    r.counter("x")
    with pytest.raises(TypeError, match="is a Counter"):
        r.histogram("x")


def test_registry_thread_safety():
    r = obs.MetricsRegistry()

    def work():
        for _ in range(500):
            r.counter("n").inc()
            r.histogram("h").observe(1.0)

    ts = [threading.Thread(target=work) for _ in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert r.counter("n").value == 2000
    assert r.histogram("h").count == 2000


# ---------------------------------------------------------------------------
# tracing core
# ---------------------------------------------------------------------------
def test_spans_nest_and_emit_records():
    recs = []
    with obs.sink_attached(recs.append):
        with obs.span("outer", k=1):
            with obs.span("inner"):
                pass
    inner, outer = recs
    assert inner["name"] == "inner" and inner["parent"] == "outer" \
        and inner["depth"] == 1
    assert outer["name"] == "outer" and outer["parent"] is None \
        and outer["attrs"] == {"k": 1}
    assert 0.0 <= inner["elapsed_s"] <= outer["elapsed_s"]
    # every close also feeds the span.<name> histogram
    assert obs.REGISTRY.histogram("span.inner").count >= 1


def test_disabled_is_a_shared_noop():
    obs.disable()
    try:
        assert obs.span("x") is obs.span("y") is obs.NOOP_SPAN
        assert not obs.active()
        before = obs.REGISTRY.counter("test.off").value
        obs.inc("test.off")             # gated: no count while disabled
        assert obs.REGISTRY.counter("test.off").value == before
        recs = []
        with obs.sink_attached(recs.append):
            obs.emit({"type": "x"})
        assert recs == []
    finally:
        obs.enable()


def test_failing_sink_is_dropped_not_fatal():
    def bad(rec):
        raise OSError("disk full")
    before = _counter("obs.sink_errors")
    with obs.sink_attached(bad):
        obs.emit({"type": "x"})         # drops the sink, counts the loss
        obs.emit({"type": "y"})         # no sink left: no second error
    assert _counter("obs.sink_errors") == before + 1


def test_sink_attached_is_reentrant():
    recs = []
    with obs.sink_attached(recs.append):
        with obs.sink_attached(recs.append):    # no double-attach
            obs.emit({"type": "x"})
        obs.emit({"type": "y"})         # still attached after inner exit
    assert [r["type"] for r in recs] == ["x", "y"]
    with obs.sink_attached(None):       # None is a no-op, not an error
        obs.emit({"type": "z"})
    assert len(recs) == 2


def test_sink_attached_refcounts_across_overlapping_scopes():
    # Two submissions sharing one fleet journal can overlap on different
    # threads; the first to finish must not detach the sink under the
    # one still running (this lost a cold run's records in bench_explore).
    recs = []
    a = obs.sink_attached(recs.append)
    b = obs.sink_attached(recs.append)
    a.__enter__()
    b.__enter__()
    a.__exit__(None, None, None)
    obs.emit({"type": "late"})          # b still holds a reference
    b.__exit__(None, None, None)
    obs.emit({"type": "gone"})          # last exit detached the sink
    assert [r["type"] for r in recs] == ["late"]


# ---------------------------------------------------------------------------
# journal: atomic lines, crash tolerance, replay
# ---------------------------------------------------------------------------
def test_journal_roundtrip_and_numpy_serialization(tmp_path):
    p = tmp_path / "j.jsonl"
    with obs.Journal(p) as j:
        j.write(dict(type="a", v=np.float32(1.5), arr=np.arange(3),
                     tup=(1, 2)))
        j.write(dict(type="b", n=np.int64(7)))
    recs = list(obs.read_journal(p))
    assert [r["type"] for r in recs] == ["a", "b"]
    assert recs[0]["arr"] == [0, 1, 2] and recs[0]["tup"] == [1, 2]
    assert recs[1]["n"] == 7
    assert all("t" in r for r in recs)


def test_journal_opens_lazily(tmp_path):
    j = obs.Journal(tmp_path / "lazy.jsonl")
    assert not j.path.exists()          # configuring costs nothing
    j.write({"type": "x"})
    assert j.path.exists()
    j.close()


def test_read_journal_tolerates_truncated_tail(tmp_path):
    p = tmp_path / "j.jsonl"
    with obs.Journal(p) as j:
        j.write({"type": "a"})
        j.write({"type": "b"})
    with open(p, "a") as f:
        f.write('{"type":"c","half')    # the line a crash leaves behind
    # an unterminated final line is the normal in-flight state of a LIVE
    # journal (or a crash tail) — skipped silently, so a reader polling
    # a journal under active append doesn't warn on every poll
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        recs = list(obs.read_journal(p))
    assert [r["type"] for r in recs] == ["a", "b"]
    # a COMPLETE final record the writer just hasn't newline-terminated
    # is salvaged, not dropped
    p2 = tmp_path / "j2.jsonl"
    with obs.Journal(p2) as j:
        j.write({"type": "a"})
    with open(p2, "a") as f:
        f.write('{"type":"c"}')         # complete JSON, no trailing newline
    assert [r["type"] for r in obs.read_journal(p2)] == ["a", "c"]


def test_read_journal_warns_on_midfile_garbage(tmp_path):
    p = tmp_path / "j.jsonl"
    p.write_text('{"type":"a"}\nnot json at all\n{"type":"b"}\n')
    with pytest.warns(UserWarning, match="unparseable"):
        recs = list(obs.read_journal(p))
    assert [r["type"] for r in recs] == ["a", "b"]


def test_read_journal_directory(tmp_path):
    for name in ("b.jsonl", "a.jsonl"):
        with obs.Journal(tmp_path / name) as j:
            j.write({"type": name})
    assert [r["type"] for r in obs.read_journal(tmp_path)] \
        == ["a.jsonl", "b.jsonl"]       # name order


def test_replay_folds_segments_and_results():
    recs = [
        dict(type="plan", key="k1", segments=[{}, {}]),
        dict(type="segment", key="k1", phase="refine", n_evals=64,
             elapsed_s=0.5, hv=[10.0]),
        dict(type="segment", key="k1", phase="realloc", n_evals=32,
             elapsed_s=0.25, hv=[12.0]),
        dict(type="result", key="k1", n_evals=96),
        dict(type="span", name="x"),    # keyless records are skipped
    ]
    r = obs.replay(recs)["k1"]
    assert r["segments"] == 2 and r["planned_segments"] == 2
    assert r["segments_by_phase"] == {"refine": 1, "realloc": 1}
    assert r["n_evals"] == 96 and r["final_hv"] == 12.0
    assert r["hv_path"] == [10.0, 12.0] and len(r["results"]) == 1


# ---------------------------------------------------------------------------
# instrumented runs: event-stream invariants
# ---------------------------------------------------------------------------
def test_segment_events_carry_timing_and_monotone_seq(tmp_path):
    s = _session(tmp_path, chunk_generations=2, adaptive=False)
    events = []
    r = s.submit(Query(_problem(), budget=32), on_segment=events.append)
    assert [e.segment for e in events] == [0, 1]
    assert [e.seq for e in events] == [0, 1]
    assert all(e.elapsed_s > 0.0 for e in events)
    # the streamed slices still reassemble into the run's full trace
    whole = events[0].trace.extend(events[1].trace)
    np.testing.assert_array_equal(whole.n_evals, r.trace.n_evals)
    np.testing.assert_allclose(whole.archive_hv, r.trace.archive_hv)


def test_realloc_events_restart_segment_but_not_seq(tmp_path):
    s = _session(tmp_path)
    # submission 1 banks ledger credit via an aggressive plateau policy
    bank = BudgetPolicy(chunk_generations=1, plateau_rel=10.0, patience=1,
                        reallocate=False)
    r1 = s.submit(Query(_problem(64), budget=128, policy=bank))
    assert r1.provenance.plateaued and r1.provenance.n_evals_banked > 0
    # submission 2 (cold problem, plateau impossible) exhausts its own
    # budget and receives a reallocation top-up from the banked credit
    spend = BudgetPolicy(chunk_generations=1, plateau_rel=0.0)
    events = []
    r2 = s.submit(Query(_problem(96), budget=16, policy=spend),
                  on_segment=events.append)
    assert r2.provenance.n_evals_realloc > 0
    phases = [e.phase for e in events]
    assert "refine" in phases and "realloc" in phases
    for phase in ("refine", "realloc"):
        idx = [e.segment for e in events if e.phase == phase]
        assert idx == list(range(len(idx)))     # 0,1,... per phase
    assert [e.seq for e in events] == list(range(len(events)))
    assert all(e.cache_key == r2.provenance.cache_key for e in events)


def test_callback_failure_names_phase_and_segment(tmp_path):
    s = _session(tmp_path, chunk_generations=2, adaptive=False)
    jp = tmp_path / "j.jsonl"
    s._journal = obs.resolve_journal(jp)

    def boom(e):
        raise RuntimeError("dashboard down")

    before = _counter("obs.on_segment_errors")
    with pytest.warns(UserWarning,
                      match=r"on_segment callback failed .*"
                            r"\(phase=refine, segment=0\)"):
        s.submit(Query(_problem(), budget=32), on_segment=boom)
    assert _counter("obs.on_segment_errors") == before + 2
    errs = [r for r in obs.read_journal(jp)
            if r["type"] == "callback_error"]
    assert len(errs) == 2 and errs[0]["phase"] == "refine"
    assert [e["segment"] for e in errs] == [0, 1]


def test_scalarized_completion_event_and_journal(tmp_path):
    jp = tmp_path / "j.jsonl"
    s = _session(tmp_path, journal=jp)
    spec = C.SystemSpec.build(_graph(), ch_max=2)
    space = C.DesignSpace(spec, **TINY)
    events = []
    s.submit(Query(Problem.from_spec(spec, space), engine="bo_sa",
                   weights=(1.0, 1.0, 0.0, 0.0),
                   engine_opts=dict(bo_fields=(), n_init=2,
                                    sa=SAConfig(steps=10, chains=2))),
             on_segment=events.append)
    assert len(events) == 1 and isinstance(events[0], SegmentEvent)
    assert events[0].phase == "bo_sa" and events[0].elapsed_s > 0.0
    recs = list(obs.read_journal(jp))
    segs = [r for r in recs if r["type"] == "segment"]
    assert len(segs) == 1 and segs[0]["phase"] == "bo_sa"
    plans = [r for r in recs if r["type"] == "plan"]
    assert plans and plans[0]["engine"] == "bo_sa"
    assert any(r["type"] == "result" and r["engine"] == "bo_sa"
               for r in recs)


# ---------------------------------------------------------------------------
# observability is free: identical results on or off
# ---------------------------------------------------------------------------
def test_fronts_bit_identical_with_obs_on_and_off(tmp_path):
    q = Query(_problem(), budget=32)
    events = []
    jp = tmp_path / "j.jsonl"
    s_on = _session(tmp_path / "on", journal=jp, chunk_generations=2,
                    adaptive=False)
    r_on = s_on.submit(q, on_segment=events.append)
    obs.disable()
    try:
        s_off = _session(tmp_path / "off", chunk_generations=2,
                         adaptive=False)
        r_off = s_off.submit(q)
    finally:
        obs.enable()
    # numeric state is untouched by instrumentation: bit-identical fronts
    assert r_on.front_metrics.tobytes() == r_off.front_metrics.tobytes()
    assert r_on.front_objs.tobytes() == r_off.front_objs.tobytes()
    np.testing.assert_array_equal(r_on.trace.archive_hv,
                                  r_off.trace.archive_hv)
    # ... and the disabled arm journaled nothing
    assert len(events) == 2 and jp.exists()


# ---------------------------------------------------------------------------
# journal replay + report against the in-memory result
# ---------------------------------------------------------------------------
def test_journal_replays_to_in_memory_result(tmp_path):
    jp = tmp_path / "j.jsonl"
    s = _session(tmp_path, journal=jp, chunk_generations=2, adaptive=False)
    r = s.submit(Query(_problem(), budget=32))
    ck = r.provenance.cache_key
    recs = list(obs.read_journal(jp))
    rp = obs.replay(recs)[ck]
    assert rp["segments"] == r.trace.archive_hv.shape[0]
    assert rp["n_evals"] == r.provenance.n_evals_run
    assert rp["final_hv"] == pytest.approx(
        float(r.trace.archive_hv[-1, 0]))
    assert rp["planned_segments"] == rp["segments"]
    report = render(recs)
    assert f"problem {ck}" in report
    assert "== fleet summary ==" in report
    assert "queries=1" in report
    # every planned segment shows an actual observation: the actual_s
    # column (token 5: phase seg pop gens plan_evals actual_s ...) is a
    # float, not the '-' an unobserved planned segment renders
    seg_rows = [ln for ln in report.splitlines()
                if ln.startswith("  refine")]
    assert len(seg_rows) == rp["segments"]
    assert all(float(row.split()[5]) > 0.0 for row in seg_rows)


def test_warm_hit_journals_plan_and_result_only(tmp_path):
    jp = tmp_path / "j.jsonl"
    s = _session(tmp_path, journal=jp)
    q = Query(_problem(), budget=16)
    hit0, miss0 = _counter("explore.cache.hit"), \
        _counter("explore.cache.miss")
    s.submit(q)
    r = s.submit(q)                     # identical query: warm serve
    assert r.provenance.from_cache
    assert _counter("explore.cache.hit") == hit0 + 1
    assert _counter("explore.cache.miss") == miss0 + 1
    rp = obs.replay(obs.read_journal(jp))[r.provenance.cache_key]
    assert len(rp["results"]) == 2
    assert rp["results"][1]["from_cache"] is True
    assert rp["plans"][-1]["cache_hit"] is True
    assert not rp["plans"][-1]["segments"]


# ---------------------------------------------------------------------------
# journal wiring: Session(journal=...), $REPRO_JOURNAL_DIR
# ---------------------------------------------------------------------------
def test_env_var_enables_default_journal(tmp_path, monkeypatch):
    monkeypatch.setenv(obs.JOURNAL_ENV, str(tmp_path / "fleet"))
    s = _session(tmp_path, journal=None)
    s.submit(Query(_problem(), budget=16))
    files = list((tmp_path / "fleet").glob("run-*.jsonl"))
    assert len(files) == 1
    assert any(r["type"] == "result" for r in obs.read_journal(files[0]))
    # journal=False opts out even with the env var set
    s2 = _session(tmp_path / "b", journal=False)
    s2.submit(Query(_problem(96), budget=16))
    recs = list(obs.read_journal(files[0]))
    assert all(r.get("key") != s2._cache_key(_problem(96))
               for r in recs if r["type"] == "result")


def test_report_cli_renders_journal(tmp_path, capsys):
    jp = tmp_path / "j.jsonl"
    s = _session(tmp_path, journal=jp, chunk_generations=2, adaptive=False)
    s.submit(Query(_problem(), budget=32))
    from repro.obs.report import main
    assert main([str(jp)]) == 0
    out = capsys.readouterr().out
    assert "== plan vs actual ==" in out and "refine" in out


# ---------------------------------------------------------------------------
# spans on the profiler's clock, named scopes inside the compiled programs
# ---------------------------------------------------------------------------
def _profiled_submit(tmp_path):
    """One tiny ``Session.submit`` under ``jax.profiler``: the host-thread
    events of the trace, ``(name, start_ns, end_ns)`` in start order."""
    import jax
    from jax.profiler import ProfileData
    q = Query(_problem(), budget=32)
    _session(tmp_path / "warm").submit(q)         # compile outside
    s = _session(tmp_path / "cold")
    with jax.profiler.trace(str(tmp_path / "trace")):
        s.submit(q)
    path = next((tmp_path / "trace").rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(path))
    return sorted(((ev.name, int(ev.start_ns),
                    int(ev.start_ns + ev.duration_ns))
                   for plane in pd.planes if plane.name.startswith("/host")
                   for line in plane.lines for ev in line.events),
                  key=lambda e: e[1])


def test_spans_are_profiler_trace_events(tmp_path):
    evs = _profiled_submit(tmp_path)

    def only(name):
        found = [e for e in evs if e[0] == name]
        assert len(found) == 1, (name, found)
        return found[0]

    def inside(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    submit = only("session.submit")
    group = only("explore.refine_group")
    dispatch = only("explore.dispatch")
    fetches = [e for e in evs if e[0] == "explore.fetch"]
    assert inside(group, submit)
    assert inside(dispatch, group)
    assert fetches and all(inside(f, group) for f in fetches)
    assert dispatch[2] <= fetches[0][1]     # dispatched, then read back
    # the sibling spans under the refinement never overlap each other
    leaves = sorted((e for e in evs if e[0] in (
        "explore.init_population", "explore.seed", "explore.dispatch",
        "archive.insert", "explore.fetch")), key=lambda e: e[1])
    assert all(a[2] <= b[1] for a, b in zip(leaves, leaves[1:]))


def test_spans_work_without_jax():
    """``repro.obs`` imports nothing of JAX; its spans still time and
    count where JAX cannot be imported."""
    import subprocess
    import sys
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro import obs\n"
            "assert 'jax' not in [m for m in sys.modules if sys.modules[m]]\n"
            "with obs.span('x'):\n    pass\n"
            "assert obs.REGISTRY.histogram('span.x').count == 1\n")
    src = str(Path(obs.__file__).resolve().parents[2])
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": src})
    assert r.returncode == 0, r.stderr


def test_disabled_spans_leave_no_trace_events(tmp_path):
    obs.disable()
    try:
        evs = _profiled_submit(tmp_path)
    finally:
        obs.enable()
    assert not [e for e in evs
                if e[0].startswith(("explore.", "session.", "archive."))]


def _scopes(text):
    """Every name-stack component of a lowered program's locations, with
    transform wrappers (``vmap(network)``) taken off."""
    import re
    out = set()
    for loc in re.findall(r'loc\("([^"]*/[^"]*)"', text):
        for part in loc.split("/"):
            while (m := re.fullmatch(r"\w+\((.*)\)", part)):
                part = m.group(1)
            out.add(part)
    return out


def test_compiled_programs_carry_named_scopes():
    """The scan and the archive update name the search's layers in their
    op metadata, each scope one path component."""
    import jax
    import jax.numpy as jnp
    from repro.explore.archive import _archive_update
    from repro.explore.nsga import (_NSGA_CACHE, _immigrants, make_nsga,
                                    sample_designs)
    p = _problem()
    cfg = NSGAConfig(pop=8, generations=2)
    make_nsga(p.spec, p.space, OBJ, cfg)
    jitted, n_imm, _ = next(v for k, v in _NSGA_CACHE.items()
                            if cfg in k and k[-1] is None)
    pop0 = sample_designs(jax.random.PRNGKey(0), p.space, cfg.pop)
    arr = {k: jnp.asarray(v) for k, v in p.spec.arrays.items()}
    imm = _immigrants(jax.random.PRNGKey(1), p.space, cfg, n_imm,
                      arr["loopmask"], arr["bounds"])
    scan = _scopes(jitted.lower(jax.random.PRNGKey(2), pop0, arr,
                                imm).as_text(debug_info=True))
    assert {"dataflow", "network", "energy_cost", "variation",
            "selection"} <= scan
    objs = jnp.zeros((16, 4), jnp.float32)
    valid = jnp.zeros(16, bool)
    designs = jax.tree.map(lambda x: x[:16], pop0) if cfg.pop >= 16 else \
        jax.tree.map(lambda x: jnp.concatenate([x, x]), pop0)
    upd = _scopes(_archive_update.lower(objs, valid, designs, objs, valid,
                                        designs).as_text(debug_info=True))
    assert {"dominance", "crowding"} <= upd


def test_job_store_spans_and_queue_wait(tmp_path):
    from repro.serve import Executor
    sess = _session(tmp_path)
    store0 = obs.REGISTRY.histogram("span.serve.store").count
    wait0 = obs.REGISTRY.histogram("serve.queue_wait_s").count
    ex = Executor(sess, store=tmp_path / "jobs", max_workers=1)
    try:
        ex.submit(Query(_problem(), budget=16), key=3).result(timeout=300)
    finally:
        ex.shutdown(wait=True)
    # create, claim, and the DONE update
    assert obs.REGISTRY.histogram("span.serve.store").count - store0 == 3
    assert obs.REGISTRY.histogram("serve.queue_wait_s").count - wait0 == 1
