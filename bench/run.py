#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
One process: name the devices (exit 1 without enough TPU chips), turn on
JAX's persistent compilation cache inside the checkout, build the seed's
stream of distinct cold design questions, and warm up on its first query
(the scan variant, the immigrant sampler and the archive insert that
every query of the cell shares).  Then a closed loop of one client sends
the next query as soon as its front is served, until ``--seconds`` have
passed and the query in flight completes.  With ``--trace 1`` the first
ten seconds of the window are traced with ``jax.profiler``, and the
per-layer metrics are read from that sub-window.  Afterwards a sample of
the served fronts is checked against the plain reference
(``harness/check.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
with ``--trace 1`` a ``breakdown``, and last the ``checks``: each
compared number with its limit.  They are also the last lines of stderr.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
WORK = ROOT / "artifacts" / "bench"      # caches and traces of runs
TRACE_S = 10.0                          # length of a traced sub-window
WARMUP = 1                              # queries sent before the window
SPANS = ("archive.save", "archive.load", "manifest.reload",
         "explore.checkpoint")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def span_totals(registry) -> dict:
    out = {}
    for name in SPANS:
        h = registry.peek(f"span.{name}")
        out[name] = (h.count, h.total) if h is not None else (0, 0.0)
    return out


def span_maxima(registry) -> dict:
    """The longest single duration of every ``repro.obs`` span so far."""
    return {k[len("span."):]: v["max"] for k, v in registry.snapshot().items()
            if k.startswith("span.") and v.get("max") is not None}


def quantile(xs, q: float) -> float:
    """The ``q`` quantile of ``xs`` (exclusive method, as
    ``statistics.quantiles``); the median for q = 0.5."""
    if q == 0.5:
        return statistics.median(xs)
    n = int(round(1 / (1 - q)))
    return statistics.quantiles(xs, n=n)[-1]


def main(argv=None, require_tpu: bool = True, control: bool = False) -> int:
    """One run of one cell.  ``control`` puts the reference at bfloat16
    in the program's place for the output check (``calibrate.py``); the
    benchmark's own runs never set it."""
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(WORK / "jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]
    import jax

    from harness.layout import Layout
    from harness.system import InsertLog

    lay = Layout(ROOT)
    cell = lay.cell(args.workload)
    config, traffic = lay.config(cell["config"]), lay.traffic(
        cell["traffic"])
    chips = int(cell["chips"])
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        log(f"needs {chips} TPU chip(s); JAX finds {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
        return 1
    log(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} cell={args.workload} seed={args.seed}")

    from repro.compile_cache import enable
    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    inserts = InsertLog().install()
    try:
        return _run(args, lay, config, traffic, chips, devs, inserts,
                    control)
    finally:
        inserts.uninstall()


def _run(args, lay, config, traffic, chips, devs, inserts, control) -> int:
    import jax

    from harness import check, devtrace, traffic as gen
    from harness.clock import CompileClock, GcClock
    from harness.reference import Model
    from harness.system import Client
    from repro import obs

    dev = devs[0]
    clock, gc_clock = CompileClock(), GcClock()
    run_dir = WORK / "runs" / args.workload
    client = Client(config, traffic, run_dir / "explore", inserts)
    stream = gen.stream(config, traffic, args.seed)
    n_warm = WARMUP

    clock.start()
    for qs in stream[:n_warm]:
        rec = client.ask(client.query(qs.seq), qs.key)
        if "error" in rec:
            log(f"warm-up query failed: {rec['error']}")
            return 1
    clock.stop()
    compile_s = clock.seconds()
    log(f"warm-up: {n_warm} query, compile "
        f"{compile_s:.3f} s, {clock.compiles} backend compiles, "
        f"{time.perf_counter() - T_START:.3f} s since start")

    trace_dir = run_dir / "trace"
    spans0, maxima0 = span_totals(obs.REGISTRY), span_maxima(obs.REGISTRY)
    clock.start()
    gc_clock.start()
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    traced = []
    if args.trace:
        # a traced sub-window at the start of the window, without Python
        # function events, which would slow the host it is to observe
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        traced = client.closed_loop(stream[n_warm:], t0,
                                    min(args.seconds, TRACE_S))
        jax.profiler.stop_trace()
        trace_s = traced[-1]["t1"] - t0
        spans_traced = span_totals(obs.REGISTRY)
    records = list(traced)
    if not records or records[-1]["t1"] - t0 < args.seconds:
        records += client.closed_loop(stream[n_warm + len(records):], t0,
                                      args.seconds)
    t_end = records[-1]["t1"]
    clock.stop()
    gc_clock.stop()
    window_s = t_end - t0
    log(f"window: {len(records)} queries in {window_s:.3f} s; "
        f"inside it {clock.compiles} backend compiles, "
        f"{clock.lowerings} lowerings, {clock.traces} jaxpr traces; "
        f"garbage collector: {gc_clock.summary()}")
    slow = max(records, key=lambda r: r["t1"] - r["t0"])
    gaps = [b["t0"] - a["t1"] for a, b in zip(records, records[1:])]
    rose = sorted(((v, k) for k, v in span_maxima(obs.REGISTRY).items()
                   if v > maxima0.get(k, 0.0)), reverse=True)
    log("spans whose longest duration rose in the window: " + (", ".join(
        f"{k}={v:.3f} s" for v, k in rose[:6]) or "none"))
    log(f"slowest query: seq={slow['seq']} "
        f"{slow['t1'] - slow['t0']:.3f} s at {slow['t0'] - t0:.3f} s "
        f"into the window; longest gap between queries "
        f"{max(gaps, default=0.0):.4f} s")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:chips])

    done = [r for r in records if "result" in r]
    failed = len(records) - len(done)
    for r in records:
        if "error" in r:
            log(f"query seq={r['seq']} failed: {r['error']}")
    evals = sum(r["n_evals"] for r in done)
    lat = [r["t1"] - r["t0"] for r in done]

    metrics, device = {}, dict(platform=dev.platform, kind=dev.device_kind,
                               count=len(devs), memory_peak_bytes=int(peak))
    breakdown = None
    e2e = {m["name"]: m for m in lay.end_to_end(args.workload)}
    if not args.trace:
        values = dict(setup_s=setup_s,
                      evals_per_s=evals / window_s if window_s > 0 else 0.0)
        for name in e2e:
            if name.startswith("front_p") and lat:
                q = float(name[len("front_p"):-len("_s")]) / 100
                values[name] = quantile(lat, q) if len(lat) > 1 else lat[0]
        metrics = {k: dict(value=values[k], unit=m["unit"])
                   for k, m in e2e.items() if k in values}
    else:
        table = devtrace.load_table()
        rows = devtrace.events(devtrace.newest_xplane(trace_dir))
        red = devtrace.reduce(rows, trace_s, table)
        breakdown = devtrace.breakdown(rows, red, table)
        device.update(busy_s=red["busy_s"], window_s=trace_s)
        log(f"traced {len(traced)} queries in {trace_s:.3f} s; device "
            "seconds by program: " + ", ".join(
                f"{k}={v:.6f}" for k, v in sorted(
                    red["program_s"].items(), key=lambda kv: -kv[1])[:8]))
        ok = [r for r in traced if "result" in r]
        run = types.SimpleNamespace(
            trace=red, window_s=trace_s, queries=len(ok),
            evals=sum(r["n_evals"] for r in ok),
            segments=sum(len(r["inserts"]) for r in ok),
            compile_s=compile_s,
            spans={k: (spans_traced[k][0] - spans0[k][0],
                       spans_traced[k][1] - spans0[k][1]) for k in SPANS})
        for m in lay.per_layer(args.workload):
            v = lay.read_metric(m["name"], run)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])

    client.close()
    sample = gen.check_sample(lat, int(traffic["check_queries"]), args.seed)
    t_check = time.perf_counter()
    served = None
    if control:
        import ml_dtypes
        served = check.control_metrics(Model(ml_dtypes.bfloat16), config)
    nums = check.compare(done, sample, config, client.cache_dir, Model(),
                         rerun=client.rerunner(run_dir), served=served)
    lim = check.limits(config, nums)
    correct = check.verdict(nums, lim) and failed == 0
    log(f"check of {nums['checked_queries']} queries, "
        f"{nums['checked_designs']} designs, "
        f"{nums['pruned_queries']} pruned: "
        f"{time.perf_counter() - t_check:.3f} s")
    checks = {k: dict(value=nums[k], limit=lim[k]) for k in lim}
    out = dict(correct=bool(correct), attempted=len(records), failed=failed,
               metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
