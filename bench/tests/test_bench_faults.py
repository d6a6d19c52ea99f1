"""A whole run of the harness on the CPU, at a tiny size, with the timed
path intact and with it broken underneath: the output check passes the
sound runs and fails each planted fault (an evaluated metric altered
where it is computed, a dominated design served, half of each archive
insert left out, a job's served answer altered) and the control (the
reference at bfloat16 in the program's place)."""

import importlib.util
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY = {"graph": {"builder": "mlp_stack", "d_model": 64, "d_ff": 128},
        "seq": [32, 160], "ch_max": 1, "max_shape": [16, 16, 4, 4, 1, 1],
        "max_total_pes": 0,
        "objectives": ["latency_ns", "energy_pj", "cost_usd"],
        "pop": 16, "archive": 32, "precision": "float32",
        "limits": {"eval_rel_err": 1e-04}}
MIX = {"budget": 256, "check_queries": 4}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY))
    (root / "bench/traffic/tiny_sweep.json").write_text(
        json.dumps(dict(MIX, entry="session")))
    (root / "bench/traffic/tiny_jobs.json").write_text(
        json.dumps(dict(MIX, entry="executor", workers=1)))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [dict(name="tiny", source="x", reduced=[], why="x",
                            file="bench/configs/tiny.json")]
    spec["workloads"] = [
        dict(name="tiny.sweep", config="tiny", traffic="tiny_sweep",
             chips=1, why="x"),
        dict(name="tiny.jobs", config="tiny", traffic="tiny_jobs",
             chips=1, why="x")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.sweep", "tiny.jobs"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    mod_spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", root / "bench/run.py")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def run(bench, capsys, monkeypatch):
    from repro.explore import nsga
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(bench.WORK / "jax_cache"))

    def go(cell, seed, control=False):
        assert bench.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", "2", "--trace", "0"],
                          require_tpu=False, control=control) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    yield go
    nsga._NSGA_CACHE.clear()        # no planted fault outlives its test


@pytest.mark.parametrize("cell", ["tiny.sweep", "tiny.jobs"])
def test_sound_run_is_correct(run, cell):
    out = run(cell, 2 ** 31 + 5)
    assert out["correct"], out["checks"]
    assert out["checks"]["eval_rel_err"]["value"] <= 1e-04
    assert list(out)[-1] == "checks"
    assert out["metrics"]["evals_per_s"]["value"] > 0


def test_metric_altered_where_computed(run, monkeypatch):
    from repro.explore import nsga
    orig = nsga.evaluate_arrays

    def skewed(*a, **k):
        m = dict(orig(*a, **k))
        m["energy_pj"] = m["energy_pj"] * 1.001
        return m

    nsga._NSGA_CACHE.clear()
    monkeypatch.setattr(nsga, "evaluate_arrays", skewed)
    out = run("tiny.sweep", 21)
    assert not out["correct"]
    assert out["checks"]["eval_rel_err"]["value"] > 1e-04


def test_dominated_design_served(run, monkeypatch):
    from repro.explore import service
    monkeypatch.setattr(service, "pareto_front",
                        lambda pts: list(range(len(pts))))
    out = run("tiny.sweep", 22)
    assert not out["correct"]
    assert out["checks"]["dominated"]["value"] > 0


def test_half_of_each_insert_left_out(run, monkeypatch):
    import jax.numpy as jnp
    from repro.explore import archive
    orig = archive._archive_update

    def half(objs, valid, designs, new_objs, new_valid, new_designs):
        keep = jnp.arange(new_valid.shape[0]) < new_valid.shape[0] // 2
        return orig(objs, valid, designs, new_objs, new_valid & keep,
                    new_designs)

    monkeypatch.setattr(archive, "_archive_update", half)
    out = run("tiny.sweep", 23)
    assert not out["correct"]
    c = out["checks"]
    assert c["lost_extreme"]["value"] + c["front_diff"]["value"] > 0


def test_job_answer_altered(run, monkeypatch):
    from repro.serve import executor
    orig = executor.JobHandle._finish

    def finish(self, result):
        result.front_objs = np.asarray(result.front_objs) * 1.01
        orig(self, result)

    monkeypatch.setattr(executor.JobHandle, "_finish", finish)
    out = run("tiny.jobs", 24)
    assert not out["correct"]
    assert out["checks"]["job_diff"]["value"] > 0


@pytest.mark.parametrize("cell", ["tiny.sweep", "tiny.jobs"])
def test_control_is_not_correct(run, cell):
    out = run(cell, 25, control=True)
    assert not out["correct"]
    assert out["checks"]["eval_rel_err"]["value"] > 1e-04
