"""The harness finds configurations, traffic mixes and per-layer metrics
by name, so a later cell or metric is a new file and no edit; without a
TPU the entry point exits non-zero and prints no result."""

import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness.layout import Layout  # noqa: E402


def _tree(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_new_files_are_found_by_name(tmp_path):
    root = _tree(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench/configs/new_cfg.json").write_text(json.dumps(
        {"graph": {"builder": "mlp_stack", "d_model": 64, "d_ff": 128}}))
    (root / "bench/traffic/new_mix.json").write_text(json.dumps(
        {"entry": "session", "budget": 64}))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return run.evals / 2\n")
    spec["configs"].append(dict(name="new_cfg", source="x",
                                file="bench/configs/new_cfg.json",
                                reduced=[], why="x"))
    spec["workloads"].append(dict(name="new_cfg.mix", config="new_cfg",
                                  traffic="new_mix", chips=1, why="x"))
    spec["per_layer"].append(dict(name="new_metric", unit="x",
                                  better="lower", source="host_clock",
                                  layer="scan", moves="evals_per_s",
                                  workloads=["new_cfg.mix"]))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    lay = Layout(root)
    assert lay.config("new_cfg")["graph"]["d_model"] == 64
    assert lay.traffic(lay.cell("new_cfg.mix")["traffic"])["budget"] == 64
    assert [m["name"] for m in lay.per_layer("new_cfg.mix")] == \
        ["new_metric"]
    assert lay.read_metric("new_metric", types.SimpleNamespace(evals=8)) \
        == 4
    assert {m["name"] for m in lay.end_to_end("new_cfg.mix")} >= \
        {"setup_s", "evals_per_s"}
    # the files that were there are unchanged
    assert all(p.read_bytes() == b for p, b in before.items())


def test_every_listed_file_exists():
    lay = Layout(BENCH.parent)
    for w in lay.spec["workloads"]:
        lay.config(w["config"])
        lay.traffic(w["traffic"])
        assert lay.per_layer(w["name"])
    for m in lay.spec["per_layer"]:
        assert callable(lay.reader(m["name"]))


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    root = _tree(tmp_path)
    cell = Layout(root).spec["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(root / "bench/run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "{" not in p.stdout
    assert "TPU" in p.stderr
