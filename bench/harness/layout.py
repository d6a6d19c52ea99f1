"""Where the benchmark's data lives, found by name.

``BENCHMARK.json`` at the checkout's root names the cells.  Each cell
names a configuration and a traffic mix; each per-layer metric names a
reader.  Their files sit under ``bench/``:

    bench/configs/<config>.json     sizes, design space, objectives
    bench/traffic/<traffic>.json    entry point, budget, checked queries
    bench/metrics/<metric>.py       ``read(run) -> float | None``
    bench/graphs/<builder>.py       the reference's workload graph of the
                                    configurations with that ``builder``:
                                    ``build(seq, **widths) -> (nests,
                                    edges)``, optionally ``program_cfg``
                                    (``reference.graph_module``)

A later cell, metric or graph builder is a new file; nothing here
changes for it.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]


def load_module(path: Path, name: str) -> ModuleType:
    """The Python file at ``path``, executed as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Layout:
    """The benchmark files of one checkout rooted at ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> Dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def end_to_end(self, cell: str) -> List[Dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[Dict]:
        """The per-layer metrics this cell reports: those that list it,
        and those without a list whose end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str):
        """The ``read`` function of ``bench/metrics/<metric>.py``."""
        return load_module(self.bench / "metrics" / f"{metric}.py",
                           f"bench_metric_{metric.replace('.', '_')}").read

    def read_metric(self, metric: str, run) -> Optional[float]:
        return self.reader(metric)(run)
