"""Device time from a profiler trace, reduced by compiled program name.

``run.py`` traces its window with ``jax.profiler``; ``events`` reads the
resulting ``.xplane.pb`` into plain rows; ``reduce`` turns rows into the
numbers the per-layer readers take.  The reduction works on rows, so it
is tested on a small recorded trace without JAX.

A row is ``(plane, line, name, start_ns, dur_ns)``.  Device planes are
named ``/device:TPU:<n>``; on each, the ``module_line`` carries one
event per execution of a compiled program (named after the jitted
function), and the ``op_line`` one event per operation.  Busy time is the
union of the op events (module events when a trace has no op line);
``programs.json`` maps program names to the layer they belong to.
"""

from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

Row = Tuple[str, str, str, int, int]
TABLE = Path(__file__).resolve().parents[1] / "programs.json"


def load_table(path: Path = TABLE) -> Dict:
    return json.loads(Path(path).read_text())


def newest_xplane(logdir: Path) -> Path:
    found = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def events(path: Path) -> List[Row]:
    """Every event of every plane of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    rows = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name,
                             int(ev.start_ns), int(ev.duration_ns)))
    return rows


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        elif b > a:
            out.append((a, b))
    return out


def device_planes(rows: Sequence[Row], prefix: str) -> List[str]:
    return sorted({r[0] for r in rows if r[0].startswith(prefix)})


def layer_of(name: str, table: Dict) -> str:
    for layer, patterns in table["layers"].items():
        if any(re.search(p, name) for p in patterns):
            return layer
    return "other"


def reduce(rows: Sequence[Row], window_s: float, table: Dict) -> Dict:
    """Per device: busy seconds (union of op intervals) and the idle
    gaps between them; over all devices: device seconds per program
    layer, per operation name and per program.  ``busy_s`` is the mean
    over the devices; with no device plane it is 0."""
    planes = device_planes(rows, table["device_prefix"])
    busy, gaps = [], []
    per_layer: Dict[str, float] = {}
    per_program: Dict[str, float] = {}
    per_op: Dict[str, float] = {}
    for pl in planes:
        mine = [r for r in rows if r[0] == pl]
        ops = [r for r in mine if r[1] == table["op_line"]]
        mods = [r for r in mine if r[1] == table["module_line"]]
        spans = _union((r[3], r[3] + r[4]) for r in (ops or mods))
        busy.append(sum(b - a for a, b in spans) * 1e-9)
        gaps.extend((spans[i + 1][0] - spans[i][1], spans[i][1],
                     spans[i + 1][0]) for i in range(len(spans) - 1))
        for r in mods:
            per_program[r[2]] = per_program.get(r[2], 0.0) + r[4] * 1e-9
            lay = layer_of(r[2], table)
            per_layer[lay] = per_layer.get(lay, 0.0) + r[4] * 1e-9
        for r in ops:
            per_op[r[2]] = per_op.get(r[2], 0.0) + r[4] * 1e-9
    n = max(len(planes), 1)
    gaps.sort(reverse=True)
    return dict(devices=len(planes), window_s=float(window_s),
                busy_s=sum(busy) / n if planes else 0.0,
                layer_s={k: v / n for k, v in per_layer.items()},
                program_s={k: v / n for k, v in per_program.items()},
                op_s={k: v / n for k, v in per_op.items()},
                gaps=gaps[:10])


def host_doing(rows: Sequence[Row], t0: int, t1: int, table: Dict) -> str:
    """What the host was doing in a device gap [t0, t1): the name of the
    shortest host event that covers the gap's midpoint."""
    mid = (t0 + t1) // 2
    best = None
    for plane, _line, name, s, d in rows:
        if plane.startswith(table["host_prefix"]) and s <= mid < s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "unattributed"


def breakdown(rows: Sequence[Row], red: Dict, table: Dict) -> Dict:
    """The ten operations that took most device time and the ten longest
    idle gaps, each named by what the host was doing."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:10]
    gaps = [[host_doing(rows, a, b, table), g * 1e-9]
            for g, a, b in red["gaps"]]
    return dict(device_ops=[[k, v] for k, v in ops], idle_gaps=gaps)
