"""Per-layer time inside the compiled programs and on the host, from the
op scopes and program spans of a profiler trace.

The program names its layers twice.  Inside the compiled programs,
``jax.named_scope`` puts one path component per layer into every
operation's ``op_name`` metadata: ``dataflow``, ``network``,
``energy_cost``, ``variation`` and ``selection`` in the scan,
``dominance`` and ``crowding`` in the archive update.  On the host,
every ``repro.obs`` span is a profiler annotation of its name.  This
module reads both from the trace a ``--trace 1`` run leaves, and the
per-layer readers in ``bench/metrics`` take their numbers from
``readings``.

A row here is ``(plane, line, name, start_ns, dur_ns, op_name)``:
``op_name`` is the operation's scope path on a device's op line and
``""`` elsewhere.  Device time is *self* time: an operation's duration
less that of the operations nested in it on its line (a ``while``
contains its body's operations).  A program span's time is self time
too: its duration less that of the program spans nested in it.
"""

from __future__ import annotations

import bisect
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from . import devtrace

Row = Tuple[str, str, str, int, int, str]
ROOT = Path(__file__).resolve().parents[2]
RUNS = ROOT / "artifacts" / "bench" / "runs"   # where run.py traces

SCOPES = {"scan": ("dataflow", "network", "energy_cost", "variation",
                   "selection"),
          "insert": ("dominance", "crowding")}
OUTSIDE = "outside"
# host time of the search loop, and the device-to-host reads
SEARCH_SPANS = ("explore.open_group", "explore.init_population",
                "explore.seed", "explore.dispatch", "archive.insert",
                "explore.project", "explore.book")
FETCH_SPANS = ("explore.fetch",)
STORE_SPANS = ("serve.store",)
# spans that only enclose other spans; coverage counts the rest
CONTAINERS = ("serve.job", "session.submit", "explore.run_queries",
              "explore.refine_group", "explore.megabatch",
              "explore.reallocate")
ROOTS = ("serve.job", "session.submit")
_WRAPPED = re.compile(r"\w+\((.*)\)")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# reading a trace
# ---------------------------------------------------------------------------
def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, lo: int, hi: int):
    """The ``(field, value)`` pairs of one protobuf message in
    ``b[lo:hi]``: a varint's value, or a length-delimited field's
    ``(start, end)``."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def xspace_hlo(data: bytes) -> Dict[str, bytes]:
    """Each compiled program's serialized ``HloProto``, keyed by the
    program's name (its module event's name), from the ``/host:metadata``
    plane of one ``.xplane.pb``; ``ProfileData`` does not expose it.
    Fields of ``tsl/profiler/protobuf/xplane.proto``: XSpace.planes 1;
    XPlane name 2, event_metadata 4 (map entry value 2), stat_metadata 5
    (map entry value 2); XEventMetadata name 2, stats 5; XStatMetadata
    id 1, name 2; XStat metadata_id 1, bytes_value 6."""
    b = memoryview(data)
    out: Dict[str, bytes] = {}
    for f, plane in _fields(b, 0, len(b)):
        if f != 1:
            continue
        parts = list(_fields(b, *plane))
        if next((_text(b, v) for g, v in parts if g == 2), "") != \
                "/host:metadata":
            continue
        stat_ids = set()
        for g, v in parts:
            if g == 5:
                for h, w in _fields(b, *v):
                    meta = dict(_fields(b, *w)) if h == 2 else {}
                    if 2 in meta and _text(b, meta[2]) == "Hlo Proto":
                        stat_ids.add(meta.get(1, 0))
        for g, v in parts:
            if g != 4:
                continue
            for h, w in _fields(b, *v):
                if h != 2:
                    continue
                name, proto = "", None
                for k, x in _fields(b, *w):
                    if k == 2:
                        name = _text(b, x)
                    elif k == 5:
                        stat = dict(_fields(b, *x))
                        if stat.get(1) in stat_ids and 6 in stat:
                            proto = bytes(b[stat[6][0]:stat[6][1]])
                if name and proto is not None:
                    out[name] = proto
    return out


def hlo_op_names(hlo_proto: bytes, scopes: Sequence[str]) -> Dict[str, str]:
    """The ``op_name`` metadata of every instruction of one program, by
    instruction name.  An instruction that calls computations (a fusion,
    a loop) and whose own ``op_name`` names none of ``scopes`` takes the
    scope that most of the instructions it reaches name: the TPU compiler
    leaves some fusions without metadata (a scatter rewritten into a
    custom fusion), while the instructions fused into them keep theirs.
    Fields of ``xla/service/hlo.proto``: HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto instructions 2,
    id 5; HloInstructionProto name 1, metadata 7, called_computation_ids
    38; OpMetadata.op_name 2."""
    b = memoryview(hlo_proto)
    comps: Dict[int, List[Tuple[str, str, List[int]]]] = {}
    for f, module in _fields(b, 0, len(b)):
        if f != 1:
            continue
        for g, comp in _fields(b, *module):
            if g != 3:
                continue
            cid, instrs = 0, []
            for h, v in _fields(b, *comp):
                if h == 5:
                    cid = v
                elif h == 2:
                    name, op_name, calls = "", "", []
                    for k, x in _fields(b, *v):
                        if k == 1:
                            name = _text(b, x)
                        elif k == 7:
                            op_name = next((_text(b, y) for m, y in
                                            _fields(b, *x) if m == 2), "")
                        elif k == 38:
                            if isinstance(x, tuple):    # packed
                                i = x[0]
                                while i < x[1]:
                                    c, i = _varint(b, i)
                                    calls.append(c)
                            else:
                                calls.append(x)
                    instrs.append((name, op_name, calls))
            comps[cid] = instrs
    memo: Dict[int, Dict[str, int]] = {}

    def tally(cids) -> Dict[str, int]:
        """Instructions per scope in the computations ``cids`` and in
        those they call."""
        count: Dict[str, int] = {}
        for cid in cids:
            if cid not in memo:
                memo[cid] = {}         # a cycle counts nothing twice
                own: Dict[str, int] = {}
                for _name, op_name, calls in comps.get(cid, ()):
                    sc = scope_of(op_name, scopes)
                    if sc != OUTSIDE:
                        own[sc] = own.get(sc, 0) + 1
                    for k, v in tally(calls).items():
                        own[k] = own.get(k, 0) + v
                memo[cid] = own
            for k, v in memo[cid].items():
                count[k] = count.get(k, 0) + v
        return count

    out: Dict[str, str] = {}
    for instrs in comps.values():
        for name, op_name, calls in instrs:
            out[name] = op_name
            if calls and scope_of(op_name, scopes) == OUTSIDE:
                count = tally(calls)
                if count:
                    out[name] = max(sorted(count), key=count.get)
    return out


def events(path: Path, spans: Sequence[str], table: Dict
           ) -> Tuple[List[Row], Dict[Tuple[str, str], str]]:
    """The rows of one ``.xplane.pb`` this module reads, every event on a
    device plane and, on host planes, the events named in ``spans``
    (each host line, one thread, named ``<thread>#<index>``); and the op
    names of the instructions of the programs that carry scopes
    (``hlo_op_names``), keyed by (program, instruction).  Op rows carry
    the instruction's name; ``resolve`` gives them their op names."""
    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    names: Dict[Tuple[str, str], str] = {}
    for program, proto in xspace_hlo(data).items():
        scopes = SCOPES.get(devtrace.layer_of(program, table))
        if scopes:
            names.update({(program, k): v for k, v in
                          hlo_op_names(proto, scopes).items()})
    wanted = set(spans)
    rows: List[Row] = []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        device = plane.name.startswith(table["device_prefix"])
        if not device and not plane.name.startswith(table["host_prefix"]):
            continue
        for i, line in enumerate(plane.lines):
            ops = device and line.name == table["op_line"]
            # two host threads may share a name: number the lines
            host_line = f"{line.name}#{i}"
            for ev in line.events:
                if ops:     # the instruction's name, not its HLO text
                    rows.append((plane.name, line.name,
                                 ev.name.split(" ", 1)[0].lstrip("%"),
                                 int(ev.start_ns), int(ev.duration_ns), ""))
                elif device:
                    rows.append((plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns), ""))
                elif ev.name in wanted:
                    rows.append((plane.name, host_line, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns), ""))
    return rows, names


def _programs(rows: Sequence[Row], table: Dict):
    """Per device plane: its op rows, each with the name of the program
    whose module event holds the op's start (``None`` outside any)."""
    for pl in devtrace.device_planes(rows, table["device_prefix"]):
        mods = sorted((r[3], r[3] + r[4], r[2]) for r in rows
                      if r[0] == pl and r[1] == table["module_line"])
        starts = [m[0] for m in mods]
        ops = [r for r in rows if r[0] == pl and r[1] == table["op_line"]]
        progs = []
        for r in ops:
            k = bisect.bisect_right(starts, r[3]) - 1
            progs.append(mods[k][2] if k >= 0 and r[3] < mods[k][1]
                         else None)
        yield pl, ops, progs


def resolve(rows: Sequence[Row], names: Dict[Tuple[str, str], str],
            table: Dict) -> List[Row]:
    """``rows`` with every op row's op name filled in from ``names``, by
    the op's program and instruction."""
    named = {}
    for _pl, ops, progs in _programs(rows, table):
        for r, prog in zip(ops, progs):
            op_name = names.get((prog, r[2]))
            if op_name:
                named[id(r)] = r[:5] + (op_name,)
    return [named.get(id(r), r) for r in rows]


def scope_of(op_name: str, scopes: Sequence[str]) -> str:
    """The innermost of ``scopes`` that names a path component of
    ``op_name``, transform wrappers (``vmap(network)``) taken off;
    ``OUTSIDE`` when none does."""
    found = OUTSIDE
    for part in op_name.split("/"):
        while True:
            m = _WRAPPED.fullmatch(part)
            if not m:
                break
            part = m.group(1)
        if part in scopes:
            found = part
    return found


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def self_times(intervals: Sequence[Tuple[int, int]]) -> List[int]:
    """Self time of each interval ``(start, dur)`` of one line: the time
    in which it is the innermost interval running, the latest started of
    those that cover the instant.  For properly nested intervals that is
    its duration less the time of the intervals nested directly in it;
    where two overlap without nesting, the overlap counts once."""
    own = [0] * len(intervals)
    edges = []
    for i, (s, d) in enumerate(intervals):
        if d > 0:       # ends before starts at one instant; outer first
            edges += [(s, 1, -d, i), (s + d, 0, 0, i)]
    edges.sort()
    stack: List[int] = []
    alive = set()
    prev = None
    for t, start, _d, i in edges:
        while stack and stack[-1] not in alive:
            stack.pop()
        if stack:
            own[stack[-1]] += t - prev
        prev = t
        if start:
            stack.append(i)
            alive.add(i)
        else:
            alive.discard(i)
    return own


def device_scopes(rows: Sequence[Row], table: Dict) -> Dict[str, Dict]:
    """Seconds of op self time per program layer (``programs.json``) and
    per scope of that layer, the mean over device planes.  An op belongs
    to the program whose module event holds its start."""
    out: Dict[str, Dict[str, float]] = {}
    n = 0
    for _pl, ops, progs in _programs(rows, table):
        n += 1
        own = self_times([(r[3], r[4]) for r in ops])
        for r, prog, t in zip(ops, progs, own):
            if prog is None:
                continue
            layer = devtrace.layer_of(prog, table)
            scope = scope_of(r[5], SCOPES.get(layer, ()))
            per = out.setdefault(layer, {})
            per[scope] = per.get(scope, 0.0) + t * 1e-9
    n = max(n, 1)
    return {lay: {k: v / n for k, v in per.items()}
            for lay, per in out.items()}


def span_self(rows: Sequence[Row], names: Sequence[str]) -> Dict[str, float]:
    """Seconds of self time of every program span (host rows), by name:
    its duration less that of the program spans nested in it."""
    out: Dict[str, float] = {}
    lines = sorted({(r[0], r[1]) for r in rows if r[2] in names})
    for pl, ln in lines:
        mine = [r for r in rows if (r[0], r[1]) == (pl, ln)
                and r[2] in names]
        own = self_times([(r[3], r[4]) for r in mine])
        for r, t in zip(mine, own):
            out[r[2]] = out.get(r[2], 0.0) + t * 1e-9
    return out


def coverage(rows: Sequence[Row], names: Sequence[str]
             ) -> Optional[Tuple[str, float]]:
    """The share of the root spans' time (``serve.job`` where there is
    one, else ``session.submit``) covered by the program spans below the
    enclosing ones (``CONTAINERS``), on the root's own line."""
    for root in ROOTS:
        roots = [r for r in rows if r[2] == root]
        if roots:
            break
    else:
        return None
    total = covered = 0
    for pl, ln, _n, s, d, _o in roots:
        inner = devtrace._union(
            (max(r[3], s), min(r[3] + r[4], s + d)) for r in rows
            if (r[0], r[1]) == (pl, ln) and r[2] in names
            and r[2] not in CONTAINERS)
        total += d
        covered += sum(b - a for a, b in inner)
    return root, covered / total if total else 0.0


def gap_span(rows: Sequence[Row], names: Sequence[str], t0: int,
             t1: int) -> str:
    """The innermost program span running at the midpoint of the device
    gap ``[t0, t1)``: the shortest of those that cover it."""
    mid = (t0 + t1) // 2
    over = [r for r in rows if r[2] in names and r[3] <= mid < r[3] + r[4]]
    return min(over, key=lambda r: r[4])[2] if over else "none"


# ---------------------------------------------------------------------------
# what the readers take
# ---------------------------------------------------------------------------
def program_spans() -> Tuple[str, ...]:
    """Every span name the program has opened in this process."""
    from repro import obs
    return tuple(sorted(k[len("span."):] for k in obs.REGISTRY.snapshot()
                        if k.startswith("span.")))


def newest_trace() -> Optional[Path]:
    found = sorted(Path(RUNS).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def rows_of(run) -> List[Row]:
    """The rows of the trace ``run`` was reduced from: the newest trace
    under ``RUNS``, kept only if its device busy time is the one
    ``run.trace`` holds (else no rows)."""
    if run.trace is None:
        return []
    path = newest_trace()
    if path is None:
        return []
    key = (str(path), path.stat().st_mtime_ns)
    if _CACHE.get("path") != key:
        table = devtrace.load_table()
        rows, names = events(path, program_spans(), table)
        rows = resolve(rows, names, table)
        red = devtrace.reduce([r[:5] for r in rows], run.window_s, table)
        same = abs(red["busy_s"] - run.trace["busy_s"]) <= 1e-9
        _CACHE.clear()
        _CACHE.update(path=key, rows=rows if same else [])
    return _CACHE["rows"]


_CACHE: Dict = {}


def readings(run) -> Dict[str, float]:
    """The new per-layer numbers of one traced run, by metric name; a
    metric whose input the trace lacks is absent.  Logs the scan's and
    the insert's split by scope, the program spans' time per query and
    their coverage, once per run."""
    rows = rows_of(run)
    key = (_CACHE.get("path"), run.queries, run.evals)
    if _CACHE.get("readings_of") == key:
        return _CACHE["readings"]
    out: Dict[str, float] = {}
    dev = device_scopes(rows, devtrace.load_table())
    scan = dev.get("scan", {})
    if run.evals > 0:
        for scope in SCOPES["scan"]:
            if scan.get(scope, 0.0) > 0:
                out[f"{scope}_us_per_eval"] = 1e6 * scan[scope] / run.evals
    for layer, per in sorted(dev.items()):
        total = sum(per.values())
        if layer in SCOPES and total > per.get(OUTSIDE, 0.0):
            log(f"{layer} op self time {total:.6f} s: " + ", ".join(
                f"{k} {100 * v / total:.2f} %" for k, v in sorted(
                    per.items(), key=lambda kv: -kv[1]))
                + f"; outside the {layer} scopes "
                f"{100 * per.get(OUTSIDE, 0.0) / total:.2f} %")
    spans = program_spans()
    own = span_self(rows, spans)
    q = run.queries
    for name, group in (("search_host_ms_per_query", SEARCH_SPANS),
                        ("fetch_wait_ms_per_query", FETCH_SPANS),
                        ("jobstore_ms_per_query", STORE_SPANS)):
        total = sum(own.get(k, 0.0) for k in group)
        if total > 0 and q > 0:
            out[name] = 1e3 * total / q
    if own and q > 0:
        log("program span self time per query (ms): " + ", ".join(
            f"{k} {1e3 * v / q:.3f}" for k, v in sorted(
                own.items(), key=lambda kv: -kv[1])))
    cov = coverage(rows, spans)
    if cov is not None:
        log(f"program spans below the enclosing ones cover "
            f"{100 * cov[1]:.2f} % of {cov[0]}")
        log("ten longest idle gaps by the innermost program span over "
            "their midpoint: " + ", ".join(
                f"{gap_span(rows, spans, a, b)} {g * 1e-9:.4f} s"
                for g, a, b in run.trace["gaps"]))
    _CACHE.update(readings_of=key, readings=out)
    return out


def reading(run, name: str) -> Optional[float]:
    return readings(run).get(name)
