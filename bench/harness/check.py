"""The output check: what the timed path served, against the reference.

For each sampled query of a run it compares three layers:

* evaluation — every served design re-evaluated by the plain reference
  model (``reference.py``, float64): the largest relative gap of a
  served metric (``eval_rel_err``), and designs served that break the
  design constraints or lie outside the design space (``infeasible``);
* archive insert and selection — against the metric rows the query's
  archive inserts were handed: served rows dominated by another served
  row (``dominated``), served rows that were never evaluated
  (``unevaluated``), objectives whose best evaluated value the front
  lost (``lost_extreme``), and, for queries whose nondominated set never
  outgrew the archive, rows by which the served front differs from the
  feasible nondominated set of everything evaluated (``front_diff``);
* service — the served front against the archive the service persisted
  (``disk_diff``) and, for jobs, against the same query answered by a
  fresh ``Session.submit`` (``job_diff``).

Every number but ``eval_rel_err`` counts faults and has the limit 0.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import reference as R

BIG = 1e30          # the archive's sentinel for an invalid row


def nondominated_rows(rows: np.ndarray) -> np.ndarray:
    """The rows of ``rows`` (all minimized) that no row dominates, found
    in one pass in lexicographic order (a row never dominates one that
    sorts before it); duplicates are all kept."""
    rows = np.asarray(rows, np.float64)
    if len(rows) == 0:
        return rows
    rows = rows[np.lexsort(rows.T[::-1])]
    keep: List[np.ndarray] = []
    acc = np.empty((0, rows.shape[1]))
    for r in rows:
        if len(acc) and np.any(np.all(acc <= r, axis=1)
                               & np.any(acc < r, axis=1)):
            continue
        keep.append(r)
        acc = np.asarray(keep)
    return acc


def _row_set(rows) -> set:
    return {np.asarray(r, np.float32).tobytes()
            for r in np.asarray(rows, np.float64)}


def evaluated_batches(inserts) -> List[np.ndarray]:
    """Per insert, the feasible finite metric rows it was handed."""
    out = []
    for objs, mask in inserts:
        o = np.asarray(objs, np.float32).reshape(-1, len(R.METRICS))
        m = (np.ones(len(o), bool) if mask is None
             else np.asarray(mask, bool).reshape(-1))
        ok = m & np.all(np.isfinite(o), axis=1) & np.all(o < BIG, axis=1)
        out.append(o[ok].astype(np.float64))
    return out


def selection(served_objs, served_metrics, batches, idx, capacity) -> Dict:
    """The archive-and-selection numbers of one query."""
    served_objs = np.asarray(served_objs, np.float64)
    ev = (np.concatenate(batches) if batches
          else np.empty((0, len(R.METRICS))))
    n_nd = int(np.sum(R.nondominated(served_objs)))
    out = dict(dominated=len(served_objs) - n_nd)
    have = _row_set(ev)
    out["unevaluated"] = sum(r not in have for r in _row_set(served_metrics))
    lost = 0
    for j, col in enumerate(idx):
        if len(ev) and (not len(served_objs) or np.float32(
                served_objs[:, j].min()) != np.float32(ev[:, col].min())):
            lost += 1
    out["lost_extreme"] = lost
    # without pruning the archive holds exactly the nondominated set of
    # everything inserted so far; then the served front is its projection
    front, pruned = np.empty((0, ev.shape[1])), False
    for b in batches:
        front = nondominated_rows(np.concatenate([front, b]))
        pruned |= len(front) > capacity
    out["front_diff"] = 0 if pruned else len(
        _row_set(nondominated_rows(front[:, idx])) ^ _row_set(served_objs))
    out["pruned"] = int(pruned)
    return out


def disk_front(cache_dir: Path, cache_key: str, idx) -> Optional[np.ndarray]:
    """The nondominated projection of the archive the service saved."""
    p = Path(cache_dir) / f"{cache_key}.npz"
    if not p.exists():
        return None
    with np.load(p) as z:
        rows = z["objs"][z["valid"]].astype(np.float64)[:, idx]
    return nondominated_rows(rows)


def control_metrics(model: R.Model, config: Dict):
    """A ``served`` function for ``compare`` that puts ``model`` (the
    reference at a lower precision: the control) in the program's place:
    the metrics of each served design as the control computes them."""
    def served(rec):
        spec = R.build_spec(*R.build_graph(config["graph"], rec["seq"]),
                            ch_max=int(config["ch_max"]))
        rows = []
        for d in rec["result"].front_designs:
            d = {k: np.asarray(v) for k, v in d.items()}
            try:
                rows.append(model.evaluate(spec, d))
            except R.OutsideSpace:
                rows.append(np.full(len(R.METRICS), np.nan))
        return np.asarray(rows, np.float64).reshape(-1, len(R.METRICS))
    return served


def compare(records: List[Dict], sample: List[int], config: Dict,
            cache_dir: Path, model: R.Model, rerun=None,
            served=None) -> Dict:
    """Every compared number over the sampled ``records``.  ``rerun``
    (executor entry) answers a query afresh through ``Session.submit``;
    ``served`` maps a record to the metric rows it served (by default
    the program's own)."""
    objectives = tuple(config["objectives"])
    idx = [R.METRICS.index(o) for o in objectives]
    nums = dict(eval_rel_err=0.0, infeasible=0, dominated=0, unevaluated=0,
                lost_extreme=0, front_diff=0, disk_diff=0)
    if rerun is not None:
        nums["job_diff"] = 0
    n_designs, pruned = 0, 0
    for i in sample:
        rec = records[i]
        r, seq = rec["result"], rec["seq"]
        spec = R.build_spec(*R.build_graph(config["graph"], seq),
                            ch_max=int(config["ch_max"]))
        rows = (np.asarray(r.front_metrics, np.float64) if served is None
                else served(rec))
        front_objs = rows[:, idx] if served is not None else r.front_objs
        for d, row in zip(r.front_designs, rows):
            d = {k: np.asarray(v) for k, v in d.items()}
            n_designs += 1
            if not R.feasible(spec, d, int(config["max_total_pes"])):
                nums["infeasible"] += 1
                continue
            ref = model.evaluate(spec, d)
            err = np.max(np.abs(row - ref) / np.maximum(np.abs(ref),
                                                        1e-300))
            err = float(err) if np.isfinite(err) else float("inf")
            nums["eval_rel_err"] = max(nums["eval_rel_err"], err)
        sel = selection(front_objs, rows, evaluated_batches(rec["inserts"]),
                        idx, int(config["archive"]))
        pruned += sel.pop("pruned")
        for k, v in sel.items():
            nums[k] += v
        disk = disk_front(cache_dir, r.provenance.cache_key, idx)
        nums["disk_diff"] += (len(front_objs) + 1 if disk is None else
                              len(_row_set(disk) ^ _row_set(front_objs)))
        if rerun is not None:
            r2 = rerun(rec)
            nums["job_diff"] += len(_row_set(r2.front_objs)
                                    ^ _row_set(front_objs)) + int(
                r2.provenance.n_evals_run != r.provenance.n_evals_run)
    nums["checked_queries"] = len(sample)
    nums["checked_designs"] = n_designs
    nums["pruned_queries"] = pruned
    return nums


def limits(config: Dict, nums: Dict) -> Dict:
    """The limit of each compared number."""
    out = {k: 0 for k in nums
           if k not in ("checked_queries", "checked_designs",
                        "pruned_queries")}
    out["eval_rel_err"] = float(config["limits"]["eval_rel_err"])
    return out


def verdict(nums: Dict, lim: Dict) -> bool:
    return nums["checked_queries"] > 0 and nums["checked_designs"] > 0 \
        and all(nums[k] <= v for k, v in lim.items())
