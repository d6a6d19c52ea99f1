"""The system under test, driven through the entry points its users call.

``Client`` owns one ``repro.api.Session`` (and, for the ``executor``
entry, one ``repro.serve.Executor`` with a single worker) over a fresh
archive cache directory, and answers one query at a time: the closed
loop of one client.  ``InsertLog`` keeps, for the query in flight, what
each archive insert was handed (the evaluated metric rows and their
feasibility), so that the output check can tell whether the served
front is the feasible nondominated set of what the query evaluated.
It only holds references; it copies nothing while the window runs.
"""

from __future__ import annotations

import shutil
import time
import types
from pathlib import Path
from typing import Dict, List, Optional

from .reference import graph_module

JOB_TIMEOUT_S = 300.0       # a job not served by then counts as failed


def program_graph(config: Dict, seq: int):
    """The configuration's workload graph, built by the system's own
    graph builder at the configuration's widths (mapped to the builder's
    attributes by the graph file's ``program_cfg``, where it has one)."""
    from repro.core import presets
    g = dict(config["graph"])
    builder = g.pop("builder")
    to_cfg = getattr(graph_module(builder), "program_cfg", None)
    if to_cfg is not None:
        g = to_cfg(g)
    return getattr(presets, builder)(types.SimpleNamespace(**g), seq=seq)


def problem(config: Dict, seq: int):
    from repro.api import Problem
    return Problem(program_graph(config, seq),
                   objectives=tuple(config["objectives"]),
                   ch_max=int(config["ch_max"]),
                   space_kwargs=space_kwargs(config))


def space_kwargs(config: Dict) -> Dict:
    """The design-space bounds a configuration sets."""
    out = dict(max_total_pes=int(config["max_total_pes"]))
    if "max_shape" in config:
        out["max_shape"] = tuple(int(v) for v in config["max_shape"])
    return out


class InsertLog:
    """Records the arguments of every ``ParetoArchive.insert`` made while
    ``current`` is a list."""

    def __init__(self):
        self.current: Optional[List] = None
        self._orig = None

    def install(self) -> "InsertLog":
        from repro.explore.archive import ParetoArchive
        orig = self._orig = ParetoArchive.insert
        log = self

        def insert(arc, designs, objs, mask=None, count_evals=True):
            if log.current is not None:
                log.current.append((objs, mask))
            return orig(arc, designs, objs, mask=mask,
                        count_evals=count_evals)

        ParetoArchive.insert = insert
        return self

    def uninstall(self) -> None:
        if self._orig is not None:
            from repro.explore.archive import ParetoArchive
            ParetoArchive.insert = self._orig
            self._orig = None


class Client:
    """One closed-loop client of one cell."""

    def __init__(self, config: Dict, traffic: Dict, cache_dir: Path,
                 log: InsertLog):
        from repro.api import Session
        from repro.explore.nsga import NSGAConfig
        from repro.explore.service import ExplorationService
        self.config, self.traffic, self.log = config, traffic, log
        self.cache_dir = Path(cache_dir)
        shutil.rmtree(self.cache_dir, ignore_errors=True)
        self.cache_dir.mkdir(parents=True)
        self.session = Session(service=ExplorationService(
            cache_dir=self.cache_dir, capacity=int(config["archive"]),
            nsga=NSGAConfig(pop=int(config["pop"]))))
        self.executor = None
        if traffic["entry"] == "executor":
            from repro.serve import Executor
            self.executor = Executor(self.session,
                                     max_workers=int(traffic["workers"]))

    def query(self, seq: int):
        from repro.api import Query
        return Query(problem(self.config, seq),
                     budget=int(self.traffic["budget"]))

    def ask(self, q, key: int) -> Dict:
        """Send one query and wait for its served front; the record of
        what came back, when, and what the query's inserts were given."""
        rec = dict(key=key, inserts=[], query=q)
        self.log.current = rec["inserts"]
        t0 = time.perf_counter()
        try:
            if self.executor is not None:
                h = self.executor.submit(q, key=key)
                r = h.result(timeout=JOB_TIMEOUT_S)
                rec["job"] = h.job_id
            else:
                import jax
                r = self.session.submit(q, key=jax.random.PRNGKey(key))
        except Exception as e:          # a failed query, counted
            rec.update(error=f"{type(e).__name__}: {e}", t0=t0,
                       t1=time.perf_counter())
            return rec
        finally:
            self.log.current = None
        rec.update(result=r, t0=t0, t1=time.perf_counter(),
                   n_evals=int(r.provenance.n_evals_run))
        return rec

    def closed_loop(self, stream, t0: float, seconds: float) -> List[Dict]:
        """Send the queries of ``stream`` one after another, each as soon
        as the previous front is served, until one completes ``seconds``
        after ``t0``; their records, in order."""
        import jax
        records = []
        for qs in stream:
            with jax.profiler.TraceAnnotation("bench.build_problem"):
                q = self.query(qs.seq)
            with jax.profiler.TraceAnnotation("bench.submit"):
                rec = self.ask(q, qs.key)
            rec["seq"] = qs.seq
            records.append(rec)
            if rec["t1"] - t0 >= seconds:
                break
        return records

    def rerunner(self, run_dir: Path):
        """For the executor entry, a function answering a record's query
        afresh through ``Session.submit`` (a new session over an empty
        cache), as the output check's ``rerun``; ``None`` otherwise."""
        if self.executor is None:
            return None
        direct = dict(self.traffic, entry="session")

        def rerun(rec):
            fresh = Client(self.config, direct, Path(run_dir) / "rerun",
                           InsertLog())
            return fresh.ask(rec["query"], rec["key"])["result"]

        return rerun

    def close(self) -> None:
        if self.executor is not None:
            self.executor.shutdown(wait=True)
