"""The one traffic generator: a closed loop of distinct cold problems.

A traffic mix (``bench/traffic/<mix>.json``) gives the entry point
(``session``: ``Session.submit``; ``executor``: ``Executor.submit`` then
``JobHandle.result``, with ``workers`` executor threads), the evaluation
budget of each query and the number of checked queries.  One client
sends the queries in a closed loop.  The configuration gives the problem family:
its graph at a sequence length drawn from ``seq`` = [lo, hi].

From ``--seed`` the generator draws a permutation of the whole sequence
range and a PRNG seed per query, so one seed gives one stream, no
problem repeats within a run, and every seed gives the same set of
shapes in another order.  The first query of the stream warms up; the
window takes the rest in order.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

ENTRIES = ("session", "executor")


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    index: int          # position in the stream (0 = the warm-up query)
    seq: int            # sequence length of this query's graph
    key: int            # PRNG seed of this query's search


def stream(config: Dict, traffic: Dict, seed: int) -> List[QuerySpec]:
    """Every query a run may send, in order."""
    if traffic["entry"] not in ENTRIES:
        raise ValueError(f"unknown entry {traffic['entry']!r}; pick from "
                         f"{ENTRIES}")
    lo, hi = config["seq"]
    rng = np.random.default_rng(int(seed))
    seqs = rng.permutation(np.arange(int(lo), int(hi) + 1))
    keys = rng.integers(0, 2 ** 31 - 1, size=len(seqs))
    return [QuerySpec(i, int(s), int(k))
            for i, (s, k) in enumerate(zip(seqs, keys))]


def check_sample(latencies: List[float], k: int, seed: int) -> List[int]:
    """Indices of the completed queries the output check compares: the
    longest-running one and up to ``k - 1`` more drawn from the seed."""
    n = len(latencies)
    if n == 0:
        return []
    longest = max(range(n), key=lambda i: latencies[i])
    rng = np.random.default_rng([int(seed), 0x5EED])
    rest = [i for i in rng.permutation(n) if i != longest]
    return sorted([longest] + [int(i) for i in rest[:max(0, k - 1)]])
