"""Benchmark runner — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [module ...]

Emits ``name,us_per_call,derived`` CSV (benchmarks/common.emit).  Heavy
results are cached under artifacts/bench/*.json; delete a JSON (or set
REPRO_BENCH_FULL=1 for the bigger search budgets) to recompute.
"""

import sys

from repro.compile_cache import enable as enable_compile_cache

from . import (bench_validation, bench_cost_fig3, bench_comparison,
               bench_codesign, bench_pareto, bench_explore, bench_transfer,
               bench_serve, bench_tt, bench_roofline, bench_autoshard,
               bench_kernels, bench_scale, bench_surrogate)
from .common import QUICK, emit

MODULES = {
    "validation": bench_validation,    # Sec. V-A model-vs-simulator
    "cost_fig3": bench_cost_fig3,      # Fig. 3
    "comparison": bench_comparison,    # Fig. 7 (Simba / NN-Baton / Monad)
    "codesign": bench_codesign,        # Fig. 8 ladder
    "pareto": bench_pareto,            # Fig. 9
    "explore": bench_explore,          # repro.explore front + cache service
    "transfer": bench_transfer,        # cross-workload transfer warm-starts
    "serve": bench_serve,              # async jobs, overload, crash-resume
    "tt": bench_tt,                    # Fig. 10 case study
    "roofline": bench_roofline,        # dry-run roofline table
    "autoshard": bench_autoshard,      # Level-B advisor
    "kernels": bench_kernels,          # kernel micro-table
    "scale": bench_scale,              # islands, megabatch, dominance kernel
    "surrogate": bench_surrogate,      # surrogate-gated eval savings
}


def main() -> None:
    enable_compile_cache()
    names = sys.argv[1:] or list(MODULES)
    print("name,us_per_call,derived")
    for n in names:
        rows = MODULES[n].run(quick=QUICK)
        emit(rows)


if __name__ == "__main__":
    main()
