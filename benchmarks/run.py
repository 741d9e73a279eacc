"""Benchmark orchestrator — one benchmark per paper table/figure.

Prints ``name,value,derived`` CSV rows.  Env knobs:
  BENCH_QUICK=1     fast pass (CI / smoke)
  BENCH_ROUNDS=N    federated rounds per run
  BENCH_ONLY=a,b    run only the named benches

Every bench runs in this one process, on the devices JAX finds.

Usage: PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import os
import sys
import time
import traceback

from benchmarks import (bench_ablation, bench_arbitration, bench_comm,
                        bench_devices, bench_drift, bench_fedsim,
                        bench_importance, bench_kernel, bench_module_pruning,
                        bench_noniid, bench_rank_alloc, bench_roofline,
                        bench_secagg, bench_serving, bench_sweeps,
                        bench_variance)
from benchmarks import common as C
from repro.compat import enable_compilation_cache

BENCHES = {
    "variance": bench_variance.main,          # Eqs 9/10
    "kernel": bench_kernel.main,              # kernels/bea_fused
    "serving": bench_serving.main,            # multi-tenant engine + bea_batched
    "fedsim": bench_fedsim.main,              # cohort/codec/async simulation
    "secagg": bench_secagg.main,              # secure aggregation + DP costs
    "module_pruning": bench_module_pruning.main,   # Figs 13/14
    "comm": bench_comm.main,                  # Figs 8/12
    "drift": bench_drift.main,                # Fig 5
    "importance": bench_importance.main,      # Table I
    "arbitration": bench_arbitration.main,    # Table II
    "ablation": bench_ablation.main,          # Fig 11
    "sweeps": bench_sweeps.main,              # Fig 15
    "rank_alloc": bench_rank_alloc.main,      # Fig 9
    "noniid": bench_noniid.main,              # Table IV / Fig 7
    "devices": bench_devices.main,            # Figs 2a/2d/10/17
    "roofline": bench_roofline.main,          # §Roofline (reads dry-run JSON)
}


def main() -> int:
    enable_compilation_cache()
    quick = C.QUICK
    only = os.environ.get("BENCH_ONLY")
    names = [n.strip() for n in only.split(",")] if only else list(BENCHES)
    failures = 0
    print("name,value,derived")
    for name in names:
        t0 = time.time()
        try:
            BENCHES[name](quick=quick)
            print(f"bench/{name}/wall_s,{time.time() - t0:.1f},", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"bench/{name}/FAILED,{type(e).__name__},{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
