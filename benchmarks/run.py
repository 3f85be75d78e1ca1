"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig9] [--full]

Prints ``name,us_per_call,derived`` CSV. BENCH_FAST=0 (or --full) runs the
long learning-curve variants.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark group name")
    ap.add_argument("--full", action="store_true",
                    help="long variants (learning curves at full length)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: engine hot path + analytic groups only")
    args = ap.parse_args()
    if args.full:
        os.environ["BENCH_FAST"] = "0"

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    # imports after BENCH_FAST is settled
    from benchmarks import figures
    from benchmarks.engine_bench import engine_benchmarks
    from benchmarks.kernels_bench import kernel_benchmarks
    from benchmarks.lag_bench import lag_benchmarks
    from benchmarks.mesh_bench import mesh_benchmarks
    from benchmarks.orchestrator_bench import (chaos_benchmarks,
                                               gray_benchmarks,
                                               orchestrator_benchmarks)
    from benchmarks.roofline_bench import roofline_rows
    from benchmarks.trainer_bench import trainer_benchmarks

    groups = {
        "fig2": figures.fig2_generation,
        "fig5": figures.fig5_learning,
        "fig6": figures.fig6_lag_ess,
        "fig7": figures.fig7_kl,
        "fig8": figures.fig8_utilization,
        "fig9": figures.fig9_pareto,
        "table1": figures.table1_success,
        "ablation": figures.ablation_update_every,
        "kernels": kernel_benchmarks,
        "roofline": roofline_rows,
        "engine": engine_benchmarks,
        "trainer": trainer_benchmarks,
        "orchestrator": orchestrator_benchmarks,
        "chaos": chaos_benchmarks,
        "gray": gray_benchmarks,
        "lag": lag_benchmarks,
        "mesh": mesh_benchmarks,
    }
    if args.smoke:
        # fast, deterministic-cost groups so per-PR CI can catch tokens/sec
        # regressions in the generation hot path, activation-memory /
        # step-time regressions in the trainer hot path, broadcast-pause /
        # throughput regressions in the orchestration layer, recovery
        # regressions in the fault-tolerance paths (fail-stop chaos +
        # gray-failure detection scenarios), and lag-distribution /
        # bounded-staleness regressions in the lag-aware training path
        groups = {k: groups[k] for k in ("engine", "trainer", "orchestrator",
                                         "chaos", "gray", "lag",
                                         "fig8", "fig9")}

    print("name,us_per_call,derived")
    failed = []
    for name, fn in groups.items():
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            rows = fn()
        except Exception as e:  # keep the harness running
            failed.append(name)
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}")
            continue
        for rname, us, derived in rows:
            print(f"{rname},{us:.1f},{derived}")
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark groups failed: {failed}")


if __name__ == "__main__":
    # support `python benchmarks/run.py` as well as `python -m benchmarks.run`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
