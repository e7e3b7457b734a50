"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Default mode is sized for a
single-CPU container; pass --full for paper-scale rounds.

Benchmarks that return structured rows (exec_scaling, transport) also
publish ``BENCH_executor.json`` / ``BENCH_transport.json`` under
``--bench-dir`` — the stable perf-trajectory documents (``repro.obs.bench``
schema) CI validates and archives.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only table1,...]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# (job name, BENCH file stem) for jobs whose run() returns structured rows
BENCH_JOBS = {"exec_scaling": "executor", "transport": "transport",
              "traffic": "traffic"}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale rounds (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: table1,table1_vit,fig3,"
                         "table3,table4,table5,table6,async_drift,"
                         "exec_scaling,transport,fused_agg,scenario_matrix,"
                         "traffic")
    ap.add_argument("--bench-dir", default=".",
                    help="directory for the BENCH_*.json perf-trajectory "
                         "documents (exec_scaling/transport jobs)")
    args = ap.parse_args(argv)
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None
    from repro.utils import hw
    hw.enable_compile_cache()

    from benchmarks import (table1_noniid, fig3_drift, table3_llm,
                            table4_beta, table5_ablation, table6_comm,
                            seed_robustness, async_drift, executor_scaling,
                            transport_bench, fused_agg_bench,
                            scenario_matrix, traffic_replay)
    from benchmarks.common import emit

    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    jobs = [
        ("table1", lambda: table1_noniid.run(quick=quick, model="cnn")),
        ("table1_vit", lambda: table1_noniid.run(quick=quick, model="vit")),
        ("fig3", lambda: fig3_drift.run(quick=quick)),
        ("table3", lambda: table3_llm.run(quick=quick)),
        ("table4", lambda: table4_beta.run(quick=quick)),
        ("table5", lambda: table5_ablation.run(quick=quick)),
        ("table6", lambda: table6_comm.run(quick=quick)),
        ("async_drift", lambda: async_drift.run(quick=quick)),
        ("exec_scaling", lambda: executor_scaling.run(quick=quick)),
        ("transport", lambda: transport_bench.run(quick=quick)),
        ("traffic", lambda: traffic_replay.run(quick=quick)),
        # standalone micro-bench (no training): the same rows also ride
        # inside the transport job's BENCH_transport.json
        ("fused_agg", lambda: fused_agg_bench.run(quick=quick)),
        ("scenario_matrix", lambda: scenario_matrix.run(quick=quick)),
        ("robust", lambda: seed_robustness.run(quick=quick)),
    ]
    failures = 0
    for name, fn in jobs:
        if only and name not in only:
            continue
        try:
            result = fn()
            if name in BENCH_JOBS and result:
                from repro.obs import write_bench
                path = os.path.join(args.bench_dir,
                                    f"BENCH_{BENCH_JOBS[name]}.json")
                write_bench(path, BENCH_JOBS[name], result,
                            config={"quick": quick})
                emit(f"{name}_bench_written", 0.0, path)
        except Exception as e:  # noqa: BLE001
            failures += 1
            emit(f"{name}_ERROR", 0.0, f"{type(e).__name__}:{str(e)[:120]}")
    emit("total_wall_s", (time.perf_counter() - t0) * 1e6,
         f"failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
