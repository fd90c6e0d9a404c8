"""Benchmark runner: one section per paper table/figure (deliverable d).

Prints ``name,us_per_call,derived`` CSV rows. The planning section runs the
small-n fast-vs-reference dp_split comparison (full-size numbers take ~47
minutes of reference DP — regenerate the tracked ``BENCH_planning.json``
with a direct ``python -m benchmarks.bench_planning`` run).
"""
from __future__ import annotations

import traceback


def main() -> None:
    from benchmarks import (bench_costmodel, bench_microbatch, bench_padding,
                            bench_planning, bench_schedule, bench_throughput)
    sections = [
        ("Fig3+18: layer time & cost-model accuracy", bench_costmodel.main),
        ("Fig13/14/4: throughput vs packing", bench_throughput.main),
        ("Fig5/16a: micro-batching ablation", bench_microbatch.main),
        ("Fig7/16b: schedule robustness", bench_schedule.main),
        ("Fig15: padding efficiency", bench_padding.main),
        ("Fig17: planning time", lambda: bench_planning.main(quick=True)),
    ]
    failures = []
    for name, fn in sections:
        print(f"\n# {name}", flush=True)
        try:
            fn()
        except Exception as e:
            failures.append((name, e))
            traceback.print_exc()

    if failures:
        raise SystemExit(f"{len(failures)} benchmark sections failed: "
                         f"{[f[0] for f in failures]}")
    print("\nALL BENCHMARK SECTIONS COMPLETED")


if __name__ == "__main__":
    main()
