"""Readings the limits of ``correct`` are set from, in one process on the chip.

    python3 -m chip_bench.calibrate --workload <cell> --seeds 1,2,... \
        [--control-seeds ...] [--fault-seeds ...] [--out readings.jsonl]

For every seed: the program's first three steps (a run with a one-second
window) against the reference. For every control seed: the reference
computed with fp8 operands (the control, one precision below the
configuration's bf16) against the float32 reference. For every fault seed:
the program with each fault of ``chip_bench.faults`` planted underneath,
against the reference. One JSON line per reading. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from chip_bench import spec


def _seeds(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train.step_cache import CompiledStepCache
    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    from chip_bench import check, faults, harness, peaks
    harness.keep_every_program()
    peak = peaks.peak(jax.devices()[0].device_kind)
    cache = CompiledStepCache()
    refs: dict[int, dict] = {}
    sink = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    def ref(seed):
        if seed not in refs:
            refs[seed] = harness.check_readings(cell, seed)
        return refs[seed]

    def program(seed, kind):
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed, 1.0, False, t0, peak,
                               step_cache=cache)
        g = check.gaps(out.readings, ref(seed))
        emit({"workload": cell.name, "kind": kind, "seed": seed, **g,
              "loss": out.readings["loss"], "ref_loss": ref(seed)["loss"],
              "m1": out.readings["m1"], "ref_m1": ref(seed)["m1"],
              "memory_peak_bytes": out.memory_peak_bytes,
              "ref_seconds": ref(seed)["seconds"],
              "seconds": time.perf_counter() - t0})

    try:
        for seed in _seeds(args.seeds):
            program(seed, "program")
        for seed in _seeds(args.control_seeds):
            q = harness.check_readings(cell, seed, prec="fp8")
            emit({"workload": cell.name, "kind": "control_fp8", "seed": seed,
                  **check.gaps(q, ref(seed)), "loss": q["loss"],
                  "ref_loss": ref(seed)["loss"], "m1": q["m1"],
                  "ref_m1": ref(seed)["m1"]})
        for seed in _seeds(args.fault_seeds):
            for name, plant in faults.FAULTS.items():
                with plant():
                    program(seed, f"fault_{name}")
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
