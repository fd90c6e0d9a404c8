"""Run one benchmark cell once, on the chip this process finds.

    python3 -m chip_bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the compared numbers beside their limits as its last lines on
standard error, and one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``.

Exits 2 without a result when the repository's program cannot be imported
(a directory holding only the benchmark), and 3 when JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402

from chip_bench import spec  # noqa: E402

SRC = spec.ROOT / "src"


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _fail(code: int, msg: str) -> int:
    print(f"chip_bench.run: {msg}", file=sys.stderr)
    return code


def _num(x: float):
    return x if math.isfinite(x) else str(x)


def main(argv=None) -> int:
    args = _args(argv)
    cell = spec.find_cell(args.workload)
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    try:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return _fail(2, f"cannot import the program from {SRC}: {e}")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(3, f"JAX found no TPU (platform {devices[0].platform!r}); "
                        "the benchmark measures only on the chip")
    if len(devices) < cell.chips:
        return _fail(3, f"{args.workload} needs {cell.chips} chips, JAX "
                        f"sees {len(devices)}")
    enable_compile_cache()
    from chip_bench import harness
    harness.keep_every_program()
    result = measure(cell, args, devices[0], len(devices))
    print(json.dumps(result), flush=True)
    return 0


def measure(cell, args, dev, n_devices: int) -> dict:
    """The rest of a run once the chip is found: set-up, window, check.
    Returns the result line's object and prints the compared numbers
    beside their limits as the last lines on standard error."""
    from chip_bench import check, harness, peaks
    peak = peaks.peak(dev.device_kind)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, peak)
    ref = harness.check_readings(cell, args.seed)
    gaps = check.gaps(out.readings, ref)
    ok, checks = check.decide(gaps, cell.config["limits"])

    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(out.window)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = harness.end_to_end(out)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_devices,
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": bool(ok), "attempted": len(out.window.iterations),
              "failed": out.faults, "metrics": metrics, "device": device}
    if args.trace:
        t = out.window.trace
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["checks"] = {k: {"value": _num(c["value"]), "limit": c["limit"]}
                        for k, c in checks.items()}
    steps = sorted((it["step_s"] * 1e3, it["iteration"])
                   for it in out.window.iterations)
    print(f"set-up phases (s from start): {out.phases}; window "
          f"{out.window.seconds:.3f} s, {len(steps)} iterations, step ms "
          f"min {steps[0][0]:.1f} median {steps[len(steps) // 2][0]:.1f} "
          f"slowest {[(round(t, 1), i) for t, i in steps[-4:]]}; "
          f"reference {ref['seconds']:.1f} s", file=sys.stderr)
    print(f"worst leaves: grad {gaps['grad_gap_leaf']}, delta "
          f"{gaps['delta_gap_leaf']}; losses program {out.readings['loss']} "
          f"reference {ref['loss']}", file=sys.stderr)
    for k, c in checks.items():
        print(f"{k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return result


if __name__ == "__main__":
    sys.exit(main())
