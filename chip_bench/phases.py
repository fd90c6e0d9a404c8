"""The chip's idle time put down to what the host was doing, and the device
time of each named program, from one traced window.

The program writes ``dynapipe.*`` spans (``repro.core.spans``) into the
profiler's trace, on the device's clock, each on the host line of the
thread that ran it, with its args as event stats; its jitted programs are
named, so each device's ``XLA Modules`` line reads ``jit_stage{j}_fwd``,
``jit_stage{j}_bwd``, ``jit_adamw_step``. From these:

- idle phases: each instant of each device-0 gap (the gaps
  ``device_idle_share`` counts) goes to the phase of the span on the
  runner's thread (the host line that holds ``dynapipe.iteration``) that
  covers it: *prep* under ``submit``, ``plan_wait``, ``materialize`` or
  ``stage_setup`` (a ``compile`` or ``plan`` inside one counts to it);
  *pipeline* under ``pipeline``, whatever the stage threads do; *step end*
  everywhere else (``grad_merge``, ``optimizer``, ``step_sync``, the
  runner's bookkeeping and the time between iterations). Phase spans do not
  overlap on one thread, so the three sum to the idle time;
- device seconds per program, and the top ops within each (an op belongs
  to the module event that covers its midpoint);
- the window's iterations (those whose ``dynapipe.iteration`` ends inside
  it) and their summed ``predicted_compute_ms``.

``metrics`` turns that into the five per-layer numbers a window would
report. The benchmark's command does not read them yet (PERF.md, Open
questions); ``python3 -m chip_bench.phases`` takes ``chip_bench.run``'s
arguments, makes one benchmark run, and with ``--trace 1`` also prints
them, the program table and any compile inside the window on standard
error.
"""
from __future__ import annotations

import bisect
import json
import sys
from dataclasses import dataclass, field

from chip_bench import harness
from chip_bench import trace as T

PREFIX = "dynapipe."
ITERATION = "dynapipe.iteration"
COMPILE = "dynapipe.compile"
PREP = frozenset({"dynapipe.submit", "dynapipe.plan_wait",
                  "dynapipe.materialize", "dynapipe.stage_setup"})
PIPELINE = frozenset({"dynapipe.pipeline"})
OPTIMIZER_PROGRAM = "jit_adamw_step"
STAGE_PROGRAM = "jit_stage"


@dataclass
class Program:
    spans: list = field(default_factory=list)  # (start, end, name, line, args)
    modules: list = field(default_factory=list)  # per device (start, end, name)


def module_name(event_name: str) -> str:
    """``jit_stage0_fwd(1234)`` -> ``jit_stage0_fwd``."""
    return event_name.split("(", 1)[0]


def load(path: str) -> Program:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    prog = Program()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            prog.modules.append(sorted(
                (e.start_ns, e.start_ns + e.duration_ns, module_name(e.name))
                for line in plane.lines if line.name == "XLA Modules"
                for e in line.events))
        elif plane.name == "/host:CPU":
            # lines of different threads may share a name: key by position
            for i, line in enumerate(plane.lines):
                prog.spans += [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name, i, dict(e.stats))
                               for e in line.events
                               if e.name.startswith(PREFIX)]
    prog.spans.sort(key=lambda sp: sp[:4])
    return prog


def runner_line(spans, lo, hi):
    """The host line holding the most ``dynapipe.iteration`` time in the
    window, or None."""
    held: dict = {}
    for s, e, name, line, _ in spans:
        if name == ITERATION:
            held[line] = held.get(line, 0.0) + max(0.0,
                                                   min(e, hi) - max(s, lo))
    return max(held, key=held.get) if held else None


def _intersect(a, b) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def idle_phases(ops, spans, lo, hi) -> dict:
    """Device idle seconds in [lo, hi] by the runner thread's phase."""
    idle = T.gaps(ops, lo, hi)
    main = runner_line(spans, lo, hi)
    on_main = [(s, e, n) for s, e, n, line, _ in spans if line == main]
    prep = T.union([x for x in on_main if x[2] in PREP], lo, hi)
    pipe = T.union([x for x in on_main if x[2] in PIPELINE], lo, hi)
    prep_ns = _length(_intersect(idle, prep))
    in_pipe = _intersect(idle, pipe)
    pipe_ns = _length(in_pipe) - _length(_intersect(in_pipe, prep))
    return {"prep": prep_ns * 1e-9, "pipeline": pipe_ns * 1e-9,
            "step_end": (_length(idle) - prep_ns - pipe_ns) * 1e-9}


def program_seconds(modules, lo, hi) -> dict:
    """Device seconds of each named program in [lo, hi], most first."""
    tot: dict[str, float] = {}
    for s, e, name in modules:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    return dict(sorted(tot.items(), key=lambda kv: -kv[1]))


def program_top_ops(ops, modules, lo, hi, n=3) -> dict:
    """The ``n`` ops with most device time within each program, seconds."""
    starts = [m[0] for m in modules]
    tot: dict[str, dict[str, float]] = {}
    for o in ops:
        mid = (o[0] + o[1]) / 2
        k = bisect.bisect_right(starts, mid) - 1
        prog = modules[k][2] if k >= 0 and mid <= modules[k][1] else "none"
        s, e = max(o[0], lo), min(o[1], hi)
        if e > s:
            per = tot.setdefault(prog, {})
            name = T.op_name(o[2])
            per[name] = per.get(name, 0.0) + (e - s) * 1e-9
    return {p: [[k, v] for k, v in sorted(per.items(),
                                          key=lambda kv: -kv[1])[:n]]
            for p, per in tot.items()}


def reduce(tr: T.Trace, prog: Program, lo: float, hi: float) -> dict:
    """Device 0's idle phases and programs, and the window's iterations."""
    ops0 = tr.ops[0] if tr.ops else []
    mods0 = prog.modules[0] if prog.modules else []
    main = runner_line(prog.spans, lo, hi)
    its = [a for s, e, n, line, a in prog.spans
           if n == ITERATION and line == main and lo <= e <= hi]
    return {
        "iterations": len(its),
        "idle_s": _length(T.gaps(ops0, lo, hi)) * 1e-9,
        "idle_phase_s": idle_phases(ops0, prog.spans, lo, hi),
        "program_s": program_seconds(mods0, lo, hi),
        "program_top_ops": program_top_ops(ops0, mods0, lo, hi),
        "predicted_compute_s": sum(a.get("predicted_compute_ms", 0.0)
                                   for a in its) * 1e-3,
        "compiles": [[a.get("stage"), a.get("kind"), a.get("shape"),
                      (min(e, hi) - max(s, lo)) * 1e-9]
                     for s, e, n, _, a in prog.spans
                     if n == COMPILE and e > lo and s < hi],
    }


def metrics(red: dict) -> dict:
    """The five per-layer numbers of a reduced window: idle ms per window
    iteration by phase, AdamW's device ms per window iteration, and the
    cost model's error on the stage programs' summed device time, in %."""
    n = red["iterations"]
    if not n:
        return {}
    ph = red["idle_phase_s"]
    out = {"idle_prep_ms": 1e3 * ph["prep"] / n,
           "idle_pipeline_ms": 1e3 * ph["pipeline"] / n,
           "idle_step_end_ms": 1e3 * ph["step_end"] / n}
    opt = red["program_s"].get(OPTIMIZER_PROGRAM)
    if opt is not None:
        out["optimizer_device_ms"] = 1e3 * opt / n
    measured = sum(v for k, v in red["program_s"].items()
                   if k.startswith(STAGE_PROGRAM))
    if measured > 0 and red["predicted_compute_s"] > 0:
        out["cost_model_error"] = 100.0 * abs(
            measured - red["predicted_compute_s"]) / measured
    return out


def report(red: dict, out=sys.stderr) -> None:
    print("phases: " + json.dumps(dict(metrics(red), **{
        k: red[k] for k in ("iterations", "idle_s", "idle_phase_s",
                            "predicted_compute_s", "compiles")})),
          file=out)
    for name, secs in red["program_s"].items():
        top = ", ".join(f"{op} {s:.4f}"
                        for op, s in red["program_top_ops"].get(name, []))
        print(f"program {name} {secs:.4f} s: {top}", file=out)


class PhaseTracer(harness.Tracer):
    """The benchmark's tracer, reporting the phases of its window too."""

    def reduce(self) -> dict:
        path = T.find_xplane(self.dir)
        tr = T.load(path)
        lo, hi = T.window_bounds(tr, "bench.window_open",
                                 "bench.window_close")
        report(reduce(tr, load(path), lo, hi))
        return T.reduce(tr, lo, hi)


def main(argv=None) -> int:
    from chip_bench import run
    harness.Tracer = PhaseTracer
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
