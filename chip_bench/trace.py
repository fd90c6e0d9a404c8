"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the benchmark's numbers.

On a TPU the trace holds one plane per device (``/device:TPU:<i>``) whose
``XLA Ops`` line has one event per operation the chip ran, and a host plane
(``/host:CPU``) whose lines hold the host's spans, among them the
``jax.profiler.TraceAnnotation`` spans the benchmark writes (prefix
``bench.``). Both are on one clock, in nanoseconds.

- busy: the union of the device-op intervals inside the window;
- kernel time: the summed durations of the ops a predicate selects (the
  Pallas kernels are the ops whose HLO text carries
  ``custom_call_target="tpu_custom_call"``);
- idle gaps: the window minus busy, each gap named by the innermost
  benchmark span that covers its midpoint, or ``runner`` when none does.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
UNCOVERED = "runner"
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'


@dataclass
class Trace:
    ops: list = field(default_factory=list)     # per device: [(start, end, text)]
    spans: list = field(default_factory=list)   # [(start, end, name)]


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
            tr.ops.append(sorted(ops))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX)]
    return tr


def window_bounds(tr: Trace, open_name: str, close_name: str):
    """(start, end) in trace ns: the starts of the two marker spans."""
    opens = [s for s, _, n in tr.spans if n == open_name]
    closes = [s for s, _, n in tr.spans if n == close_name]
    if not opens or not closes:
        raise ValueError(f"markers {open_name!r}/{close_name!r} not in trace")
    return min(opens), max(closes)


def _clip(intervals, lo, hi):
    for s, e, *rest in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def union(intervals, lo, hi) -> list[tuple[float, float]]:
    """Merged intervals of ``intervals`` clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for s, e in sorted(_clip(intervals, lo, hi)):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(ops, lo, hi) -> float:
    return float(sum(e - s for s, e in union(ops, lo, hi)))


def gaps(ops, lo, hi) -> list[tuple[float, float]]:
    out, cur = [], lo
    for s, e in union(ops, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def kernel_ns(ops, lo, hi, pred) -> float:
    return float(sum(e - s for o in ops if pred(o[2])
                     for s, e in _clip([o], lo, hi)))


def is_pallas(text: str) -> bool:
    return PALLAS_MARK in text


def top_ops(ops, lo, hi, n=10) -> list[list]:
    """The ``n`` op names with the most device time in [lo, hi], seconds."""
    tot: dict[str, float] = {}
    for o in ops:
        for s, e in _clip([o], lo, hi):
            k = op_name(o[2])
            tot[k] = tot.get(k, 0.0) + (e - s)
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def name_gap(spans, s, e) -> str:
    """The innermost (shortest) benchmark span covering the gap's midpoint."""
    mid = (s + e) / 2
    best = None
    for ss, se, name in spans:
        if ss <= mid <= se and (best is None or se - ss < best[0]):
            best = (se - ss, name)
    return best[1][len(SPAN_PREFIX):] if best else UNCOVERED


def longest_gaps(ops, spans, lo, hi, n=10) -> list[list]:
    gs = sorted(gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[name_gap(spans, s, e), (e - s) * 1e-9] for s, e in gs]


def reduce(tr: Trace, lo: float, hi: float) -> dict:
    """Busy and kernel seconds averaged over the devices, the window, and
    the breakdown of the first device."""
    ndev = max(1, len(tr.ops))
    ops0 = tr.ops[0] if tr.ops else []
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_ns(o, lo, hi) for o in tr.ops) * 1e-9 / ndev,
        "pallas_s": sum(kernel_ns(o, lo, hi, is_pallas)
                        for o in tr.ops) * 1e-9 / ndev,
        "device_ops": top_ops(ops0, lo, hi),
        "idle_gaps": longest_gaps(ops0, tr.spans, lo, hi),
    }
