"""The trace reduction on a small trace recorded on a TPU v5e: three
iterations of a matmul program and the Pallas attention kernel forward and
backward, each followed by a host sleep under a ``bench.host_sleep`` span."""
from pathlib import Path

import numpy as np
import pytest

from chip_bench import trace as T

DATA = Path(__file__).parent / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return T.load(str(DATA))


@pytest.fixture(scope="module")
def window(tr):
    # from the first device op to the end of the last sleep
    sleeps = [s for s in tr.spans if s[2] == "bench.host_sleep"]
    return min(s for s, _, _ in tr.ops[0]), max(s[1] for s in sleeps)


def test_planes_read(tr):
    assert len(tr.ops) == 1 and len(tr.ops[0]) == 150
    assert {n for _, _, n in tr.spans} == {"bench.step", "bench.host_sleep"}


def test_busy_is_the_union_of_op_intervals(tr, window):
    lo, hi = window
    # independent count: mark every nanosecond an op covers
    mask = np.zeros(int(hi - lo), bool)
    for s, e, _ in tr.ops[0]:
        a, b = int(max(s, lo) - lo), int(min(e, hi) - lo)
        if b > a:
            mask[a:b] = True
    assert T.busy_ns(tr.ops[0], lo, hi) == pytest.approx(mask.sum(), abs=2)
    # overlapping intervals count once
    assert T.busy_ns([(0, 10, "a"), (5, 15, "b"), (20, 30, "c")], 0, 40) == 25
    total_gap = sum(e - s for s, e in T.gaps(tr.ops[0], lo, hi))
    assert total_gap + mask.sum() == pytest.approx(hi - lo, abs=2)


def test_kernel_time_by_name(tr, window):
    lo, hi = window
    pallas = [e - s for s, e, t in tr.ops[0]
              if 'custom_call_target="tpu_custom_call"' in t]
    assert len(pallas) == 12      # fwd, and fwd + dq + dkv in the grad, x3
    assert T.kernel_ns(tr.ops[0], lo, hi, T.is_pallas) == sum(pallas)
    red = T.reduce(tr, lo, hi)
    assert red["pallas_s"] == pytest.approx(sum(pallas) * 1e-9)
    names = dict(red["device_ops"])
    assert names["convolution_tanh_fusion"] == pytest.approx(sum(
        e - s for s, e, t in tr.ops[0]
        if t.startswith("%convolution_tanh_fusion ")) * 1e-9)


def test_gaps_named_by_covering_span(tr, window):
    lo, hi = window
    gaps = T.longest_gaps(tr.ops[0], tr.spans, lo, hi, n=3)
    # the three sleeps are the three longest idle gaps
    assert [g[0] for g in gaps] == ["host_sleep"] * 3
    assert all(g[1] > 0.01 for g in gaps)
    assert T.name_gap(tr.spans, hi + 1e6, hi + 2e6) == T.UNCOVERED
