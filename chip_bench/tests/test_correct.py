"""How ``correct`` is decided, at a size a test run holds (toy widths, on the
CPU), through the run's own path after its look for a chip
(``chip_bench.run.measure``): a sound run is correct; the control (the
reference with fp8 operands in the program's place) fails the limits; and
so does a run with each fault planted underneath the timed path."""
import types

import pytest

from chip_bench import check, faults, harness, run
from chip_bench.tests.tiny import tiny_cell

SEED = 2**31 + 4242
DEVICE = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")


@pytest.fixture(scope="module",
                params=["gpt-paper-2L", "t5-paper-1enc1dec"])
def cell(request):
    return tiny_cell(request.param, "flan-mix")


def _measure(cell) -> dict:
    args = types.SimpleNamespace(seed=SEED, seconds=1.0, trace=0)
    return run.measure(cell, args, DEVICE, 1)


def test_sound_run_is_correct(cell):
    result = _measure(cell)
    assert result["correct"], result["checks"]


def test_control_is_not_correct(cell):
    ref = harness.check_readings(cell, SEED)
    q = harness.check_readings(cell, SEED, prec="fp8")
    ok, checks = check.decide(check.gaps(q, ref), cell.config["limits"])
    assert not ok, checks


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(cell, fault):
    with faults.FAULTS[fault]():
        result = _measure(cell)
    assert not result["correct"], result["checks"]
