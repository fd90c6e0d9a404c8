"""The t5-11b cell at toy widths on the CPU: the program (T5's own block)
follows ``references/t5.py``'s mathematics to float32 round-off, and the
run's own check (``chip_bench.run.measure``) passes a sound run and fails
the fp8 control and every planted fault, under limits of its own."""
import json
import time
import types

import jax
import jax.numpy as jnp
import pytest

from chip_bench import check, faults, harness, peaks, refmath, run, spec
from chip_bench.tests.tiny import TINY_WIDTHS, tiny_traffic

SEED = 2**31 + 4242
DEVICE = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
# Set as the chip's limits are (PERF.md section 2), from CPU readings at
# these widths over seeds 2**31 + 4242 ... + 4249: the gradient gap reads
# 1.7e-3-4.6e-3 on sound runs and 1.06e-2-5.5e-2 under the fp8 control, so
# its limit sits between with room on both sides. The first-step loss gap
# (sound at most 1.9e-4, control 2.4e-4-1.4e-3) and the weight-change gap
# do not separate the control at these widths and are held loosely.
TINY_LIMITS = {"loss_gap": 5e-4, "grad_gap": 7e-3, "delta_gap": 5e-2}


def tiny_cell():
    with open(spec.BENCH_DIR / "configs" / "t5-11b-1enc1dec.json") as f:
        config = json.load(f)
    config["program"]["replace"].update(TINY_WIDTHS)
    config["model"].update(TINY_WIDTHS)
    config["limits"] = dict(TINY_LIMITS)
    return spec.Cell(name="tiny.t5-11b-1enc1dec.flan-mix", chips=1,
                     config=config, traffic_name="flan-mix",
                     traffic=tiny_traffic())


def test_file_matches_the_program_config():
    config = tiny_cell().config
    cfg = harness.program_config(config)
    assert cfg.t5_block and not cfg.use_rope
    assert cfg.rel_attn_buckets == config["model"]["rel_attn_buckets"]
    assert cfg.rel_attn_max_distance == \
        config["model"]["rel_attn_max_distance"]


def test_program_in_float32_matches_the_reference(monkeypatch):
    monkeypatch.setattr(refmath, "normal", lambda key, shape, scale:
                        jax.random.normal(key, shape, jnp.float32) * scale)
    cell = tiny_cell()
    cell.config["program"]["replace"]["dtype"] = "float32"
    ref = harness.check_readings(cell, SEED)
    out = harness.run_cell(cell, SEED, 1.0, False, time.perf_counter(),
                           peaks.peak("TPU v5 lite"))
    # the tables' gradients are among the leaves compared
    assert {"enc_rel_bias", "dec_rel_bias"} <= set(ref["m1"])
    assert {"enc_rel_bias", "dec_rel_bias"} <= set(out.readings["m1"])
    g = check.gaps(out.readings, ref)
    assert max(g["loss_gap"], g["grad_gap"], g["delta_gap"]) < 2e-6, g


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


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
