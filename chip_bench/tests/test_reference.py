"""The plain references follow the program's mathematics: with the program
run in float32 and the reference's weights left unrounded, the three
compared numbers fall to float32 round-off (they read 2e-7 on the CPU),
so what the chip's limits see is the program's bf16 alone."""
import time

import jax
import jax.numpy as jnp
import pytest

from chip_bench import check, harness, peaks, refmath
from chip_bench.tests.tiny import tiny_cell

SEED = 2**31 + 321


@pytest.mark.parametrize("config", ["gpt-paper-2L", "t5-paper-1enc1dec"])
def test_program_in_float32_matches_the_reference(config, monkeypatch):
    monkeypatch.setattr(refmath, "normal", lambda key, shape, scale:
                        jax.random.normal(key, shape, jnp.float32) * scale)
    cell = tiny_cell(config, "flan-mix")
    cell.config["program"]["replace"]["dtype"] = "float32"
    ref = harness.check_readings(cell, SEED)
    out = harness.run_cell(cell, SEED, 1.0, False, time.perf_counter(),
                           peaks.peak("TPU v5 lite"))
    g = check.gaps(out.readings, ref)
    assert max(g["loss_gap"], g["grad_gap"], g["delta_gap"]) < 2e-6, g
