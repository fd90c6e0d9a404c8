"""FLOP and byte counts against values worked by hand."""
import json

import numpy as np
import pytest

from chip_bench import flops, spec


def _model(name):
    with open(spec.BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)["model"]


def test_gpt_sample():
    m = _model("gpt-paper-2L")
    # weights that multiply activations: 2 layers x (4*4096^2 + 2*4096*16384)
    # + head 50304*4096 = 608,698,368; fwd 2*W*100 plus causal attention
    # 4*32*128 x 5050 pairs x 2 layers; training = 3 x fwd
    assert flops.model_flops(m, np.array([[100, 0]])) == 365_715_456_000
    f, b = flops.attention_work(m, np.array([[100, 0]]))
    assert f == 2 * 12 * 32 * 128 * 5050
    # per layer: 6 reads/writes of q-sized and 6 of k-sized tensors, bf16
    assert b == 2 * 12 * 100 * 32 * 128 * 2


def test_t5_sample():
    m = _model("t5-paper-1enc1dec")
    # enc 200 tokens: 2*201,326,592*200 + full attention 65536*200^2
    # dec 50 tokens: 2*(201,326,592 + 33,554,432 + 32128*1024)*50
    #   + cross k/v over the 200 encoder tokens 2*33,554,432*200
    #   + attention 65536*(1275 causal + 200*50 cross)
    assert flops.model_flops(m, np.array([[200, 50]])) == 372_272_332_800


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000, 50, peak) == pytest.approx(10.0)
    assert flops.roofline_seconds(100, 500, peak) == pytest.approx(50.0)
