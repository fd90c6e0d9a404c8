"""An encoder-decoder run's spans carry its tokens per side, and T5's
biased kernels are named in its device programs: a tiny two-stage t5-11b
runner, its Pallas kernels interpreted, traced by ``jax.profiler`` on the
CPU."""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.core import spans
from repro.core.cost_model import AnalyticCostModel
from repro.core.planner import PlannerConfig
from repro.core.shapes import ShapePalette
from repro.data.streams import MultiTaskStream, StreamConfig
from repro.train.runner import PlanAheadRunner, RunnerConfig
from repro.train.step_cache import CompiledStepCache

CFG = dataclasses.replace(reduced(get_arch("t5-11b")), n_layers=1)
PAL = ShapePalette.build(min_seq=32, max_seq=64, seq_align=32, max_mbs=4)
STREAM = StreamConfig(n_tasks=4, global_tokens=256, max_len=64,
                      vocab=CFG.vocab, encdec_fraction=1.0, seed=5)
N_ITERS = 2
SIDES = ("real_enc_tokens", "padded_enc_tokens", "real_dec_tokens",
         "padded_dec_tokens")
KERNELS = ("flash_fwd_relbias", "flash_dq_relbias", "flash_dkv_relbias")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("trace"))
    cache = CompiledStepCache()
    pcfg = PlannerConfig(n_stages=2, d_model=CFG.d_model, palette=PAL)
    rcfg = RunnerConfig(n_iters=N_ITERS, use_executor=True, log_every=0,
                        impl="interpret")
    runner = PlanAheadRunner(CFG, AnalyticCostModel(CFG, n_stages=2), pcfg,
                             rcfg, MultiTaskStream(STREAM), step_cache=cache)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        _, history, _ = runner.run()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events]
    return events, history, cache


@pytest.mark.parametrize("name", [spans.PLAN, spans.ITERATION])
def test_plan_and_iteration_carry_tokens_per_side(traced, name):
    events, history, _ = traced
    got = [args for n, args in events if n == name]
    assert len(got) == N_ITERS
    assert sorted(a["real_tokens"] for a in got) == sorted(
        h["tokens"] for h in history)
    for a in got:
        assert a["real_enc_tokens"] + a["real_dec_tokens"] == a["real_tokens"]
        assert a["padded_enc_tokens"] + a["padded_dec_tokens"] == \
            a["padded_tokens"]
        assert a["padded_enc_tokens"] >= a["real_enc_tokens"] > 0
        assert a["padded_dec_tokens"] >= a["real_dec_tokens"] > 0


def test_decoder_only_plans_carry_no_side_args():
    lengths = np.array([[40, 0], [12, 0]])
    mbs = [type("M", (), {"mbs": 2, "seq": 64})()]
    assert spans.encdec_tokens(lengths, mbs) == {}


def test_biased_kernels_are_named_in_the_stage_programs(traced):
    """The trace names each stage program it ran; those programs hold the
    three biased kernels by name (the encoder's forward holds only the
    forward kernel)."""
    events, _, cache = traced
    names = {n for n, _ in events}
    assert {f"PjitFunction(jit({p}))" for p in (
        "stage0_fwd", "stage0_bwd", "stage1_fwd_bwd")} <= names
    texts = {kind: [exe.as_text() for exe in cache.entries(kind)]
             for kind in ("fwd", "bwd", "fwd_bwd")}
    assert all("flash_fwd_relbias" in t for t in texts["fwd"])
    for kind in ("bwd", "fwd_bwd"):
        assert texts[kind] and all(all(k in t for k in KERNELS)
                                   for t in texts[kind])

