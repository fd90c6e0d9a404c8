"""Program spans (``repro.core.spans``) and the stable names of the device
programs: a tiny two-stage runner traced by ``jax.profiler`` on the CPU
emits every span on the thread that does the work, with args equal to what
the runner records; with no profiler, or with the spans replaced by no-ops,
it trains bit-identically."""
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
from repro.dist.backend import _scaled_adamw
from repro.models import model as MD
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.pipeline_adapter import build_grad_step
from repro.train.runner import PlanAheadRunner, RunnerConfig
from repro.train.step_cache import CompiledStepCache

CFG = dataclasses.replace(reduced(get_arch("gpt-paper")), n_layers=2)
PAL = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32, max_mbs=8)
STREAM = StreamConfig(n_tasks=8, global_tokens=768, max_len=128,
                      vocab=CFG.vocab, seed=3)
N_ITERS = 3
MAIN = ["dynapipe.submit", "dynapipe.plan_wait", "dynapipe.materialize",
        "dynapipe.stage_setup", "dynapipe.pipeline", "dynapipe.grad_merge",
        "dynapipe.optimizer", "dynapipe.step_sync", "dynapipe.loss_sync"]


def _run(step_cache):
    pcfg = PlannerConfig(n_stages=2, d_model=CFG.d_model, palette=PAL)
    rcfg = RunnerConfig(n_iters=N_ITERS, use_executor=True, log_every=0)
    runner = PlanAheadRunner(CFG, AnalyticCostModel(CFG, n_stages=2), pcfg,
                             rcfg, MultiTaskStream(STREAM),
                             step_cache=step_cache)
    return runner.run()


def _program_spans(log_dir):
    """[(line, start, end, name, args)] of the ``dynapipe.*`` host events;
    ``line`` is the index of the host line (one per thread)."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                out += [(i, e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("dynapipe.")]
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("trace"))
    cache = CompiledStepCache()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        params, history, _ = _run(cache)
    finally:
        jax.profiler.stop_trace()
    return _program_spans(log_dir), history, params, cache


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_iteration_spans_carry_the_runner_history(traced):
    sp, history, _, _ = traced
    its = [s for s in sp if s[3] == spans.ITERATION]
    assert len(its) == N_ITERS == len(history)
    assert len({s[0] for s in its}) == 1          # all on the runner thread
    for s, h in zip(its, history):
        args = s[4]
        assert args["it"] == h["iter"]
        assert args["real_tokens"] == h["tokens"]
        assert args["padded_tokens"] == h["padded_tokens"]
        assert args["n_micro"] == h["n_micro"]
        assert args["plan_wait_ms"] == h["plan_wait_s"] * 1e3
        assert args["predicted_compute_ms"] > 0
        # the iteration's time is the span's interval, which holds the
        # step_sync (test_main_thread_spans_nest_in_order); the slack is a
        # thread switch at either end
        assert (s[2] - s[1]) * 1e-9 == pytest.approx(h["time_s"], abs=0.02)


def test_main_thread_spans_nest_in_order(traced):
    sp, _, _, _ = traced
    its = [s for s in sp if s[3] == spans.ITERATION]
    main = its[0][0]
    for k, it in enumerate(its):
        names = [s[3] for s in sp if s[0] == main and s[3] in MAIN
                 and _inside(s, it)]
        # no batch is submitted ahead of the last iteration
        assert names == (MAIN if k < N_ITERS - 1 else MAIN[1:]), names
    compiles = [s for s in sp if s[3] == spans.COMPILE]
    setups = [s for s in sp if s[3] == spans.STAGE_SETUP]
    assert compiles and all(
        s[0] == main and any(_inside(s, u) for u in setups) for s in compiles)
    assert {(s[4]["stage"], s[4]["kind"]) for s in compiles} == {
        (0, "fwd"), (0, "bwd"), (1, "fwd_bwd")}
    assert all(s[4]["shape"].count("x") == 1 for s in compiles)


def test_stage_spans_on_stage_threads(traced):
    sp, history, _, _ = traced
    main = next(s[0] for s in sp if s[3] == spans.ITERATION)
    pipes = [s for s in sp if s[3] == spans.PIPELINE]
    assert len(pipes) == N_ITERS
    for pipe, h in zip(pipes, history):
        inside = [s for s in sp if _inside(s, pipe) and s[0] != main]
        lines = {}
        for j in (0, 1):
            for kind in ("fwd", "bwd"):
                got = [s for s in inside if s[3] == spans.stage(j, kind)]
                assert sorted(s[4]["mb"] for s in got) == list(
                    range(h["n_micro"]))
                lines.setdefault(j, set()).update(s[0] for s in got)
        # one compute thread per stage, neither of them the runner's
        assert len(lines[0]) == len(lines[1]) == 1
        assert lines[0] != lines[1] and main not in lines[0] | lines[1]
        # device_put in stage 0's forward
        got = [s for s in inside if s[3] == spans.DEVICE_PUT]
        assert len(got) == h["n_micro"]
        assert all(any(_inside(s, f) and f[0] == s[0] for f in inside
                       if f[3] == spans.stage(0, "fwd")) for s in got)
        # loss_sync on no stage thread: the runner reads the step's losses
        # once, after step_sync, one device scalar per micro-batch
        assert not [s for s in sp if s[3] == spans.LOSS_SYNC
                    and s[0] != main]
    its = [s for s in sp if s[3] == spans.ITERATION]
    for it, h in zip(its, history):
        got = [s for s in sp if s[3] == spans.LOSS_SYNC and _inside(s, it)]
        assert len(got) == 1 and got[0][4]["n_reads"] == h["n_micro"]
        sync, = [s for s in sp if s[3] == spans.STEP_SYNC and _inside(s, it)]
        assert sync[2] <= got[0][1]
        assert it[4]["pipeline_syncs"] == 0
        waits = [s for s in inside if s[3] == spans.RECV_WAIT]
        assert waits and {s[0] for s in waits} <= lines[0] | lines[1]


def test_plan_spans_on_planner_threads(traced):
    sp, history, _, _ = traced
    main = next(s[0] for s in sp if s[3] == spans.ITERATION)
    plans = [s for s in sp if s[3] == spans.PLAN]
    assert len(plans) == N_ITERS and main not in {s[0] for s in plans}
    got = sorted((s[4]["real_tokens"], s[4]["padded_tokens"],
                  s[4]["n_micro"]) for s in plans)
    assert got == sorted((h["tokens"], h["padded_tokens"], h["n_micro"])
                         for h in history)
    assert all(s[4]["predicted_ms"] > 0 for s in plans)


def test_device_programs_carry_stable_names(traced):
    _, _, params, cache = traced
    assert {(key[2], kind) for kind in ("fwd", "bwd", "fwd_bwd")
            for key in cache.keys_for(kind)} == {
        (0, "fwd"), (0, "bwd"), (1, "fwd_bwd")}
    for kind in ("fwd", "bwd", "fwd_bwd"):
        for key, exe in zip(cache.keys_for(kind), cache.entries(kind)):
            assert exe.as_text().startswith(
                f"HloModule jit_stage{key[2]}_{kind},")
    opt_cfg = AdamWConfig()
    opt = init_opt_state(params, opt_cfg)
    text = _scaled_adamw(opt_cfg, donate=False).lower(
        params, params, opt, 1.0).compile().as_text()
    assert text.startswith("HloModule jit_adamw_step,")
    batch = {"tokens": np.zeros((1, 32), np.int32),
             "labels": np.zeros((1, 32), np.int32),
             "loss_weights": np.ones((1, 32), np.float32),
             "positions": np.arange(32, dtype=np.int32)[None],
             "segment_ids": np.zeros((1, 32), np.int32)}
    text = build_grad_step(CFG).lower(
        MD.init_params(jax.random.PRNGKey(0), CFG), batch).compile().as_text()
    assert text.startswith("HloModule jit_grad_step,")


class _NoSpan:
    def __init__(self, name, **args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        pass


def test_losses_bit_identical_without_profiler_and_without_spans(
        traced, monkeypatch):
    _, traced_history, traced_params, cache = traced
    params, history, _ = _run(cache)
    monkeypatch.setattr(spans, "_annotation", _NoSpan)
    bare_params, bare_history, _ = _run(cache)
    for h in (history, bare_history):
        assert [x["loss"] for x in h] == [x["loss"] for x in traced_history]
        assert [x["grad_norm"] for x in h] == [
            x["grad_norm"] for x in traced_history]
    for p in (params, bare_params):
        assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
                   zip(jax.tree.leaves(p), jax.tree.leaves(traced_params)))
