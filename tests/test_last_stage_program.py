"""The last pipeline stage's forward-and-backward program.

The last stage trains each micro-batch in one ``stage{c-1}_fwd_bwd``
program, compiled once per shape, with no forward program of its own; with
one period per stage no program checkpoints its period. Checked on a tiny
decoder and a tiny encoder-decoder, at one and at two periods per stage:
the two-stage pipeline matches the sequential grad step (loss bit for bit),
the compile spans record whether periods are checkpointed, and at one
period the fused program multiplies fewer matrices than the separate
forward and backward programs it replaces.
"""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.core import spans
from repro.core.cost_model import AnalyticCostModel
from repro.core.executor import PipelineExecutor, StageCallbacks
from repro.core.instructions import ExecutionPlan, MicroBatchSpec
from repro.core.planner import PlannerConfig, plan_iteration
from repro.core.shapes import ShapePalette
from repro.data.dataset import materialize_micro_batch
from repro.data.streams import MultiTaskStream, StreamConfig
from repro.dist.backend import _timed_callbacks
from repro.models import model as MD
from repro.models import transformer as T
from repro.train import pipeline_adapter as PA

PAL = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32, max_mbs=8)
# (family, periods per stage): n_layers such that 2 stages hold k periods
CASES = [("gpt", 1), ("gpt", 2), ("t5", 1), ("t5", 2)]


def _cfg(family: str, k: int):
    if family == "gpt":
        return dataclasses.replace(reduced(get_arch("gpt-paper")),
                                   n_layers=2 * k)
    # enc-dec: n_layers encoder + n_layers decoder periods over 2 stages
    return dataclasses.replace(reduced(get_arch("t5-paper")), n_layers=k)


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps each span's
    name and args."""
    log: list = []

    def __init__(self, name, **args):
        self.log.append((name, args))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        pass


@pytest.fixture(scope="module", params=CASES,
                ids=[f"{f}-k{k}" for f, k in CASES])
def pipelined(request):
    """One two-stage pipelined step over a planned batch, with the
    compile spans it emitted."""
    family, k = request.param
    cfg = _cfg(family, k)
    encdec = family == "t5"
    stream = StreamConfig(n_tasks=8, global_tokens=512, max_len=96,
                          vocab=cfg.vocab, seed=3,
                          encdec_fraction=1.0 if encdec else 0.0)
    gb = MultiTaskStream(stream).batch(0)
    pcfg = PlannerConfig(n_stages=2, d_model=cfg.d_model, palette=PAL)
    plan = plan_iteration(gb.lengths, AnalyticCostModel(cfg, n_stages=2),
                          pcfg).replica_plans[0]
    batches = {m.mb_id: materialize_micro_batch(m, gb.tokens,
                                                lengths=gb.lengths)
               for m in plan.micro_batches}
    if encdec:
        params = T.init_encdec(jax.random.PRNGKey(0), cfg)
        pm = PA.EncDecPipelinedModel(cfg, params, 2)
    else:
        params = MD.init_params(jax.random.PRNGKey(0), cfg)
        pm = PA.PipelinedModel(cfg, params, 2)
    assert pm.k == k
    _Recorder.log = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "_annotation", _Recorder)
        cbs, result = pm.make_callbacks(plan, batches)
        PipelineExecutor(plan, cbs, timeout=120).run()
    compiles = [args for name, args in _Recorder.log if name == spans.COMPILE]
    return dict(family=family, k=k, cfg=cfg, params=params, pm=pm,
                batches=batches, result=result, compiles=compiles)


def test_last_stage_compiles_one_program_per_shape(pipelined):
    pm, batches = pipelined["pm"], pipelined["batches"]
    cache = pm.step_cache
    shapes = {pm._batch_shape(b) for b in batches.values()}
    by_stage = {}
    for kind in ("fwd", "bwd", "fwd_bwd"):
        for key in cache.keys_for(kind):
            by_stage.setdefault((key[2], kind), set()).add(key[3:])
    assert by_stage == {(0, "fwd"): shapes, (0, "bwd"): shapes,
                        (1, "fwd_bwd"): shapes}
    assert len(cache.keys_for("fwd_bwd")) == len(shapes)
    for exe in cache.entries("fwd_bwd"):
        assert exe.as_text().startswith("HloModule jit_stage1_fwd_bwd,")


def test_pipelined_matches_sequential_grad_step(pipelined):
    cfg, params, pm = pipelined["cfg"], pipelined["params"], pipelined["pm"]
    result, batches = pipelined["result"], pipelined["batches"]
    step = (PA.build_encdec_grad_step(cfg) if pipelined["family"] == "t5"
            else PA.build_grad_step(cfg))
    ls = ws = 0.0
    gacc = None
    for mb_id in sorted(batches):
        b = {key: jnp.asarray(v) for key, v in batches[mb_id].items()}
        loss_sum, w_sum, g = step(params, b)
        ls += float(loss_sum)
        ws += float(w_sum)
        gacc = g if gacc is None else jax.tree.map(jnp.add, gacc, g)
    loss_pipe = float(result["loss_sum"]) / result["weight_sum"]
    assert np.isfinite(loss_pipe)
    assert loss_pipe == ls / ws          # bit for bit
    grads = pm.merge_stage_grads(result["stage_grads"])
    assert jax.tree.structure(grads) == jax.tree.structure(gacc)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(gacc)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() / max(np.abs(b).max(), 1e-6) < 1e-5


def test_compile_spans_record_period_remat(pipelined):
    compiles = pipelined["compiles"]
    assert {(c["stage"], c["kind"]) for c in compiles} == {
        (0, "fwd"), (0, "bwd"), (1, "fwd_bwd")}
    want = int(pipelined["k"] > 1)
    assert [c["remat"] for c in compiles] == [want] * len(compiles)


def _products(exe) -> int:
    """Matrix products (``dot`` and ``convolution``) in an optimized HLO."""
    return sum(" dot(" in line or " convolution(" in line
               for line in exe.as_text().splitlines())


@pytest.mark.parametrize("family", ["gpt", "t5"])
def test_fused_last_stage_multiplies_less_than_a_separate_pair(
        family, monkeypatch):
    """At one period per stage, the fused program has fewer products than
    a separate forward program plus a backward program that recomputes
    the stage forward under a period checkpoint (the layout it replaced),
    and fewer than that backward program alone: it runs the forward once."""
    cfg = _cfg(family, 1)
    init = T.init_encdec if family == "t5" else MD.init_params
    params = init(jax.random.PRNGKey(0), cfg)
    pm = (PA.EncDecPipelinedModel if family == "t5" else PA.PipelinedModel)(
        cfg, params, 2)
    seq, lengths = ((64, 32), np.array([[64, 32]])) if family == "t5" \
        else (64, np.array([64]))
    mb = MicroBatchSpec(0, [0], mbs=2, seq=seq, t_fwd=0, t_bwd=0, mem=0)
    batch = materialize_micro_batch(mb, [np.arange(96, dtype=np.int32)],
                                    lengths=lengths)
    plan = ExecutionPlan(n_stages=2, micro_batches=[mb], per_stage=[[], []])
    pm.compile_plan(plan, {0: batch})
    fused, = pm.step_cache.entries("fwd_bwd")

    # the replaced pair: the last stage's forward, and jax.grad of it from
    # the stashed input, with every period checkpointed
    monkeypatch.setattr(PA, "_period_remat", lambda k: True)
    apply_fn, static = pm._apply_fn, pm._apply_static
    sp = jax.eval_shape(lambda: pm.stage_params(1))
    b = {key: PA._struct(jnp.asarray(v)) for key, v in batch.items()}
    aux = {key: b[key] for key in pm._aux_keys if key in b}
    x = jax.eval_shape(lambda b_, aux_: apply_fn(
        *static, 0, pm.stage_params(0), b_, aux_), b, aux)

    def fwd(sp_, x_, aux_):
        return apply_fn(*static, 1, sp_, x_, aux_)

    def bwd(sp_, x_, aux_):
        return jax.grad(lambda p, x2: fwd(p, x2, aux_)[0],
                        argnums=(0, 1))(sp_, x_)
    pair = [jax.jit(f).lower(sp, x, aux).compile() for f in (fwd, bwd)]
    n_fused, n_fwd, n_bwd = map(_products, [fused] + pair)
    assert 0 < n_fused < n_bwd < n_fwd + n_bwd


def test_timed_callbacks_time_the_fused_program_as_a_total():
    """The calibrator's timings: the last stage's forward is its whole
    fused program ("total"); its backward, a hand-off, is not timed."""
    calls = []

    def cb(j):
        return StageCallbacks(lambda mb, h=None: calls.append((j, "f", mb)),
                              lambda mb, g: calls.append((j, "b", mb)),
                              lambda: None)
    records = []
    cbs = _timed_callbacks([cb(0), cb(1)], records, threading.Lock())
    cbs[0].forward(3)
    cbs[1].forward(3, None)
    cbs[1].backward(3, None)
    cbs[0].backward(3, None)
    assert calls == [(0, "f", 3), (1, "f", 3), (1, "b", 3), (0, "b", 3)]
    assert [r[:2] for r in records] == [("f", 3), ("total", 3), ("b", 3)]
