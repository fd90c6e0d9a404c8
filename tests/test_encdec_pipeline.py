"""Encoder-decoder execution, end to end (the paper's T5 workload):
2D materialization, the enc-dec stage layout, pipelined-vs-oracle parity,
and plan-ahead bit-identity on a 2D stream."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.core.cost_model import AnalyticCostModel
from repro.core.executor import PipelineExecutor
from repro.core.instructions import MicroBatchSpec
from repro.core.packing import pack_encdec_first_fit
from repro.core.planner import PlannerConfig, plan_iteration
from repro.core.shapes import ShapePalette
from repro.data.dataset import (materialize_micro_batch,
                                materialize_packed_encdec_rows)
from repro.data.streams import MultiTaskStream, StreamConfig
from repro.models import transformer as T
from repro.train.pipeline_adapter import EncDecPipelinedModel, _xent_sum
from repro.train.runner import (PlanAheadRunner, RunnerConfig,
                                build_encdec_grad_step)

CFG = dataclasses.replace(reduced(get_arch("t5-paper")), n_layers=2)
PAL = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32, max_mbs=8)
STREAM_CFG = StreamConfig(n_tasks=8, global_tokens=512, max_len=96,
                          vocab=CFG.vocab, encdec_fraction=1.0, seed=3)


def _plan_and_batches(n_stages=2, seed_it=0):
    gb = MultiTaskStream(STREAM_CFG).batch(seed_it)
    cm = AnalyticCostModel(CFG, n_stages=n_stages)
    pcfg = PlannerConfig(n_stages=n_stages, d_model=CFG.d_model, palette=PAL)
    plan = plan_iteration(gb.lengths, cm, pcfg).replica_plans[0]
    batches = {m.mb_id: materialize_micro_batch(m, gb.tokens,
                                                lengths=gb.lengths)
               for m in plan.micro_batches}
    return gb, plan, batches


def _oracle_fwd_loss():
    @jax.jit
    def fwd_loss(p, b):
        hd = T.encdec_fwd(p, b["enc_tokens"], b["dec_tokens"], CFG,
                          enc_segments=b["enc_segment_ids"],
                          dec_segments=b["dec_segment_ids"],
                          enc_positions=b["enc_positions"],
                          dec_positions=b["dec_positions"])
        return _xent_sum(p["embed"], hd, b["labels"], b["loss_weights"], CFG)
    return fwd_loss


# --------------------------- materialization ---------------------------
def test_materialize_encdec_splits_and_masks():
    gb = MultiTaskStream(STREAM_CFG).batch(0)
    assert gb.has_decoder and np.all(gb.lengths[:, 1] >= 2)
    spec = MicroBatchSpec(0, [0, 1], mbs=4, seq=(96, 32),
                          t_fwd=0, t_bwd=0, mem=0)
    b = materialize_micro_batch(spec, gb.tokens, lengths=gb.lengths)
    assert b["enc_tokens"].shape == (4, 96)
    assert b["dec_tokens"].shape == (4, 32)
    for row, i in enumerate(spec.sample_indices):
        le, ld = int(gb.lengths[i, 0]), int(gb.lengths[i, 1])
        np.testing.assert_array_equal(b["enc_tokens"][row, :le],
                                      gb.enc_tokens(i)[:96])
        np.testing.assert_array_equal(b["dec_tokens"][row, :ld],
                                      gb.dec_tokens(i)[:32])
        # dec-side labels are next-token shifted within the dec stream only
        np.testing.assert_array_equal(b["labels"][row, : ld - 1],
                                      gb.dec_tokens(i)[1:ld])
        assert b["loss_weights"][row, ld - 1:].sum() == 0
        assert (b["enc_segment_ids"][row, le:] == -1).all()
        assert (b["dec_segment_ids"][row, ld:] == -1).all()
        assert (b["enc_positions"][row, :le] == np.arange(le)).all()
    # empty rows (mbs > n samples) are fully masked
    assert (b["enc_segment_ids"][2:] == -1).all()
    assert b["loss_weights"][2:].sum() == 0


def test_materialize_encdec_requires_lengths():
    gb = MultiTaskStream(STREAM_CFG).batch(0)
    spec = MicroBatchSpec(0, [0], mbs=1, seq=(96, 32),
                          t_fwd=0, t_bwd=0, mem=0)
    with pytest.raises(ValueError, match="lengths"):
        materialize_micro_batch(spec, gb.tokens)


def test_packed_encdec_rows_skip_degenerate_samples():
    """Regression: a dec-only sample (dec_len 0) sharing a packed row must
    be skipped, not abort the whole row — the samples after it still
    materialize."""
    lengths = np.array([[100, 0], [50, 20]])
    tokens = [np.arange(100, dtype=np.int32), np.arange(70, dtype=np.int32)]
    rows = pack_encdec_first_fit(lengths, 160, 32)
    assert rows == [[0, 1]]          # FFD packs both into one row
    b = materialize_packed_encdec_rows(rows, tokens, lengths, 160, 32)
    assert (b["enc_segment_ids"][0] >= 0).sum() == 50   # sample 1 survives
    assert (b["dec_segment_ids"][0] >= 0).sum() == 20
    assert b["loss_weights"].sum() == 19


def test_packed_encdec_rows_pair_segments():
    gb = MultiTaskStream(STREAM_CFG).batch(1)
    rows = pack_encdec_first_fit(gb.lengths, 96, 48)
    assert sorted(i for r in rows for i in r) == list(range(gb.n_samples))
    b = materialize_packed_encdec_rows(rows, gb.tokens, gb.lengths, 96, 48)
    for r, row in enumerate(rows):
        # both sides carry the same set of segments, in the same order
        enc_segs = [s for s in dict.fromkeys(b["enc_segment_ids"][r]) if s >= 0]
        dec_segs = [s for s in dict.fromkeys(b["dec_segment_ids"][r]) if s >= 0]
        assert enc_segs == dec_segs
        assert len(enc_segs) <= len(row)


# --------------------------- stage layout ------------------------------
def test_encdec_layout_boundary():
    assert EncDecPipelinedModel.layout(CFG, 2) == (2, 1)  # 2+2 periods
    assert EncDecPipelinedModel.layout(CFG, 4) == (1, 2)
    with pytest.raises(ValueError):
        EncDecPipelinedModel.layout(CFG, 3)   # 4 periods over 3 stages
    with pytest.raises(ValueError):
        EncDecPipelinedModel.layout(CFG, 1)   # no pipeline
    cfg3 = dataclasses.replace(CFG, n_layers=3)
    assert EncDecPipelinedModel.layout(cfg3, 2) == (3, 1)
    with pytest.raises(ValueError, match="straddles"):
        EncDecPipelinedModel.layout(cfg3, 3)  # k=2 crosses the boundary


def test_encdec_stage_params_cover_model():
    params = T.init_encdec(jax.random.PRNGKey(0), CFG)
    pm = EncDecPipelinedModel(CFG, params, 2)
    s0, s1 = pm.stage_params(0), pm.stage_params(1)
    assert set(s0) == {"stack", "embed", "enc_norm"}
    assert set(s1) == {"stack", "cross", "embed", "dec_norm"}
    assert jax.tree.leaves(s0["stack"])[0].shape[0] == CFG.n_periods
    assert jax.tree.leaves(s1["cross"])[0].shape[0] == CFG.n_periods


# ------------------------- parity with the oracle -----------------------
def test_pipelined_encdec_matches_sequential_oracle_bitwise():
    """The acceptance invariant: 2-stage pipelined enc-dec loss is
    bit-identical to the sequential ``encdec_fwd`` oracle, and gradients
    match to float tolerance."""
    gb, plan, batches = _plan_and_batches(n_stages=2)
    assert all(isinstance(m.seq, tuple) for m in plan.micro_batches)
    params = T.init_encdec(jax.random.PRNGKey(0), CFG)

    pm = EncDecPipelinedModel(CFG, params, 2)
    cbs, result = pm.make_callbacks(plan, batches)
    PipelineExecutor(plan, cbs, timeout=120).run()
    grads_pipe = pm.merge_stage_grads(result["stage_grads"])
    loss_pipe = float(result["loss_sum"]) / result["weight_sum"]

    fwd_loss = _oracle_fwd_loss()
    step = build_encdec_grad_step(CFG)
    ls = ws = 0.0
    gacc = None
    for mb_id in sorted(batches):
        b = {k: jnp.asarray(v) for k, v in batches[mb_id].items()}
        l, w = fwd_loss(params, b)
        ls += float(l)
        ws += float(w)
        _, _, g = step(params, b)
        gacc = g if gacc is None else jax.tree.map(jnp.add, gacc, g)

    assert loss_pipe == ls / ws          # bit-for-bit
    assert np.isfinite(loss_pipe)
    for a, b in zip(jax.tree.leaves(grads_pipe), jax.tree.leaves(gacc)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        denom = max(np.abs(b).max(), 1e-6)
        assert np.abs(a - b).max() / denom < 1e-5


def test_cross_attention_grads_reach_encoder():
    """The he leg of the (he, hd) payload must carry cross-attention
    gradients back: encoder-stage grads are nonzero even though the loss
    lives entirely on the decoder side."""
    _, plan, batches = _plan_and_batches(n_stages=2)
    params = T.init_encdec(jax.random.PRNGKey(1), CFG)
    pm = EncDecPipelinedModel(CFG, params, 2)
    cbs, result = pm.make_callbacks(plan, batches)
    PipelineExecutor(plan, cbs, timeout=120).run()
    enc_grads = result["stage_grads"][0]["stack"]
    assert max(float(jnp.abs(g).max()) for g in jax.tree.leaves(enc_grads)) > 0


# ------------------------- plan-ahead on a 2D stream --------------------
def _runner(synchronous, n_stages=2, use_executor=True, step_cache=None):
    cm = AnalyticCostModel(CFG, n_stages=n_stages)
    pcfg = PlannerConfig(n_stages=n_stages, d_model=CFG.d_model, palette=PAL)
    rcfg = RunnerConfig(n_iters=3, synchronous=synchronous,
                        use_executor=use_executor, log_every=0)
    return PlanAheadRunner(CFG, cm, pcfg, rcfg, MultiTaskStream(STREAM_CFG),
                           step_cache=step_cache)


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


@pytest.mark.slow
def test_plan_ahead_matches_synchronous_on_2d_stream():
    """Double-buffered planning over a 2D (enc, dec) stream changes when
    plans are computed, never what executes — losses and params identical
    through the enc-dec pipeline executor."""
    from repro.train.step_cache import CompiledStepCache
    shared = CompiledStepCache()
    p_async, h_async, s_async = _runner(False, step_cache=shared).run()
    p_sync, h_sync, _ = _runner(True, step_cache=shared).run()
    assert [h["loss"] for h in h_async] == [h["loss"] for h in h_sync]
    assert _tree_equal(p_async, p_sync)
    assert all(np.isfinite(h["loss"]) for h in h_async)
    # 2D cache keys: every compiled stage fn is keyed (mbs, enc, dec)
    fwd_keys = shared.keys_for("fwd")
    assert fwd_keys and all(len(k) == 6 for k in fwd_keys)
    assert all(k[3] in PAL.mbs_buckets and k[4] in PAL.seq_buckets
               and k[5] in PAL.seq_buckets for k in fwd_keys)


@pytest.mark.slow
def test_encdec_sequential_runner_trains():
    """n_stages=1 falls back to the sequential encdec grad step."""
    cm = AnalyticCostModel(CFG, n_stages=1)
    pcfg = PlannerConfig(n_stages=1, d_model=CFG.d_model, palette=PAL)
    rcfg = RunnerConfig(n_iters=3, synchronous=True, use_executor=False,
                        log_every=0)
    _, hist, _ = PlanAheadRunner(CFG, cm, pcfg, rcfg,
                                 MultiTaskStream(STREAM_CFG)).run()
    assert len(hist) == 3
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert all(h["padded_tokens"] >= h["tokens"] for h in hist)
