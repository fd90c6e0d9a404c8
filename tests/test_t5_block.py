"""T5's own block (t5-11b): the relative position buckets, the config at
its published widths, the decoder period (self attention, cross attention,
MLP) with unscaled, biased scores, the bias tables' stage slicing and
gradient merge, and the pipelined model against the sequential oracle."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.core.cost_model import AnalyticCostModel
from repro.core.executor import PipelineExecutor
from repro.core.planner import PlannerConfig, plan_iteration
from repro.core.shapes import ShapePalette
from repro.data.dataset import materialize_micro_batch
from repro.data.streams import MultiTaskStream, StreamConfig
from repro.kernels import relpos
from repro.models import layers as L
from repro.models import transformer as T
from repro.train.pipeline_adapter import EncDecPipelinedModel, _xent_sum
from repro.train.runner import build_encdec_grad_step

CFG = dataclasses.replace(reduced(get_arch("t5-11b")), n_layers=2)
PAL = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32, max_mbs=8)
STREAM_CFG = StreamConfig(n_tasks=8, global_tokens=512, max_len=96,
                          vocab=CFG.vocab, encdec_fraction=1.0, seed=3)

# rel = key position - query position -> bucket (Mesh TF's, 32 buckets,
# max distance 128)
BIDIRECTIONAL = {0: 0, -1: 1, 1: 17, -7: 7, -12: 9, 12: 25, -50: 13,
                 -100: 15, -500: 15, 500: 31}
CAUSAL = {0: 0, 5: 0, -1: 1, -12: 12, -20: 17, -50: 24, -100: 30, -500: 31}


def _bucket(rel, bidirectional):
    return relpos.bucket(jnp.asarray(rel, jnp.int32), n_buckets=32,
                         max_distance=128, bidirectional=bidirectional)


@pytest.mark.parametrize("bidirectional,table", [(True, BIDIRECTIONAL),
                                                 (False, CAUSAL)])
def test_bucket_table(bidirectional, table):
    rel = list(table)
    assert np.asarray(_bucket(rel, bidirectional)).tolist() == \
        [table[r] for r in rel]


def _mesh_tf_bucket(rel, bidirectional, num_buckets=32, max_distance=128):
    """Mesh TF's ``_relative_position_bucket``, float32 as written there."""
    ret = 0
    n = -rel
    if bidirectional:
        num_buckets //= 2
        ret += (n < 0).astype(np.int32) * num_buckets
        n = np.abs(n)
    else:
        n = np.maximum(n, 0)
    max_exact = num_buckets // 2
    with np.errstate(divide="ignore", invalid="ignore"):   # log(0): small
        large = max_exact + (
            np.log(n.astype(np.float32) / np.float32(max_exact))
            / np.float32(math.log(max_distance / max_exact))
            * np.float32(num_buckets - max_exact)).astype(np.int32)
    large = np.minimum(large, num_buckets - 1)
    return ret + np.where(n < max_exact, n, large)


@pytest.mark.parametrize("bidirectional", [True, False])
def test_bucket_matches_mesh_tf_float32(bidirectional):
    rel = np.arange(-2000, 2001, dtype=np.int32)
    np.testing.assert_array_equal(np.asarray(_bucket(rel, bidirectional)),
                                  _mesh_tf_bucket(rel, bidirectional))


def test_t5_11b_at_published_widths():
    cfg = get_arch("t5-11b")
    assert (cfg.family, cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_head,
            cfg.d_ff, cfg.vocab) == ("encdec", 24, 1024, 128, 128, 65536,
                                     32128)
    assert (cfg.rel_attn_buckets, cfg.rel_attn_max_distance) == (32, 128)
    assert cfg.t5_block and not cfg.use_rope and cfg.tie_embeddings
    assert (cfg.act, cfg.mlp_gated, cfg.norm_eps) == ("relu", False, 1e-6)
    assert not get_arch("t5-paper").t5_block


def test_tables_and_init():
    cfg = dataclasses.replace(CFG, d_model=256, d_head=64, dtype="float32")
    p = T.init_encdec(jax.random.PRNGKey(0), cfg)
    for name in ("enc_rel_bias", "dec_rel_bias"):
        assert p[name].shape == (32, cfg.n_heads)
        assert float(jnp.std(p[name])) == pytest.approx(cfg.d_model ** -0.5,
                                                        rel=0.3)
    wq = p["enc"]["l0"]["mixer"]["wq"]
    wk = p["enc"]["l0"]["mixer"]["wk"]
    assert float(jnp.std(wq)) == pytest.approx(
        (cfg.d_model * cfg.d_head) ** -0.5, rel=0.05)
    assert float(jnp.std(wk)) == pytest.approx(cfg.d_model ** -0.5, rel=0.05)
    assert not np.array_equal(p["enc_rel_bias"], p["dec_rel_bias"])


# ------------------------- the decoder period ---------------------------
def _attention(p, x, kv_in, mask, bias, cfg):
    """Unscaled attention written out: scores q·k plus bias, masked."""
    b, t, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = (x @ p["wq"]).reshape(b, t, h, dh)
    k = jnp.repeat((kv_in @ p["wk"]).reshape(b, -1, kv, dh), h // kv, 2)
    v = jnp.repeat((kv_in @ p["wv"]).reshape(b, -1, kv, dh), h // kv, 2)
    s = jnp.einsum("bthd,bshd->bhts", q, k)
    if bias is not None:
        s = s + bias
    s = jnp.where(mask[:, None], s, -1e30)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, t, h * dh) @ p["wo"]


def _mlp(p, x):
    return jax.nn.relu(x @ p["w_in"]) @ p["w_out"]


def test_decoder_period_is_self_cross_mlp_with_unscaled_biased_scores():
    cfg = dataclasses.replace(CFG, n_layers=1, dtype="float32")
    params = T.init_encdec(jax.random.PRNGKey(2), cfg)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    b, td, te = 2, 24, 40
    hd = jax.random.normal(ks[0], (b, td, cfg.d_model))
    he = jax.random.normal(ks[1], (b, te, cfg.d_model))
    pos_d = jnp.broadcast_to(jnp.arange(td, dtype=jnp.int32), (b, td))
    got = T.dec_stage_fwd({"stack": params["dec"], "cross": params["cross"]},
                          hd, he, cfg, positions=pos_d,
                          rel_bias=params["dec_rel_bias"], impl="ref")

    lp = jax.tree.map(lambda x: x[0], params["dec"])["l0"]
    cp = jax.tree.map(lambda x: x[0], params["cross"])
    rel = pos_d[:, None, :] - pos_d[:, :, None]
    bias = jnp.moveaxis(params["dec_rel_bias"][_bucket(rel, False)], -1, 1)
    causal = rel <= 0
    h = hd + _attention(lp["mixer"], L.rms_norm(hd, lp["ln1"], 1e-6),
                        L.rms_norm(hd, lp["ln1"], 1e-6), causal, bias, cfg)
    h = h + _attention(cp["attn"], L.rms_norm(h, cp["ln"], 1e-6), he,
                       jnp.ones((b, td, te), bool), None, cfg)
    want = h + _mlp(lp["ffn"], L.rms_norm(h, lp["ln2"], 1e-6))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------- stage slicing, merge, pipeline ---------------------
def test_tables_ride_every_stage_of_their_stack_and_grads_sum():
    params = T.init_encdec(jax.random.PRNGKey(0), CFG)
    pm = EncDecPipelinedModel(CFG, params, 4)          # 2 enc + 2 dec stages
    for j in range(4):
        table = "enc_rel_bias" if j < 2 else "dec_rel_bias"
        assert pm.stage_params(j)["rel_bias"] is params[table]
    grads = [jax.tree.map(jnp.zeros_like, pm.stage_params(j))
             for j in range(4)]
    for j, g in enumerate(grads):
        g["rel_bias"] = jnp.full_like(g["rel_bias"], j + 1)
    merged = pm.merge_stage_grads(grads)
    assert set(merged) == set(params)
    np.testing.assert_array_equal(np.asarray(merged["enc_rel_bias"]), 3)
    np.testing.assert_array_equal(np.asarray(merged["dec_rel_bias"]), 7)


def test_pipelined_t5_matches_sequential_oracle_bitwise():
    gb = MultiTaskStream(STREAM_CFG).batch(0)
    pcfg = PlannerConfig(n_stages=2, d_model=CFG.d_model, palette=PAL)
    plan = plan_iteration(gb.lengths, AnalyticCostModel(CFG, n_stages=2),
                          pcfg).replica_plans[0]
    batches = {m.mb_id: materialize_micro_batch(m, gb.tokens,
                                                lengths=gb.lengths)
               for m in plan.micro_batches}
    params = T.init_encdec(jax.random.PRNGKey(0), CFG)
    pm = EncDecPipelinedModel(CFG, params, 2)
    cbs, result = pm.make_callbacks(plan, batches)
    PipelineExecutor(plan, cbs, timeout=120).run()
    grads_pipe = pm.merge_stage_grads(result["stage_grads"])

    @jax.jit
    def fwd_loss(p, b):
        hd = T.encdec_fwd(p, b["enc_tokens"], b["dec_tokens"], CFG,
                          enc_segments=b["enc_segment_ids"],
                          dec_segments=b["dec_segment_ids"],
                          enc_positions=b["enc_positions"],
                          dec_positions=b["dec_positions"])
        return _xent_sum(p["embed"], hd, b["labels"], b["loss_weights"], CFG)

    step = build_encdec_grad_step(CFG)
    ls = ws = 0.0
    gacc = None
    for mb_id in sorted(batches):
        b = {k: jnp.asarray(v) for k, v in batches[mb_id].items()}
        l, w = fwd_loss(params, b)
        ls, ws = ls + float(l), ws + float(w)
        _, _, g = step(params, b)
        gacc = g if gacc is None else jax.tree.map(jnp.add, gacc, g)

    assert float(result["loss_sum"]) / result["weight_sum"] == ls / ws  # bitwise
    assert float(jnp.abs(grads_pipe["enc_rel_bias"]).max()) > 0
    assert float(jnp.abs(grads_pipe["dec_rel_bias"]).max()) > 0
    for a, b in zip(jax.tree.leaves(grads_pipe), jax.tree.leaves(gacc)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() / max(np.abs(b).max(), 1e-6) < 1e-5
