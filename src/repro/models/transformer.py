"""Layer stacks: periodic-pattern decoder/encoder + T5-style encoder-decoder.

Big models scan over *periods* (one period = one repetition of
``cfg.layer_pattern``, e.g. jamba's 8-layer mamba/attn interleave) with
parameters stacked on a leading ``n_periods`` axis — O(1) HLO size in depth.
``jax.checkpoint`` on the period body gives per-period remat: the only
activations saved across the backward pass are the period-boundary residuals
(which are SP-sharded), everything else is recomputed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.dist.sharding import shard
from repro.models import layers as L
from repro.models import mamba as M

# ----------------------------------------------------------------------
# per-layer block
# ----------------------------------------------------------------------
def init_block(key, cfg: ArchConfig, spec: LayerSpec):
    ks = jax.random.split(key, 3)
    dt = L._dtype(cfg)
    p: dict = {"ln1": jnp.zeros((cfg.d_model,), dt)}
    if spec.mixer == "mamba":
        p["mixer"] = M.init_mamba(ks[0], cfg)
    else:
        p["mixer"] = L.init_attention(ks[0], cfg)
    if spec.moe:
        p["ln2"] = jnp.zeros((cfg.d_model,), dt)
        p["ffn"] = L.init_moe(ks[1], cfg)
    elif cfg.d_ff:
        p["ln2"] = jnp.zeros((cfg.d_model,), dt)
        p["ffn"] = L.init_mlp(ks[1], cfg)
    return p


def block_fwd(p, h, cfg: ArchConfig, spec: LayerSpec, *,
              positions, segment_ids, cache=None, cache_pos=None,
              mode="train", impl=None, rel_bias=None):
    x = L.rms_norm(h, p["ln1"], cfg.norm_eps)
    if spec.mixer == "mamba":
        y, new_cache = M.mamba_fwd(p["mixer"], x, cfg, cache=cache, mode=mode, impl=impl)
    else:
        y, new_cache = L.attention_fwd(
            p["mixer"], x, cfg, local=(spec.mixer == "attn_local"),
            positions=positions, segment_ids=segment_ids,
            cache=cache, cache_pos=cache_pos, mode=mode, impl=impl,
            rel_bias=rel_bias,
        )
    h = h + y
    aux = jnp.zeros((), jnp.float32)
    if "ffn" in p:
        x = L.rms_norm(h, p["ln2"], cfg.norm_eps)
        if spec.moe:
            y, aux = L.moe_fwd(p["ffn"], x, cfg)
        else:
            y = L.mlp_fwd(p["ffn"], x, cfg)
        h = h + y
    h = shard(h, "dp", "sp", None)
    return h, new_cache, aux


# ----------------------------------------------------------------------
# cache construction
# ----------------------------------------------------------------------
def init_cache(cfg: ArchConfig, batch: int, seq: int, dtype=jnp.bfloat16):
    """Per-period-position cache, stacked over periods: tuple of dicts."""
    caches = []
    np_ = cfg.n_periods
    for spec in cfg.layer_pattern:
        if spec.mixer == "mamba":
            di, g, n, hh, conv_ch = M._dims(cfg)
            caches.append({
                "conv": jnp.zeros((np_, batch, cfg.ssm_conv - 1, conv_ch), dtype),
                "ssm": jnp.zeros((np_, batch, hh, cfg.ssm_headdim, n), jnp.float32),
            })
        else:
            caches.append({
                "k": jnp.zeros((np_, batch, seq, cfg.n_kv_heads, cfg.d_head), dtype),
                "v": jnp.zeros((np_, batch, seq, cfg.n_kv_heads, cfg.d_head), dtype),
            })
    return tuple(caches)


# ----------------------------------------------------------------------
# the stack
# ----------------------------------------------------------------------
def init_stack(key, cfg: ArchConfig):
    """Params stacked over periods: leaf shape (n_periods, *leaf_shape)."""
    def one_period(k):
        ks = jax.random.split(k, len(cfg.layer_pattern))
        return {f"l{i}": init_block(ks[i], cfg, spec)
                for i, spec in enumerate(cfg.layer_pattern)}
    keys = jax.random.split(key, cfg.n_periods)
    periods = [one_period(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *periods)


def stack_fwd(params, h, cfg: ArchConfig, *,
              positions, segment_ids, cache=None, cache_pos=None,
              mode="train", impl=None, remat=True, rel_bias=None):
    """Scan over periods. Returns (h, new_cache, aux_sum). ``rel_bias`` is
    the stack's T5 bias table, shared by every layer."""

    def period_fn(h, xs):
        pparams, pcache = xs
        new_caches = []
        aux_total = jnp.zeros((), jnp.float32)
        for i, spec in enumerate(cfg.layer_pattern):
            lc = pcache[i] if pcache is not None else None
            h, nc, aux = block_fwd(
                pparams[f"l{i}"], h, cfg, spec,
                positions=positions, segment_ids=segment_ids,
                cache=lc, cache_pos=cache_pos, mode=mode, impl=impl,
                rel_bias=rel_bias,
            )
            new_caches.append(nc if nc is not None else jnp.zeros((), jnp.float32))
            aux_total = aux_total + aux
        return h, (tuple(new_caches), aux_total)

    if remat:
        period_fn = jax.checkpoint(
            period_fn, policy=jax.checkpoint_policies.nothing_saveable)

    cache_xs = cache if cache is not None else _none_like_periods(params, cfg)
    xs = (params, cache_xs)
    h, (new_cache, aux) = jax.lax.scan(period_fn, h, xs)
    if cache is None:
        new_cache = None
    return h, new_cache, jnp.sum(aux)


def _none_like_periods(params, cfg):
    """Placeholder xs when no cache: zeros scanned alongside params."""
    return tuple(jnp.zeros((cfg.n_periods,), jnp.float32)
                 for _ in cfg.layer_pattern)


# ----------------------------------------------------------------------
# T5-style encoder-decoder (the paper's flagship workload)
# ----------------------------------------------------------------------
def init_encdec(key, cfg: ArchConfig):
    """Cross-attention params are stacked *period-major* (leading dim
    ``n_periods``, like the enc/dec stacks) so they slice into pipeline
    stages the same way: stage j of the decoder owns ``cross[j*k:(j+1)*k]``
    alongside ``dec[j*k:(j+1)*k]``. One cross block runs after each period
    (T5 has per-layer cross-attn; t5-paper's period is 1 layer, so exact).
    T5's block adds one relative bias table per stack, ``enc_rel_bias``
    and ``dec_rel_bias`` (buckets, heads), from keys 3 and 5."""
    ks = jax.random.split(key, 6)
    dt = L._dtype(cfg)
    dec_cross = []
    for i in range(cfg.n_periods):
        kk = jax.random.fold_in(ks[4], i)
        dec_cross.append({"ln": jnp.zeros((cfg.d_model,), dt),
                          "attn": L.init_attention(kk, cfg)})
    params = {
        "embed": L._init(ks[0], (cfg.vocab_padded, cfg.d_model), 1.0, dt),
        "enc": init_stack(ks[1], cfg),
        "dec": init_stack(ks[2], cfg),
        "cross": jax.tree.map(lambda *xs: jnp.stack(xs), *dec_cross),
        "enc_norm": jnp.zeros((cfg.d_model,), dt),
        "dec_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if cfg.t5_block:
        params["enc_rel_bias"] = L.init_rel_bias(ks[3], cfg)
        params["dec_rel_bias"] = L.init_rel_bias(ks[5], cfg)
    return params


def cross_attention_fwd(p, x, he, cfg: ArchConfig, *,
                        q_segment_ids=None, kv_segment_ids=None, impl=None):
    """One cross-attention block: queries from the decoder stream ``x``,
    keys/values from the encoder output ``he`` (no RoPE — absolute content
    addressing). Segment ids mask padded encoder keys and, in packed rows,
    keep each decoder segment on its own encoder segment. Returns the
    residual delta (caller adds it to ``x``'s stream)."""
    xn = L.rms_norm(x, p["ln"], cfg.norm_eps)
    hh, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    b = xn.shape[0]
    q = jnp.einsum("btd,de->bte", xn, p["attn"]["wq"]).reshape(b, -1, hh, dh)
    k = jnp.einsum("bsd,de->bse", he, p["attn"]["wk"]).reshape(b, -1, kv, dh)
    v = jnp.einsum("bsd,de->bse", he, p["attn"]["wv"]).reshape(b, -1, kv, dh)
    from repro.kernels import ops
    o = ops.attention(q, k, v, causal=False,
                      q_segment_ids=q_segment_ids,
                      kv_segment_ids=kv_segment_ids, impl=impl,
                      sm_scale=1.0 if cfg.t5_block else None)
    return jnp.einsum("bthk,hkd->btd", o,
                      p["attn"]["wo"].reshape(hh, dh, cfg.d_model))


def enc_stage_fwd(stack_params, h, cfg: ArchConfig, *,
                  positions, segment_ids=None, impl=None, remat=True,
                  rel_bias=None):
    """Encoder slice: non-causal stack over ``stack_params``'s periods.
    ``cfg.n_periods`` must equal the slice's period count (pipeline stages
    pass a ``dataclasses.replace``d sub-config). ``h`` is already embedded.
    ``rel_bias`` is T5's encoder table."""
    enc_cfg = cfg if not cfg.causal else _replace_causal(cfg, False)
    h, _, _ = stack_fwd(stack_params, h, enc_cfg, positions=positions,
                        segment_ids=segment_ids, impl=impl, remat=remat,
                        rel_bias=rel_bias)
    return h


def t5_dec_layer(p, cross_p, h, he, cfg: ArchConfig, *, positions,
                 segment_ids, enc_segment_ids, rel_bias, impl=None):
    """T5's decoder layer: causal self-attention with the decoder table,
    then cross-attention against ``he``, then the MLP, each pre-norm."""
    y, _ = L.attention_fwd(p["mixer"], L.rms_norm(h, p["ln1"], cfg.norm_eps),
                           cfg, local=False, positions=positions,
                           segment_ids=segment_ids, impl=impl,
                           rel_bias=rel_bias)
    h = h + y
    h = h + cross_attention_fwd(cross_p, h, he, cfg,
                                q_segment_ids=segment_ids,
                                kv_segment_ids=enc_segment_ids, impl=impl)
    h = h + L.mlp_fwd(p["ffn"], L.rms_norm(h, p["ln2"], cfg.norm_eps), cfg)
    return shard(h, "dp", "sp", None)


def dec_stage_fwd(params, hd, he, cfg: ArchConfig, *,
                  positions, segment_ids=None, enc_segment_ids=None,
                  impl=None, remat=True, rel_bias=None):
    """Decoder slice: causal self-attention periods, each followed by
    cross-attention against the encoder output ``he`` (T5's block:
    ``t5_dec_layer``, cross-attention before the MLP, with the decoder
    table ``rel_bias``). ``params`` carries period-major ``{"stack",
    "cross"}`` slices of equal leading length; ``he`` is the *final*
    encoder output, which the pipeline forwards unchanged to every decoder
    stage."""

    def dec_period(h, xs):
        pparams, cross_p = xs
        if cfg.t5_block:
            assert len(cfg.layer_pattern) == 1, "T5's period is one layer"
            return t5_dec_layer(pparams["l0"], cross_p, h, he, cfg,
                                positions=positions, segment_ids=segment_ids,
                                enc_segment_ids=enc_segment_ids,
                                rel_bias=rel_bias, impl=impl), None
        for i, spec in enumerate(cfg.layer_pattern):
            h, _, _ = block_fwd(pparams[f"l{i}"], h, cfg, spec,
                                positions=positions, segment_ids=segment_ids,
                                impl=impl)
        h = h + cross_attention_fwd(cross_p, h, he, cfg,
                                    q_segment_ids=segment_ids,
                                    kv_segment_ids=enc_segment_ids, impl=impl)
        return h, None

    fn = jax.checkpoint(dec_period) if remat else dec_period
    hd, _ = jax.lax.scan(fn, hd, (params["stack"], params["cross"]))
    return hd


def encdec_fwd(params, enc_tokens, dec_tokens, cfg: ArchConfig, *,
               enc_segments=None, dec_segments=None,
               enc_positions=None, dec_positions=None,
               impl=None, remat=True):
    """Sequential oracle: the full encoder-decoder forward, composed of the
    same ``enc_stage_fwd``/``dec_stage_fwd`` primitives the pipelined
    execution slices — pipelined runs are parity-tested against this.
    Returns the tied head's input (B, T_dec, D): ``dec_head_input``."""
    b, t_enc = enc_tokens.shape
    t_dec = dec_tokens.shape[1]
    if enc_positions is None:
        enc_positions = jnp.broadcast_to(
            jnp.arange(t_enc, dtype=jnp.int32)[None], (b, t_enc))
    if dec_positions is None:
        dec_positions = jnp.broadcast_to(
            jnp.arange(t_dec, dtype=jnp.int32)[None], (b, t_dec))

    he = jnp.take(params["embed"], enc_tokens, axis=0)
    he = enc_stage_fwd(params["enc"], he, cfg, positions=enc_positions,
                       segment_ids=enc_segments, impl=impl, remat=remat,
                       rel_bias=params.get("enc_rel_bias"))
    he = L.rms_norm(he, params["enc_norm"], cfg.norm_eps)

    hd = jnp.take(params["embed"], dec_tokens, axis=0)
    hd = dec_stage_fwd({"stack": params["dec"], "cross": params["cross"]},
                       hd, he, cfg, positions=dec_positions,
                       segment_ids=dec_segments,
                       enc_segment_ids=enc_segments, impl=impl, remat=remat,
                       rel_bias=params.get("dec_rel_bias"))
    return dec_head_input(hd, params["dec_norm"], cfg)


def dec_head_input(hd, dec_norm, cfg: ArchConfig):
    """The decoder's final RMSNorm, and T5's d_model^-1/2 before the tied
    head."""
    hd = L.rms_norm(hd, dec_norm, cfg.norm_eps)
    return hd * cfg.d_model ** -0.5 if cfg.t5_block else hd


def _replace_causal(cfg: ArchConfig, causal: bool) -> ArchConfig:
    import dataclasses
    return dataclasses.replace(cfg, causal=causal)
