"""Model facade: init / forward / loss / prefill / decode.

One entry point per mode:
  train   -> ``loss_fn``            (lowered per micro-batch by the executor)
  prefill -> ``prefill``            (full-sequence forward, returns KV/SSM cache)
  decode  -> ``decode``             (one token against the cache)

Batch schemas (all provided by the data pipeline):
  tokens : {tokens, labels, loss_weights, positions, segment_ids}
  mixed  : + patches (B, P, d_model) precomputed anyres embeddings (vlm stub)
  frames : {frames (B,S,d_model), mask, labels, loss_weights} (audio stub)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import shard
from repro.models import layers as L
from repro.models import transformer as T

LOSS_CHUNK = 512

# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------
def init_params(key, cfg: ArchConfig):
    ks = jax.random.split(key, 6)
    dt = L._dtype(cfg)
    p = {
        "embed": L._init(ks[0], (cfg.vocab_padded, cfg.d_model), 1.0, dt),
        "stack": T.init_stack(ks[1], cfg),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if cfg.input_mode == "frames":
        p["frame_adapter"] = L._init(ks[2], (cfg.d_model, cfg.d_model),
                                     cfg.d_model ** -0.5, dt)
        p["mask_emb"] = L._init(ks[3], (cfg.d_model,), 0.02, dt)
    if cfg.input_mode == "mixed":
        p["patch_adapter"] = L._init(ks[2], (cfg.d_model, cfg.d_model),
                                     cfg.d_model ** -0.5, dt)
    if not cfg.tie_embeddings:
        p["head"] = L._init(ks[4], (cfg.vocab_padded, cfg.d_model),
                            cfg.d_model ** -0.5, dt)
    return p


def _head_weight(params):
    return params.get("head", params["embed"])


# ----------------------------------------------------------------------
# embedding / trunk
# ----------------------------------------------------------------------
def embed_inputs(params, batch, cfg: ArchConfig, *, mode="train"):
    """Returns h (B, S, D)."""
    if cfg.input_mode == "frames":
        h = jnp.einsum("btd,de->bte", batch["frames"].astype(L._dtype(cfg)),
                       params["frame_adapter"])
        mask = batch["mask"][..., None]
        h = jnp.where(mask, params["mask_emb"].astype(h.dtype), h)
    elif cfg.input_mode == "mixed" and mode != "decode":
        htok = jnp.take(params["embed"], batch["tokens"], axis=0)
        hpatch = jnp.einsum("bpd,de->bpe", batch["patches"].astype(L._dtype(cfg)),
                            params["patch_adapter"])
        h = jnp.concatenate([hpatch, htok], axis=1)
    else:
        h = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.scale_embed:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return shard(h, "dp", "sp", None)


def forward(params, batch, cfg: ArchConfig, *, mode="train",
            cache=None, cache_pos=None, impl=None, remat=True):
    h = embed_inputs(params, batch, cfg, mode=mode)
    h, new_cache, aux = T.stack_fwd(
        params["stack"], h, cfg,
        positions=batch["positions"],
        segment_ids=batch.get("segment_ids"),
        cache=cache, cache_pos=cache_pos, mode=mode, impl=impl, remat=remat,
    )
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return h, new_cache, aux


# ----------------------------------------------------------------------
# loss (chunked over sequence; logits never fully materialized)
# ----------------------------------------------------------------------
def _xent_chunk(head_w, h_c, labels_c, w_c, cfg: ArchConfig):
    logits = jnp.einsum("btd,vd->btv", h_c, head_w).astype(jnp.float32)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    vocab_ok = jnp.arange(cfg.vocab_padded) < cfg.vocab
    logits = jnp.where(vocab_ok[None, None, :], logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # vocab-parallel label pick: a select-and-reduce over the (sharded) vocab
    # dim — GSPMD keeps it local + a scalar psum. (take_along_axis on a
    # sharded dim all-gathers the whole logits chunk.)
    onehot = (jnp.arange(cfg.vocab_padded)[None, None, :]
              == labels_c[..., None])
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    return jnp.sum((lse - ll) * w_c), jnp.sum(w_c)


def lm_loss(params, h, labels, weights, cfg: ArchConfig):
    """Chunked softmax-xent. h (B,T,D); labels/weights (B,T)."""
    b, t, d = h.shape
    chunk = min(LOSS_CHUNK, t)
    while t % chunk:
        chunk //= 2
    n = t // chunk
    head_w = _head_weight(params)
    hc = h.reshape(b, n, chunk, d).swapaxes(0, 1)          # (n, B, c, D)
    lc = labels.reshape(b, n, chunk).swapaxes(0, 1)
    wc = weights.reshape(b, n, chunk).swapaxes(0, 1)

    body = jax.checkpoint(
        lambda carry, xs: (
            tuple(a + b_ for a, b_ in zip(carry, _xent_chunk(head_w, *xs, cfg))),
            None,
        )
    )
    (loss_sum, w_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, lc, wc))
    return loss_sum / jnp.maximum(w_sum, 1.0)


def loss_fn(params, batch, cfg: ArchConfig, *, impl=None, remat=True,
            aux_weight: float = 0.01):
    """Scalar training loss (+ MoE load-balance aux)."""
    h, _, aux = forward(params, batch, cfg, mode="train", impl=impl, remat=remat)
    loss = lm_loss(params, h, batch["labels"], batch["loss_weights"], cfg)
    if cfg.has_moe:
        loss = loss + aux_weight * aux / cfg.n_layers
    return loss, {"xent": loss, "moe_aux": aux}


# ----------------------------------------------------------------------
# serving
# ----------------------------------------------------------------------
def prefill(params, batch, cfg: ArchConfig, *, impl=None, cache_len=None):
    """Full-sequence forward. Returns (last_logits (B,Vp), cache).

    ``cache_len`` (>= seq len) sizes the KV cache so subsequent decode steps
    have headroom; defaults to the prompt length."""
    b = batch["positions"].shape[0]
    s = cache_len or batch["positions"].shape[1]
    if cfg.decode:
        cache = T.init_cache(cfg, b, s, dtype=L._dtype(cfg))
        h, new_cache, _ = forward(params, batch, cfg, mode="prefill",
                                  cache=cache,
                                  cache_pos=jnp.zeros((), jnp.int32),
                                  impl=impl, remat=False)
    else:  # encoder-only: prefill == full encode forward (no cache)
        h, new_cache, _ = forward(params, batch, cfg, mode="train",
                                  impl=impl, remat=False)
    logits = jnp.einsum("bd,vd->bv", h[:, -1, :], _head_weight(params))
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.final_softcap)
    return logits.astype(jnp.float32), new_cache


def decode(params, batch, cfg: ArchConfig, *, impl=None):
    """One decode step. batch: {tokens (B,1), positions (B,1), cache, cache_pos}.

    Returns (logits (B, Vp), new_cache).
    """
    h, new_cache, _ = forward(
        params, batch, cfg, mode="decode",
        cache=batch["cache"], cache_pos=batch["cache_pos"],
        impl=impl, remat=False,
    )
    logits = jnp.einsum("bd,vd->bv", h[:, -1, :], _head_weight(params))
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.final_softcap)
    return logits.astype(jnp.float32), new_cache
