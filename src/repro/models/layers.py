"""Composable pure-JAX layers: norms, RoPE, GQA attention, MLP, MoE.

Parameters are plain nested dicts. Activations are annotated in-line with
``repro.dist.sharding.shard(x, *logical_dims)`` — logical dims from {"dp",
"tp", "sp", "ep", None}, resolved against the ambient mesh and a no-op
without one — so GSPMD propagates DP/TP/SP placements from those anchors.

dtype policy: params bf16 (cfg.dtype), math that needs it (softmax, norms,
SSM recurrences, loss) in fp32.

Kernel contract: ``ops.attention`` consumes GQA k/v heads natively (no
head repetition here or in the kernels) and is differentiable on every
impl — the Pallas kernels carry fused custom-VJP backwards, so the
``impl`` a caller selects stays in force under ``jax.grad`` (``ref``
remains the oracle).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import axis_size, shard
from repro.kernels import ops


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ----------------------------------------------------------------------
# norms / rope / activations
# ----------------------------------------------------------------------
def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, H, D); positions: (B, T) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, :, None, None] * freqs  # (B,T,1,half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# attention (GQA + RoPE + window + softcap + KV cache)
# ----------------------------------------------------------------------
def heads_even(cfg: ArchConfig) -> bool:
    """Whether attention heads divide the model axis.

    Even (jamba 64H, qwen1.5 64H, hubert 16H): Megatron-style head-parallel
    attention (GQA kv heads smaller than the axis stay replicated — the
    ``shard`` helper drops uneven dims automatically). Uneven (gemma2 8H,
    starcoder2 36H, qwen2.5 40H, granite 24H, llama4 40H, llava 56H on a
    16-way axis): the attention *compute* is sequence-parallel instead."""
    tp = axis_size("tp")
    return tp == 1 or cfg.n_heads % tp == 0


def init_attention(key, cfg: ArchConfig):
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    # fused-head 2D layouts: h*dh and kv*dh divide any power-of-two axis
    # T5 folds the 1/sqrt(d_head) score scale into W_q's init
    wq_scale = (d * dh) ** -0.5 if cfg.t5_block else d ** -0.5
    p = {
        "wq": _init(ks[0], (d, h * dh), wq_scale, dt),
        "wk": _init(ks[1], (d, kv * dh), d ** -0.5, dt),
        "wv": _init(ks[2], (d, kv * dh), d ** -0.5, dt),
        "wo": _init(ks[3], (h * dh, d), (h * dh) ** -0.5, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dt)
        p["bk"] = jnp.zeros((kv * dh,), dt)
        p["bv"] = jnp.zeros((kv * dh,), dt)
    return p


def init_rel_bias(key, cfg: ArchConfig):
    """A stack's T5 relative bias table, (buckets, heads), N(0, d^-1/2)."""
    return _init(key, (cfg.rel_attn_buckets, cfg.n_heads), cfg.d_model ** -0.5,
                 _dtype(cfg))


def attention_fwd(
    p,
    x: jax.Array,                       # (B, T, D)
    cfg: ArchConfig,
    *,
    local: bool,
    positions: jax.Array,               # (B, T)
    segment_ids: Optional[jax.Array],   # (B, T) or None
    cache: Optional[dict] = None,       # {"k","v"}: (B, S, KV, Dh)
    cache_pos: Optional[jax.Array] = None,  # scalar int32: tokens already cached
    mode: str = "train",                # train | prefill | decode
    impl: Optional[str] = None,
    rel_bias: Optional[jax.Array] = None,   # (buckets, H): T5's stack table
):
    window = cfg.window if local else 0
    b, t, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    even = heads_even(cfg)
    q = jnp.einsum("btd,de->bte", x, p["wq"])
    k = jnp.einsum("btd,de->bte", x, p["wk"])
    v = jnp.einsum("btd,de->bte", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, t, h, dh)
    k = k.reshape(b, t, kv, dh)
    v = v.reshape(b, t, kv, dh)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if even:
        # Megatron head-parallel attention
        q = shard(q, "dp", None, "tp", None)
        k = shard(k, "dp", None, "tp", None)
        v = shard(v, "dp", None, "tp", None)
    else:
        # sequence-parallel attention: q over the model axis on seq; k/v
        # replicated (one all-gather per layer); no score-psum needed.
        q = shard(q, "dp", "sp", None, None)
        k = shard(k, "dp", None, None, None)
        v = shard(v, "dp", None, None, None)

    chunk = "q" if even else "head"
    new_cache = None
    if mode == "train":
        out = ops.attention(
            q, k, v, causal=cfg.causal, window=window, softcap=cfg.attn_softcap,
            q_positions=positions, kv_positions=positions,
            q_segment_ids=segment_ids, kv_segment_ids=segment_ids, impl=impl,
            chunk_strategy=chunk, sm_scale=1.0 if cfg.t5_block else None,
            rel_bias=None if rel_bias is None else rel_bias.T,
            rel_max_distance=cfg.rel_attn_max_distance,
        )
    else:
        assert not cfg.t5_block, "T5's block trains only"
        s = cache["k"].shape[1]
        start = jnp.zeros((), jnp.int32) if mode == "prefill" else cache_pos
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, start, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, start, 0, 0))
        cache_seq_dim = "sp" if mode == "decode" else None
        ck = shard(ck, "dp", cache_seq_dim, None, None)
        cv = shard(cv, "dp", cache_seq_dim, None, None)
        new_cache = {"k": ck, "v": cv}
        kv_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        # positions beyond the causal frontier hold garbage but are masked
        # (kv_pos > q_pos). decode: q_pos == cache_pos.
        out = ops.attention(
            q, ck, cv, causal=True, window=window, softcap=cfg.attn_softcap,
            q_positions=positions, kv_positions=kv_pos, impl=impl,
            chunk_strategy=chunk,
        )
    if even:
        out = shard(out, "dp", None, "tp", None)
    else:
        out = shard(out, "dp", "sp", None, None)   # sp auto-dropped when t==1
    y = jnp.einsum("bthk,hkd->btd", out, p["wo"].reshape(h, dh, d))
    return shard(y, "dp", "sp", None), new_cache


# ----------------------------------------------------------------------
# dense MLP
# ----------------------------------------------------------------------
def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    p = {
        "w_in": _init(ks[0], (d, f), d ** -0.5, dt),
        "w_out": _init(ks[1], (f, d), f ** -0.5, dt),
    }
    if cfg.mlp_gated:
        p["w_gate"] = _init(ks[2], (d, f), d ** -0.5, dt)
    return p


def mlp_fwd(p, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    act = act_fn(cfg.act)
    h = jnp.einsum("btd,df->btf", x, p["w_in"])
    if cfg.mlp_gated:
        h = act(jnp.einsum("btd,df->btf", x, p["w_gate"])) * h
    else:
        h = act(h)
    h = shard(h, "dp", None, "tp")
    y = jnp.einsum("btf,fd->btd", h, p["w_out"])
    return shard(y, "dp", "sp", None)


# ----------------------------------------------------------------------
# MoE (top-k, capacity-dropped, scatter/gather dispatch)
# ----------------------------------------------------------------------
def init_moe(key, cfg: ArchConfig):
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    dt = _dtype(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d, e), d ** -0.5, jnp.float32),
        "w_in": _init(ks[1], (e, d, f), d ** -0.5, dt),
        "w_out": _init(ks[2], (e, f, d), f ** -0.5, dt),
    }
    if cfg.mlp_gated:
        p["w_gate"] = _init(ks[3], (e, d, f), d ** -0.5, dt)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.n_shared_experts * cfg.d_ff_expert)
    return p


def _moe_local_compute(xf, router, w_in, w_gate, w_out, cfg: ArchConfig,
                       e0: int | jax.Array, e_local: int):
    """Token dispatch + expert matmuls over a LOCAL token shard and a LOCAL
    expert slice [e0, e0+e_local). Returns (partial_y (N,D) fp32, aux)."""
    n, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    act = act_fn(cfg.act)
    logits = (xf.astype(jnp.float32) @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    cap = int(math.ceil(n * k / e * cfg.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)

    buf = jnp.zeros((e_local * cap, d), xf.dtype)
    counts = jnp.zeros((e,), jnp.int32)
    dests, keeps = [], []
    for j in range(k):
        ej = top_i[:, j]
        oh = jax.nn.one_hot(ej, e, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0), ej[:, None], 1)[:, 0] - 1
        pos = pos + counts[ej]
        el = ej - e0                                  # local expert index
        keep = (pos < cap) & (el >= 0) & (el < e_local)
        dest = jnp.where(keep, el * cap + pos, e_local * cap)
        buf = buf.at[dest].add(xf, mode="drop")
        counts = counts + oh.sum(axis=0)
        dests.append(dest)
        keeps.append(keep)

    buf = buf.reshape(e_local, cap, d)
    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    if cfg.mlp_gated:
        h = act(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * h
    else:
        h = act(h)
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_out).reshape(e_local * cap, d)

    y = jnp.zeros((n, d), jnp.float32)
    for j in range(k):
        got = jnp.take(out_buf, jnp.minimum(dests[j], e_local * cap - 1), axis=0)
        w = (top_p[:, j] * keeps[j]).astype(jnp.float32)
        y = y + got.astype(jnp.float32) * w[:, None]

    frac_tokens = jnp.mean(jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return y, aux


def _moe_fwd_shardmap(p, x: jax.Array, cfg: ArchConfig):
    """MoE under an ambient mesh: tokens dp-local, experts sliced over the
    model axis (EP) or — when E doesn't divide it (granite's 40e/16) —
    expert-internal TP on d_ff. Dispatch runs per dp-shard (local scatter,
    never a GSPMD global scatter); partial outputs psum over the model axis,
    which is the same comm pattern as a row-parallel dense MLP."""
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import ambient_mesh, axis_map
    mesh = ambient_mesh()
    amap = axis_map(mesh)
    dp_axes = amap.get("dp", ())
    tp_axes = amap.get("tp", ())
    tp = 1
    for a in tp_axes:
        tp *= mesh.shape[a]
    e = cfg.n_experts
    ep = e % tp == 0 and tp > 1
    # decode with tiny batches: replicate rows over dp when not divisible
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    if x.shape[0] % max(dp_size, 1):
        dp_axes = ()
    dp_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    tp0 = tp_axes[0] if tp_axes else None

    in_specs = (
        P(dp_spec, None, None),                       # x: rows per dp shard
        P(None, None),                                 # router replicated
        P(tp0 if ep else None, None, None if ep else tp0),   # w_in
        P(tp0 if ep else None, None if ep else tp0, None),   # w_out
    )
    if cfg.mlp_gated:
        in_specs += (P(tp0 if ep else None, None, None if ep else tp0),)
    e_local = e // tp if ep else e

    def local_fn(x_l, router, w_in, w_out, *maybe_gate):
        w_gate = maybe_gate[0] if maybe_gate else None
        b_l, t, d = x_l.shape
        xf = x_l.reshape(b_l * t, d)
        if ep:
            idx = jax.lax.axis_index(tp0)
            e0 = idx * e_local
        else:
            e0 = 0
        y, aux = _moe_local_compute(xf, router, w_in, w_gate, w_out, cfg,
                                    e0, e_local)
        y = jax.lax.psum(y, tp_axes)          # combine expert partials
        aux = jax.lax.pmean(aux, tp_axes)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(b_l, t, d).astype(x_l.dtype), aux

    args = [x, p["router"], p["w_in"], p["w_out"]]
    if cfg.mlp_gated:
        args.append(p["w_gate"])
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False,
    )(*args)
    if cfg.n_shared_experts:
        y = y + mlp_fwd(p["shared"], x, cfg).astype(y.dtype)
    return y, aux


def moe_fwd(p, x: jax.Array, cfg: ArchConfig):
    """Returns (y, aux) with load-balancing loss in aux."""
    from repro.dist.sharding import ambient_mesh, axis_map
    mesh = ambient_mesh()
    if mesh is not None and axis_map(mesh).get("tp"):
        return _moe_fwd_shardmap(p, x, cfg)
    b, t, d = x.shape
    n = b * t
    e, k = cfg.n_experts, cfg.top_k
    act = act_fn(cfg.act)
    xf = x.reshape(n, d)

    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (N,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                                # (N,k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    cap = int(math.ceil(n * k / e * cfg.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)  # align up to 8

    buf = jnp.zeros((e * cap, d), x.dtype)
    counts = jnp.zeros((e,), jnp.int32)
    dests, keeps = [], []
    for j in range(k):
        ej = top_i[:, j]                                   # (N,)
        oh = jax.nn.one_hot(ej, e, dtype=jnp.int32)        # (N,E)
        pos_in_e = jnp.take_along_axis(jnp.cumsum(oh, axis=0), ej[:, None], 1)[:, 0] - 1
        pos_in_e = pos_in_e + counts[ej]
        keep = pos_in_e < cap
        dest = jnp.where(keep, ej * cap + pos_in_e, e * cap)  # OOB => dropped
        buf = buf.at[dest].add(xf, mode="drop")
        counts = counts + oh.sum(axis=0)
        dests.append(dest)
        keeps.append(keep)

    buf = shard(buf.reshape(e, cap, d), "ep", None, None)
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_in"])
    if cfg.mlp_gated:
        h = act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * h
    else:
        h = act(h)
    h = shard(h, "ep", None, "tp")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_out"])
    out_buf = shard(out_buf, "ep", None, None).reshape(e * cap, d)

    y = jnp.zeros((n, d), jnp.float32)
    for j in range(k):
        got = jnp.take(out_buf, jnp.minimum(dests[j], e * cap - 1), axis=0)
        w = (top_p[:, j] * keeps[j]).astype(jnp.float32)
        y = y + got.astype(jnp.float32) * w[:, None]

    if cfg.n_shared_experts:
        y = y + mlp_fwd(p["shared"], x, cfg).reshape(n, d).astype(jnp.float32)

    # Switch-style load-balance aux loss
    frac_tokens = jnp.mean(jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return y.reshape(b, t, d).astype(x.dtype), aux
