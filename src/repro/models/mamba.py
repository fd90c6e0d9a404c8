"""Mamba2 (SSD) mixer block — used by mamba2-130m and jamba's SSM layers.

Structure follows arXiv:2405.21060: fused in_proj -> [z | x | B | C | dt],
causal depthwise conv over [x|B|C], softplus(dt + bias), SSD core (Pallas
chunked kernel or jnp oracle via kernels.ops), per-head D skip, gated
RMSNorm, out_proj. Decode keeps (conv_state, ssm_state) and costs O(1)/token.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import axis_size, shard
from repro.kernels import ops
from repro.models.layers import _dtype, _init, rms_norm


def _tp_ok(cfg: ArchConfig) -> bool:
    """Mamba internals are TP-sharded only when the SSD head count divides
    the model axis (e.g. jamba's 128 heads); otherwise the block runs in
    pure-DP mode to avoid GSPMD reshard storms at the head reshape
    (mamba2-130m's 24 heads on a 16-way axis)."""
    tp = axis_size("tp")
    return tp == 1 or cfg.ssm_heads % tp == 0


def _dims(cfg: ArchConfig):
    di = cfg.d_inner
    g, n, hh = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    conv_ch = di + 2 * g * n
    return di, g, n, hh, conv_ch


def init_mamba(key, cfg: ArchConfig):
    d = cfg.d_model
    di, g, n, hh, conv_ch = _dims(cfg)
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    proj_out = 2 * di + 2 * g * n + hh
    return {
        "in_proj": _init(ks[0], (d, proj_out), d ** -0.5, dt),
        "conv_w": _init(ks[1], (cfg.ssm_conv, conv_ch), 0.3, dt),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.zeros((hh,), jnp.float32),          # A = -exp(A_log) = -1
        "dt_bias": jnp.zeros((hh,), jnp.float32),
        "D": jnp.ones((hh,), jnp.float32),
        "norm_w": jnp.zeros((di,), dt),
        "out_proj": _init(ks[2], (di, d), di ** -0.5, dt),
    }


def _split_proj(zxbcdt, cfg: ArchConfig):
    di, g, n, hh, _ = _dims(cfg)
    z = zxbcdt[..., :di]
    xin = zxbcdt[..., di : 2 * di]
    bc = zxbcdt[..., 2 * di : 2 * di + 2 * g * n]
    dt = zxbcdt[..., 2 * di + 2 * g * n :]
    return z, xin, bc, dt


def _causal_conv(u: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv: u (B,T,C), w (K,C) -> (B,T,C)."""
    k, c = w.shape
    out = jax.lax.conv_general_dilated(
        u.astype(jnp.float32),
        w.astype(jnp.float32)[:, None, :],          # (K, 1, C)
        window_strides=(1,),
        padding=[(k - 1, 0)],
        dimension_numbers=("NTC", "TIO", "NTC"),
        feature_group_count=c,
    )
    return (out + b.astype(jnp.float32)).astype(u.dtype)


def mamba_fwd(
    p,
    x: jax.Array,                       # (B, T, D)
    cfg: ArchConfig,
    *,
    cache: Optional[dict] = None,       # {"conv": (B,K-1,C), "ssm": (B,H,P,N)}
    mode: str = "train",
    impl: Optional[str] = None,
):
    b, t, d = x.shape
    di, g, n, hh, conv_ch = _dims(cfg)
    hd = cfg.ssm_headdim

    tp_ok = _tp_ok(cfg)
    tpd = "tp" if tp_ok else None
    if not tp_ok:
        x = shard(x, "dp", None, None)
    zxbcdt = jnp.einsum("btd,de->bte", x, p["in_proj"])
    z, xin, bc, dtp = _split_proj(zxbcdt, cfg)
    # the fused projection width (2*di + 2*g*n + h) is generally not divisible
    # by the model axis, but the post-split slices are — constrain those.
    z = shard(z, "dp", None, tpd)
    u = jnp.concatenate([xin, bc], axis=-1)          # (B,T,conv_ch)
    u = shard(u, "dp", None, tpd)

    new_cache = None
    if mode == "decode":
        conv_state = cache["conv"]                    # (B, K-1, C)
        win = jnp.concatenate([conv_state, u], axis=1)          # (B,K,C)
        conv = jnp.einsum("bkc,kc->bc", win.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32))
        conv = (conv + p["conv_b"].astype(jnp.float32))[:, None, :].astype(x.dtype)
        new_conv_state = win[:, 1:, :]
    else:
        conv = _causal_conv(u, p["conv_w"], p["conv_b"])
        new_conv_state = None
        if mode == "prefill":
            k = cfg.ssm_conv
            pad = jnp.zeros((b, k - 1, conv_ch), u.dtype)
            new_conv_state = jnp.concatenate([pad, u], axis=1)[:, -(k - 1):, :]
    conv = jax.nn.silu(conv.astype(jnp.float32)).astype(x.dtype)
    if mode != "decode":
        conv = shard(conv, "dp", None, tpd)

    xc = conv[..., :di]
    bcc = conv[..., di:]
    Bc = bcc[..., : g * n].reshape(b, -1, g, n)
    Cc = bcc[..., g * n :].reshape(b, -1, g, n)
    dt = jax.nn.softplus(dtp.astype(jnp.float32) + p["dt_bias"])  # (B,T,H)
    A = -jnp.exp(p["A_log"])                                       # (H,)

    if mode == "decode":
        xh = xc.reshape(b, hh, hd)
        y, new_ssm = ops.ssd_decode(xh, dt[:, 0], A, Bc[:, 0], Cc[:, 0], cache["ssm"])
        y = y + p["D"][None, :, None] * xh.astype(jnp.float32)
        y = y.reshape(b, 1, di).astype(x.dtype)
        new_cache = {"conv": new_conv_state, "ssm": new_ssm.astype(cache["ssm"].dtype)}
    else:
        xh = xc.reshape(b, t, hh, hd)
        xh = shard(xh, "dp", None, tpd, None)
        if mode == "prefill":
            y, st = ops.ssd(xh, dt, A, Bc, Cc, return_state=True, impl=impl)
            new_cache = {"conv": new_conv_state, "ssm": st.astype(jnp.float32)}
        else:
            y = ops.ssd(xh, dt, A, Bc, Cc, impl=impl)
        y = y + (p["D"][None, None, :, None] * xh.astype(jnp.float32)).astype(y.dtype)
        y = y.reshape(b, -1, di)

    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.astype(x.dtype), p["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bte,ed->btd", y, p["out_proj"])
    return shard(out, "dp", "sp", None), new_cache
