"""Plain float32 building blocks of the reference models.

Everything here is ``jax.numpy`` at ``Precision.HIGHEST`` (true float32 on
a TPU, whose default would run float32 matmuls in bf16 passes). The one
switch is ``prec``: ``"f32"`` is the reference; ``"fp8"`` rounds both
operands of every product to float8 e4m3 with a per-tensor scale (gradients
pass straight through the rounding) and is the control that stands one
precision below the configuration's bf16.

Nothing is imported from the program. The weight recipe (``normal``) is
the program's published init scheme, written out again: a seed gives the
same weights here as there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
VOCAB_ALIGN = 256


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_ALIGN) * VOCAB_ALIGN


def normal(key, shape, scale):
    """N(0, 1) * scale rounded to bf16, held in f32: the stored weights."""
    x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def zeros(n: int):
    return jnp.zeros((n,), jnp.float32)


def _fp8(x):
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def dot(eq: str, a, b, prec: str):
    if prec == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rms_norm(x, w, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, positions, theta: float):
    """Rotate the two halves of the head dim; x (B, T, H, D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, :, None, None] * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, mask, prec: str):
    """q (B, T, H, D), k/v (B, S, KV, D), mask (B, T, S) -> (B, T, H, D).
    Rows with no visible key give zeros."""
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = dot("bthd,bshd->bhts", q, k, prec) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    m = mask[:, None]
    s = jnp.where(m, s, -1e30)
    e = jnp.exp(s - jax.lax.stop_gradient(jnp.max(s, -1, keepdims=True)))
    e = jnp.where(m, e, 0.0)
    p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    return dot("bhts,bshd->bthd", p, v, prec)


def act(name: str, x):
    if name == "gelu":                  # tanh form, as jax.nn.gelu's default
        return 0.5 * x * (1.0 + jnp.tanh(
            jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))
    if name == "relu":
        return jnp.maximum(x, 0.0)
    raise ValueError(f"activation {name!r}")


def xent_sum(head, h, labels, weights, vocab: int, prec: str):
    """Summed next-token cross entropy over weighted positions, and the
    weight sum. ``head`` is (padded vocab, D); padded rows never win."""
    logits = dot("btd,vd->btv", h, head, prec)
    ok = jnp.arange(head.shape[0]) < vocab
    logits = jnp.where(ok[None, None], logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum((lse - ll) * weights), jnp.sum(weights)


def bucket(n: int, lo: int = 128) -> int:
    b = lo
    while b < n:
        b *= 2
    return b
