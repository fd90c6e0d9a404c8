"""Plain reference of T5's encoder-decoder (t5-11b, as Raffel et al. 2020
and Mesh TF define it), written apart from the program.

Encoder layer: h += Wo·attn(RMSNorm(h)) with scores q·k (no 1/sqrt(d)
scale) plus the encoder's relative bias B_enc[head, bucket(k − q)],
bidirectional buckets; then h += W_out·relu(W_in·RMSNorm(h)). Decoder
layer: causal self attention with B_dec and causal buckets, then cross
attention over the encoder output (no bias, padded encoder keys masked),
then the MLP. Each stack ends in RMSNorm; the decoder's output times
d_model^-1/2 meets the tied embedding as the head, with next-token cross
entropy on the decoder side. RMSNorm scales by (1 + w). Each (encoder,
decoder) pair is a row of its own, as ``encdec.blocks`` lays them out.

Weights: the program's recipe from ``PRNGKey(seed)``: split 6; embed
N(0,1) from key 0; encoder layers from key 1, decoder layers from key 2
(split per layer, then 3: attention from the first (split 4: q at
(d·d_head)^-1/2, k and v at d^-1/2, o at (h·d_head)^-1/2), MLP from the
second (split 3: in at d^-1/2, out at d_ff^-1/2)); cross attention of
layer i from ``fold_in(key 4, i)``, likewise; the bias tables
(buckets, heads) at d^-1/2 from keys 3 (encoder) and 5 (decoder); norms
start at 0; all rounded to bf16 as stored.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chip_bench import refmath as R
from chip_bench.references.encdec import blocks  # noqa: F401  (row blocks)

STACKED = ("enc", "dec", "cross")


def bucket(relative_position, bidirectional: bool, num_buckets: int,
           max_distance: int):
    """Mesh TF's ``_relative_position_bucket``, in float32 as it is there;
    ``relative_position`` is key position minus query position."""
    ret = 0
    n = -relative_position
    if bidirectional:
        num_buckets //= 2
        ret += (n < 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = jnp.maximum(n, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    val_if_large = max_exact + (
        jnp.log(n.astype(jnp.float32) / max_exact)
        / math.log(max_distance / max_exact)
        * (num_buckets - max_exact)).astype(jnp.int32)
    val_if_large = jnp.minimum(val_if_large, num_buckets - 1)
    return ret + jnp.where(is_small, n, val_if_large)


def _attn_init(key, m):
    d, h, kv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    ks = jax.random.split(key, 4)
    return {"wq": R.normal(ks[0], (d, h * dh), (d * dh) ** -0.5),
            "wk": R.normal(ks[1], (d, kv * dh), d ** -0.5),
            "wv": R.normal(ks[2], (d, kv * dh), d ** -0.5),
            "wo": R.normal(ks[3], (h * dh, d), (h * dh) ** -0.5)}


def _layer_init(key, m):
    d, f = m["d_model"], m["d_ff"]
    ks = jax.random.split(key, 3)
    km = jax.random.split(ks[1], 3)
    return {"ln1": R.zeros(d), "mixer": _attn_init(ks[0], m),
            "ln2": R.zeros(d),
            "ffn": {"w_in": R.normal(km[0], (d, f), d ** -0.5),
                    "w_out": R.normal(km[1], (f, d), f ** -0.5)}}


def _stack(xs):
    return jax.tree.map(lambda *a: jnp.stack(a), *xs)


def init(seed: int, m: dict):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    vp, d, n = R.padded_vocab(m["vocab"]), m["d_model"], m["n_layers"]

    def layers(key):
        return _stack([{"l0": _layer_init(jax.random.split(k, 1)[0], m)}
                       for k in jax.random.split(key, n)])
    table = (m["rel_attn_buckets"], m["n_heads"])
    return {"embed": R.normal(ks[0], (vp, d), 1.0),
            "enc": layers(ks[1]),
            "dec": layers(ks[2]),
            "cross": _stack([{"ln": R.zeros(d), "attn": _attn_init(
                jax.random.fold_in(ks[4], i), m)} for i in range(n)]),
            "enc_norm": R.zeros(d),
            "dec_norm": R.zeros(d),
            "enc_rel_bias": R.normal(ks[3], table, d ** -0.5),
            "dec_rel_bias": R.normal(ks[5], table, d ** -0.5)}


def _position_bias(table, q_pos, k_pos, bidirectional, m):
    """(B, H, T, S): table[bucket(k − q), head]."""
    b = bucket(k_pos[:, None, :] - q_pos[:, :, None], bidirectional,
               m["rel_attn_buckets"], m["rel_attn_max_distance"])
    return jnp.moveaxis(table[b], -1, 1)


def _attention(p, x, kv_in, mask, bias, m, prec):
    """Unscaled multi-head attention of queries from ``x`` over keys and
    values from ``kv_in``; ``bias`` (B, H, T, S) or None. Rows with no
    visible key give zeros."""
    b, t, _ = x.shape
    s = kv_in.shape[1]
    hh, kv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    q = R.dot("btd,de->bte", x, p["wq"], prec).reshape(b, t, hh, dh)
    k = R.dot("bsd,de->bse", kv_in, p["wk"], prec).reshape(b, s, kv, dh)
    v = R.dot("bsd,de->bse", kv_in, p["wv"], prec).reshape(b, s, kv, dh)
    k = jnp.repeat(k, hh // kv, axis=2)
    v = jnp.repeat(v, hh // kv, axis=2)
    sc = R.dot("bthd,bshd->bhts", q, k, prec)
    if bias is not None:
        sc = sc + bias
    mk = mask[:, None]
    sc = jnp.where(mk, sc, -1e30)
    e = jnp.exp(sc - jax.lax.stop_gradient(jnp.max(sc, -1, keepdims=True)))
    e = jnp.where(mk, e, 0.0)
    pr = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    o = R.dot("bhts,bshd->bthd", pr, v, prec).reshape(b, t, hh * dh)
    return R.dot("bte,ed->btd", o, p["wo"], prec)


def _mlp(p, h, m, prec):
    x = R.rms_norm(h, p["ln2"], m["norm_eps"])
    f = p["ffn"]
    y = R.act(m["act"], R.dot("btd,df->btf", x, f["w_in"], prec))
    return R.dot("btf,fd->btd", y, f["w_out"], prec)


def loss(params, blk, m, prec):
    eps = m["norm_eps"]
    ve, vd = blk["enc_valid"], blk["dec_valid"]
    pe, pd = blk["enc_positions"], blk["dec_positions"]
    enc_bias = _position_bias(params["enc_rel_bias"], pe, pe, True, m)
    dec_bias = _position_bias(params["dec_rel_bias"], pd, pd, False, m)
    enc_mask = ve[:, :, None] & ve[:, None, :]
    dec_mask = vd[:, :, None] & vd[:, None, :] \
        & (pd[:, :, None] >= pd[:, None, :])
    cross_mask = vd[:, :, None] & ve[:, None, :]

    he = params["embed"][blk["enc_tokens"]]
    for i in range(m["n_layers"]):
        lp = jax.tree.map(lambda x, i=i: x[i], params["enc"]["l0"])
        x = R.rms_norm(he, lp["ln1"], eps)
        he = he + _attention(lp["mixer"], x, x, enc_mask, enc_bias, m, prec)
        he = he + _mlp(lp, he, m, prec)
    he = R.rms_norm(he, params["enc_norm"], eps)

    hd = params["embed"][blk["dec_tokens"]]
    for i in range(m["n_layers"]):
        lp = jax.tree.map(lambda x, i=i: x[i], params["dec"]["l0"])
        cp = jax.tree.map(lambda x, i=i: x[i], params["cross"])
        x = R.rms_norm(hd, lp["ln1"], eps)
        hd = hd + _attention(lp["mixer"], x, x, dec_mask, dec_bias, m, prec)
        x = R.rms_norm(hd, cp["ln"], eps)
        hd = hd + _attention(cp["attn"], x, he, cross_mask, None, m, prec)
        hd = hd + _mlp(lp, hd, m, prec)
    hd = R.rms_norm(hd, params["dec_norm"], eps) * m["d_model"] ** -0.5
    return R.xent_sum(params["embed"], hd, blk["labels"], blk["weights"],
                      m["vocab"], prec)
