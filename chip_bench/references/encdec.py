"""Plain reference of the encoder-decoder transformer (t5-paper, as the
program implements it).

Encoder: the decoder reference's layer without the causal mask, over the
encoder tokens, then RMSNorm. Decoder, per layer: the causal layer, then
cross attention h += Wo·attn(Wq·RMSNorm(h), Wk·he, Wv·he) over the encoder
output (no RoPE, padded encoder keys masked). Then RMSNorm and the tied
embedding as the head; the loss is next-token cross entropy on the decoder
side only. Each (encoder, decoder) pair is a row of its own.

Weights: the program's recipe from ``PRNGKey(seed)``: split 6; embed
N(0,1) from key 0, encoder layers from key 1, decoder layers from key 2
(as the decoder reference's stack), cross attention of layer i from
``fold_in(key 4, i)``; norms start at 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench import refmath as R
from chip_bench.references import decoder as D

STACKED = ("enc", "dec", "cross")


def init(seed: int, m: dict):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    vp, d = R.padded_vocab(m["vocab"]), m["d_model"]
    cross = [{"ln": R.zeros(d),
              "attn": D._attn_init(jax.random.fold_in(ks[4], i), m)}
             for i in range(m["n_layers"])]
    return {"embed": R.normal(ks[0], (vp, d), 1.0),
            "enc": D.stack_init(ks[1], m),
            "dec": D.stack_init(ks[2], m),
            "cross": jax.tree.map(lambda *xs: jnp.stack(xs), *cross),
            "enc_norm": R.zeros(d),
            "dec_norm": R.zeros(d)}


def _cross(p, hd, he, mask, m, prec):
    b, t, _ = hd.shape
    s = he.shape[1]
    hh, kv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    x = R.rms_norm(hd, p["ln"], m["norm_eps"])
    a = p["attn"]
    q = R.dot("btd,de->bte", x, a["wq"], prec).reshape(b, t, hh, dh)
    k = R.dot("bsd,de->bse", he, a["wk"], prec).reshape(b, s, kv, dh)
    v = R.dot("bsd,de->bse", he, a["wv"], prec).reshape(b, s, kv, dh)
    o = R.attention(q, k, v, mask, prec).reshape(b, t, hh * dh)
    return R.dot("bte,ed->btd", o, a["wo"], prec)


def loss(params, blk, m, prec):
    ve, vd = blk["enc_valid"], blk["dec_valid"]
    he = params["embed"][blk["enc_tokens"]]
    for i in range(m["n_layers"]):
        lp = jax.tree.map(lambda x, i=i: x[i], params["enc"]["l0"])
        he = D.layer(lp, he, ve, blk["enc_positions"], m, prec, causal=False)
    he = R.rms_norm(he, params["enc_norm"], m["norm_eps"])
    hd = params["embed"][blk["dec_tokens"]]
    cmask = vd[:, :, None] & ve[:, None, :]
    for i in range(m["n_layers"]):
        lp = jax.tree.map(lambda x, i=i: x[i], params["dec"]["l0"])
        hd = D.layer(lp, hd, vd, blk["dec_positions"], m, prec)
        cp = jax.tree.map(lambda x, i=i: x[i], params["cross"])
        hd = hd + _cross(cp, hd, he, cmask, m, prec)
    hd = R.rms_norm(hd, params["dec_norm"], m["norm_eps"])
    return R.xent_sum(params["embed"], hd, blk["labels"], blk["weights"],
                      m["vocab"], prec)


def blocks(batch, m, block_tokens: int) -> list[dict]:
    """Rows grouped by (encoder bucket, decoder bucket); a block holds
    ``block_tokens // encoder bucket`` rows."""
    groups: dict[tuple, list] = {}
    for i, (e, d) in enumerate(batch.lengths):
        e, d = int(e), int(d)
        t = batch.tokens[i]
        groups.setdefault((R.bucket(e), R.bucket(d)), []).append(
            (t[:e], t[e: e + d]))
    out = []
    for (le, ld), pairs in sorted(groups.items()):
        rows = max(1, block_tokens // le)
        for lo in range(0, len(pairs), rows):
            b = {"enc_tokens": np.zeros((rows, le), np.int32),
                 "enc_valid": np.zeros((rows, le), bool),
                 "dec_tokens": np.zeros((rows, ld), np.int32),
                 "dec_valid": np.zeros((rows, ld), bool),
                 "labels": np.zeros((rows, ld), np.int32),
                 "weights": np.zeros((rows, ld), np.float32),
                 "enc_positions": np.broadcast_to(
                     np.arange(le, dtype=np.int32), (rows, le)).copy(),
                 "dec_positions": np.broadcast_to(
                     np.arange(ld, dtype=np.int32), (rows, ld)).copy()}
            for r, (te, td) in enumerate(pairs[lo: lo + rows]):
                b["enc_tokens"][r, :len(te)] = te
                b["enc_valid"][r, :len(te)] = True
                b["dec_tokens"][r, :len(td)] = td
                b["dec_valid"][r, :len(td)] = True
                b["labels"][r, : len(td) - 1] = td[1:]
                b["weights"][r, : len(td) - 1] = 1.0
            out.append(b)
    return out
