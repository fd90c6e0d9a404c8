"""Plain reference of a pre-norm decoder-only transformer (gpt-paper).

Per layer: h += Wo·attn(RoPE(Wq·x), RoPE(Wk·x), Wv·x) with x = RMSNorm(h),
causal; h += W_out·act(W_in·RMSNorm(h)). Then RMSNorm and an untied head,
next-token cross entropy within each sample. RMSNorm scales by (1 + w),
weights start at 0. No biases. Each sample is a row of its own, padded to
a power-of-two bucket and masked; nothing is packed.

Weights: the program's recipe from ``PRNGKey(seed)``: split 6; embed
N(0,1) from key 0, layers from key 1 (split per layer, then 3 per layer,
attention from the first (split 4: q, k, v, o at d^-1/2 and (h·dh)^-1/2),
MLP from the second (split 3: in at d^-1/2, out at d_ff^-1/2)), head
N(0,1)·d^-1/2 from key 4; all rounded to bf16 as stored.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench import refmath as R

STACKED = ("stack",)


def _attn_init(key, m):
    d, h, kv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    ks = jax.random.split(key, 4)
    return {"wq": R.normal(ks[0], (d, h * dh), d ** -0.5),
            "wk": R.normal(ks[1], (d, kv * dh), d ** -0.5),
            "wv": R.normal(ks[2], (d, kv * dh), d ** -0.5),
            "wo": R.normal(ks[3], (h * dh, d), (h * dh) ** -0.5)}


def _block_init(key, m):
    d, f = m["d_model"], m["d_ff"]
    ks = jax.random.split(key, 3)
    km = jax.random.split(ks[1], 3)
    return {"ln1": R.zeros(d), "mixer": _attn_init(ks[0], m),
            "ln2": R.zeros(d),
            "ffn": {"w_in": R.normal(km[0], (d, f), d ** -0.5),
                    "w_out": R.normal(km[1], (f, d), f ** -0.5)}}


def stack_init(key, m):
    """Layers stacked on a leading axis, as the program stores them."""
    layers = [{"l0": _block_init(jax.random.split(k, 1)[0], m)}
              for k in jax.random.split(key, m["n_layers"])]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def init(seed: int, m: dict):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    vp, d = R.padded_vocab(m["vocab"]), m["d_model"]
    return {"embed": R.normal(ks[0], (vp, d), 1.0),
            "stack": stack_init(ks[1], m),
            "final_norm": R.zeros(d),
            "head": R.normal(ks[4], (vp, d), d ** -0.5)}


def layer(p, h, valid, positions, m, prec, causal=True):
    """One pre-norm self-attention + MLP layer over rows ``h`` (B, T, D)."""
    b, t, _ = h.shape
    hh, kv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    x = R.rms_norm(h, p["ln1"], m["norm_eps"])
    a = p["mixer"]
    q = R.dot("btd,de->bte", x, a["wq"], prec).reshape(b, t, hh, dh)
    k = R.dot("btd,de->bte", x, a["wk"], prec).reshape(b, t, kv, dh)
    v = R.dot("btd,de->bte", x, a["wv"], prec).reshape(b, t, kv, dh)
    q = R.rope(q, positions, m["rope_theta"])
    k = R.rope(k, positions, m["rope_theta"])
    mask = valid[:, :, None] & valid[:, None, :]
    if causal:
        mask &= positions[:, :, None] >= positions[:, None, :]
    o = R.attention(q, k, v, mask, prec).reshape(b, t, hh * dh)
    h = h + R.dot("bte,ed->btd", o, a["wo"], prec)
    x = R.rms_norm(h, p["ln2"], m["norm_eps"])
    f = p["ffn"]
    y = R.act(m["act"], R.dot("btd,df->btf", x, f["w_in"], prec))
    return h + R.dot("btf,fd->btd", y, f["w_out"], prec)


def loss(params, blk, m, prec):
    """(summed xent, weight sum) of one block of rows."""
    h = params["embed"][blk["tokens"]]
    valid, pos = blk["valid"], blk["positions"]
    for i in range(m["n_layers"]):
        lp = jax.tree.map(lambda x, i=i: x[i], params["stack"]["l0"])
        h = layer(lp, h, valid, pos, m, prec)
    h = R.rms_norm(h, params["final_norm"], m["norm_eps"])
    return R.xent_sum(params["head"], h, blk["labels"], blk["weights"],
                      m["vocab"], prec)


def blocks(batch, m, block_tokens: int) -> list[dict]:
    """One step's samples as row blocks: each sample alone in a row of its
    power-of-two bucket, ``block_tokens // bucket`` rows a block (the last
    one filled with empty rows)."""
    by_len: dict[int, list] = {}
    for i, (e, d) in enumerate(batch.lengths):
        n = int(e + d)
        by_len.setdefault(R.bucket(n), []).append(batch.tokens[i][:n])
    out = []
    for L, seqs in sorted(by_len.items()):
        rows = max(1, block_tokens // L)
        for lo in range(0, len(seqs), rows):
            tok = np.zeros((rows, L), np.int32)
            lab = np.zeros((rows, L), np.int32)
            w = np.zeros((rows, L), np.float32)
            valid = np.zeros((rows, L), bool)
            for r, t in enumerate(seqs[lo: lo + rows]):
                n = len(t)
                tok[r, :n] = t
                lab[r, : n - 1] = t[1:]
                w[r, : n - 1] = 1.0
                valid[r, :n] = True
            out.append({"tokens": tok, "labels": lab, "weights": w,
                        "valid": valid,
                        "positions": np.broadcast_to(
                            np.arange(L, dtype=np.int32), (rows, L)).copy()})
    return out
