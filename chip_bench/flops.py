"""Operation and byte counts the benchmark charges, from shapes and real lengths.

Model FLOPs (for ``mfu``): 6 per weight that multiplies an activation, per
token that weight sees (forward 2, backward 4), plus attention's score and
value products over the pairs each sample's real lengths need. Remat's
recomputation is not counted; padding is not counted; the embedding gather
is not a multiply.

Attention work (for ``attention_roofline``): per layer and sample, the
forward's two products (QK^T, PV) and the backward's four (dV, dP, dQ, dK),
2 FLOPs each per (query, key, head-dim) triple; bytes are what a fused
kernel must move at the least: q, k, v read and o written forward; q, k, v,
o, dO read and dq, dk, dv written backward, each once, in bf16.

Sizes are the configuration file's ``model`` section: ``n_layers`` (per
side for an encoder-decoder), ``d_model``, ``n_heads``, ``n_kv_heads``,
``d_head``, ``d_ff``, ``vocab``, ``family``.
"""
from __future__ import annotations

import numpy as np

BF16_BYTES = 2


def _layer_weights(m: dict) -> int:
    """Weights that multiply activations in one self-attention + MLP layer."""
    d, h, kv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_head"], m["d_ff"])
    return d * h * dh * 2 + d * kv * dh * 2 + d * f * (3 if m.get("mlp_gated")
                                                       else 2)


def _pairs_causal(n: np.ndarray) -> np.ndarray:
    return n * (n + 1) // 2


def model_flops(m: dict, lengths: np.ndarray) -> float:
    """Training FLOPs the real tokens of ``lengths`` ((n, 2): enc or whole,
    dec) need."""
    lengths = np.asarray(lengths, np.int64)
    h, kv, dh, d = m["n_heads"], m["n_kv_heads"], m["d_head"], m["d_model"]
    layers = m["n_layers"]
    att = 4 * h * dh            # forward FLOPs per (query, key) pair, per layer
    if m["family"] == "decoder":
        n = lengths[:, 0] + lengths[:, 1]
        w = layers * _layer_weights(m) + m["vocab"] * d
        fwd = 2 * w * n.sum() + layers * att * _pairs_causal(n).sum()
        return float(3 * fwd)
    le, ld = lengths[:, 0], lengths[:, 1]
    enc = 2 * layers * _layer_weights(m) * le.sum() \
        + layers * att * (le * le).sum()
    cross_q_o = 2 * d * h * dh          # decoder side: wq, wo
    cross_k_v = 2 * d * kv * dh         # encoder side: wk, wv
    dec = 2 * (layers * (_layer_weights(m) + cross_q_o) + m["vocab"] * d) \
        * ld.sum() + 2 * layers * cross_k_v * le.sum() \
        + layers * att * (_pairs_causal(ld) + le * ld).sum()
    return float(3 * (enc + dec))


def attention_work(m: dict, lengths: np.ndarray) -> tuple[float, float]:
    """(FLOPs, bytes) of the attention kernels' least work, forward and
    backward, for the real lengths."""
    lengths = np.asarray(lengths, np.int64)
    h, kv, dh, layers = m["n_heads"], m["n_kv_heads"], m["d_head"], \
        m["n_layers"]
    per_pair = 3 * 4 * h * dh           # fwd 2 products + bwd 4, 2 FLOPs each

    def io(nq, nk):                     # bytes per layer for one sample
        q = nq * h * dh * BF16_BYTES
        k = nk * kv * dh * BF16_BYTES
        # fwd: read q, k, v, write o; bwd: read q, k, v, o, do, write dq, dk, dv
        return (q + 2 * k + q) + (3 * q + 2 * k + q + 2 * k)

    if m["family"] == "decoder":
        n = lengths[:, 0] + lengths[:, 1]
        return (float(layers * per_pair * _pairs_causal(n).sum()),
                float(layers * io(n, n).sum()))
    le, ld = lengths[:, 0], lengths[:, 1]
    flops = layers * per_pair * ((le * le) + _pairs_causal(ld) + le * ld).sum()
    nbytes = layers * (io(le, le) + io(ld, ld) + io(ld, le)).sum()
    return float(flops), float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
