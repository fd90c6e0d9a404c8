"""Segment-aware (ragged / varlen) FlashAttention for TPU in Pallas.

This is the TPU-native answer to the paper's "packing without
cross-contamination" problem (DynaPipe §2.2): when a micro-batch row still
concatenates several samples of unequal length (or carries right-padding),
per-token *segment ids* mark sample boundaries, and

  1. (q-block, kv-block) pairs whose segment-id ranges are disjoint are
     skipped entirely — with samples laid out contiguously, segment ids are
     non-decreasing along the row, so range-disjointness is exact, and the
     quadratic cross-sample waste of packing never reaches the MXU;
  2. mixed boundary blocks apply an exact element-wise segment mask;
  3. padding tokens carry segment id -1 and are masked from both sides.

Forward, fused backward (``jax.custom_vjp``), sliding-window and
logit-softcap masking (gemma2-style packed batches), and GQA-native
indexing are all shared with ``flash_attention.py`` — this module binds
the segmented variant of the same kernel bodies, so the backward carries
the identical segment-range block-skip predicate (cross-sample blocks are
skipped in *both* passes, where they cost twice what they do in forward).
T5's relative position bias rides along the same way (``rel_bias``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (
    NEG_INF,            # noqa: F401  (re-exported for callers/tests)
    _int_ct,
    _scale,
    live_block_mask,    # noqa: F401  (segment-aware liveness, re-exported)
    mha_backward,
    mha_forward,
    shrink_block,
)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(8, 16)))
def _ragged(q, k, v, qseg, kseg, qpos, kpos, table, causal, window, softcap,
            sm_scale, max_distance, block_q, block_kv, interpret):
    o, _ = mha_forward(q, k, v, qpos, kpos, qseg, kseg, causal=causal,
                       window=window, softcap=softcap, sm_scale=sm_scale,
                       rel_table=table, rel_max_distance=max_distance,
                       block_q=block_q, block_kv=block_kv,
                       interpret=interpret)
    return o


def _ragged_fwd(q, k, v, qseg, kseg, qpos, kpos, table, causal, window,
                softcap, sm_scale, max_distance, block_q, block_kv,
                interpret):
    o, lse = mha_forward(q, k, v, qpos, kpos, qseg, kseg, causal=causal,
                         window=window, softcap=softcap, sm_scale=sm_scale,
                         rel_table=table, rel_max_distance=max_distance,
                         block_q=block_q, block_kv=block_kv,
                         interpret=interpret)
    return o, (q, k, v, qseg, kseg, qpos, kpos, table, o, lse)


def _ragged_bwd(causal, window, softcap, sm_scale, max_distance, block_q,
                block_kv, interpret, res, do):
    q, k, v, qseg, kseg, qpos, kpos, table, o, lse = res
    dq, dk, dv, dtable = mha_backward(
        q, k, v, qpos, kpos, qseg, kseg, o, lse, do,
        causal=causal, window=window, softcap=softcap, sm_scale=sm_scale,
        rel_table=table, rel_max_distance=max_distance,
        block_q=block_q, block_kv=block_kv, interpret=interpret)
    return (dq, dk, dv, _int_ct(qseg), _int_ct(kseg),
            _int_ct(qpos), _int_ct(kpos), dtable)


_ragged.defvjp(_ragged_fwd, _ragged_bwd)


def ragged_attention(
    q: jax.Array,                  # (B, T, H, D)
    k: jax.Array,                  # (B, S, KV, D)  (GQA-native: KV <= H)
    v: jax.Array,                  # (B, S, KV, D)
    q_segment_ids: jax.Array,      # (B, T) int32, -1 = padding
    kv_segment_ids: jax.Array,     # (B, S) int32
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float | None = None,
    q_positions: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    sm_scale: float | None = None,          # default 1/sqrt(D)
    rel_bias: jax.Array | None = None,      # (H, n_buckets) table
    rel_max_distance: int = 128,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, t, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    assert k.shape == (b, s, kvh, d) and v.shape == (b, s, kvh, d)
    assert h % kvh == 0, (h, kvh)
    block_q = shrink_block(t, block_q)
    block_kv = shrink_block(s, block_kv)
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if rel_bias is not None:
        rel_bias = rel_bias.astype(jnp.float32)
    return _ragged(q, k, v, q_segment_ids.astype(jnp.int32),
                   kv_segment_ids.astype(jnp.int32),
                   q_positions.astype(jnp.int32),
                   kv_positions.astype(jnp.int32), rel_bias, causal,
                   int(window), softcap, _scale(sm_scale, d),
                   int(rel_max_distance), block_q, block_kv, interpret)
