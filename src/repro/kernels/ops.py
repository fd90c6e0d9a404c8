"""Jit'd public wrappers over the Pallas kernels with pure-jnp fallbacks.

``impl`` selects the compute path:
  - "pallas"     : pl.pallas_call targeting TPU (the production path)
  - "interpret"  : same kernel body, interpreted on CPU (used by tests)
  - "ref"        : pure-jnp oracle, the ground truth and the path where
                   no Pallas kernel applies.

The kernel paths carry ``jax.custom_vjp`` fused backward passes, so
``impl`` is *sticky under grad*: training steps differentiate straight
through the Pallas kernels instead of silently re-tracing the quadratic
``ref`` oracle. GQA k/v heads are consumed natively by the kernels (index
maps address ``q_head // group``) — no head-repetition materializes here.

The default comes from ``repro.kernels.default_impl()`` which picks
"pallas" on TPU backends and "ref" elsewhere; the ``REPRO_KERNEL_IMPL``
environment variable overrides it (benches/CI force ``pallas`` /
``interpret`` / ``ref`` without threading ``impl`` through every call
site).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import ragged_attention as _ra
from repro.kernels import relpos
from repro.kernels import ssd as _ssd
from repro.kernels import ref as _ref

_IMPLS = ("pallas", "interpret", "ref")


def default_impl() -> str:
    env = os.environ.get("REPRO_KERNEL_IMPL", "").strip().lower()
    if env:
        if env not in _IMPLS:
            raise ValueError(
                f"REPRO_KERNEL_IMPL={env!r} not in {_IMPLS}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(impl: str | None) -> str:
    return impl if impl is not None else default_impl()


def attention(
    q, k, v, *,
    causal=True, window=0, softcap=None,
    q_positions=None, kv_positions=None,
    q_segment_ids=None, kv_segment_ids=None,
    sm_scale=None, rel_bias=None, rel_max_distance=128,
    block_q=512, block_kv=512, impl: str | None = None,
    chunk_strategy: str = "q",
):
    """Multi-head attention entry point. k/v carry KV heads; every impl
    consumes GQA natively (the ref oracle repeats heads internally, the
    kernels address kv heads through their index maps — nothing repeated
    in HBM).

    chunk_strategy (ref path, long sequences): "q" scans query blocks
    (head-parallel attention), "head" scans head blocks (sequence-parallel
    attention, where the q seq dim is mesh-sharded and must not be scanned).

    sm_scale multiplies q·k (default 1/sqrt(head dim); T5 uses 1).
    rel_bias, an (H, n_buckets) table, adds T5's relative position bias
    ``table[h, bucket(kv_pos − q_pos)]``, bucketed causally when ``causal``
    (see ``relpos``); the kernels read the relative position off the row
    index, so positions must count up by one within each segment. The ref
    path does not chunk an unscaled or biased call.
    """
    impl = _resolve(impl)
    h = q.shape[2]
    if (q_segment_ids is None) != (kv_segment_ids is None):
        # one-sided segment ids (e.g. cross-attention with padded encoder
        # keys but no decoder segments): synthesize the missing side as one
        # all-zero segment so the mask applies — every path previously
        # required both sides and silently dropped a lone one
        if q_segment_ids is None:
            q_segment_ids = jnp.zeros(q.shape[:2], jnp.int32)
        else:
            kv_segment_ids = jnp.zeros(k.shape[:2], jnp.int32)
    ragged = q_segment_ids is not None
    if impl == "ref" and (sm_scale is not None or rel_bias is not None):
        if q_positions is None:
            q_positions = jnp.broadcast_to(
                jnp.arange(q.shape[1], dtype=jnp.int32)[None], q.shape[:2])
        if kv_positions is None:
            kv_positions = jnp.broadcast_to(
                jnp.arange(k.shape[1], dtype=jnp.int32)[None], k.shape[:2])
        bias = None if rel_bias is None else relpos.bias(
            rel_bias, q_positions, kv_positions,
            max_distance=rel_max_distance, bidirectional=not causal)
        return _ref.attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_positions=q_positions, kv_positions=kv_positions,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            sm_scale=sm_scale, bias=bias)
    if impl == "ref":
        # score-matrix element count decides chunking; batch rows multiply
        # the working set exactly like heads do, so B is part of the bound
        # (large-batch short-seq micro-batches must not take the
        # materialize-everything path)
        big = q.shape[0] * q.shape[1] * k.shape[1] * h >= 2048 * 2048 * 8
        if big and chunk_strategy == "head":
            fn = _ref.attention_ref_headchunked
        elif big and q.shape[1] >= 2048:
            fn = _ref.attention_ref_chunked
        elif big:
            # large-batch short-seq: per-row (T, S) blocks are small but
            # there are many rows — chunk over the batch instead
            fn = _ref.attention_ref_batchchunked
        else:
            fn = _ref.attention_ref
        return fn(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_positions=q_positions, kv_positions=kv_positions,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
        )
    interpret = impl == "interpret"
    if ragged:
        return _ra.ragged_attention(
            q, k, v, q_segment_ids, kv_segment_ids, causal=causal,
            window=window, softcap=softcap,
            q_positions=q_positions, kv_positions=kv_positions,
            sm_scale=sm_scale, rel_bias=rel_bias,
            rel_max_distance=rel_max_distance,
            block_q=block_q, block_kv=block_kv, interpret=interpret,
        )
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_positions=q_positions, kv_positions=kv_positions,
        sm_scale=sm_scale, rel_bias=rel_bias,
        rel_max_distance=rel_max_distance,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )


def ssd(x, dt, A, B, C, *, initial_state=None, return_state=False,
        block_t=128, impl: str | None = None):
    """Mamba2 SSD over a full sequence. Returns y or (y, final_state)."""
    impl = _resolve(impl)
    if impl == "ref" or initial_state is not None:
        # the chunked kernel assumes zero initial state; prefill always does.
        if initial_state is None and x.shape[1] >= 512:
            return _ref.ssd_ref_chunked(
                x, dt, A, B, C, block_t=block_t, return_state=return_state)
        return _ref.ssd_ref(
            x, dt, A, B, C, initial_state=initial_state, return_state=return_state
        )
    interpret = impl == "interpret"
    y, st = _ssd.ssd_chunked(x, dt, A, B, C, block_t=block_t, interpret=interpret)
    return (y, st) if return_state else y


def ssd_decode(x, dt, A, B, C, state):
    """Single-token SSM recurrence (decode): tiny, stays pure-jnp."""
    return _ref.ssd_decode_ref(x, dt, A, B, C, state)
