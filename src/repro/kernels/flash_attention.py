"""FlashAttention for TPU in Pallas — forward AND fused backward.

Blockwise attention with online softmax. Forward grid = (batch, q_head,
Q blocks, KV blocks); the KV-block dimension is innermost and executed
sequentially on TPU, so fp32 running statistics (m, l, acc) live in VMEM
scratch and carry across KV steps. Causal / sliding-window / cross-segment
block pairs that are fully masked are skipped with ``pl.when`` (predicated
out — no MXU work issued).

Training path: the public entry points carry a ``jax.custom_vjp``. The
forward saves ``(o, lse)`` residuals (``lse = m + log l`` per query row);
the backward precomputes ``delta = rowsum(do * o)`` and then runs two
passes that carry the *same* block-skip predicate as the forward —
skipping cross-sample blocks is worth twice as much in backward (~2x the
FLOPs of forward):

  - **dq pass** — q-major grid ``(b, h, nq, nk)``: for each query block,
    sweep kv blocks accumulating ``dq += (ds @ k) * scale`` in VMEM.
  - **dk/dv pass** — kv-major grid ``(b, kv_head, nk, group, nq)``: for
    each kv block, sweep the q-head *group* and query blocks accumulating
    ``dv += p^T @ do`` and ``dk += (ds^T @ q) * scale``; one program per
    KV head writes its dk/dv block exactly once.

GQA is native: k/v carry ``kv_heads`` and the index maps address
``q_head // group`` directly — no head-repeated K/V is ever materialized
in HBM. Positions (and segment ids, for the ragged wrapper) are ``(B, T)``
arrays, reshaped (not repeated) to a column or row per batch row and read
through BlockSpec index maps — never repeated to ``B*H`` rows.

BlockSpec tiling: every block's last two dims are a multiple of (8, 128)
or the full array dims, which the TPU lowering requires (the layout table
is above the pallas_call builders). Q tile (block_q, Dp), K/V tiles
(block_kv, Dp) with block_q = block_kv = 512 by default and D padded to
Dp = 128·⌈D/128⌉; :func:`shrink_block` picks legal sequence tiles for any
palette bucket. Forward VMEM working set ≈ (block_q + 2·block_kv)·d·2B +
block_q·(d+2)·4B ≈ 1.6 MiB at d=128; the dk/dv pass peaks at
(2·block_q + 2·block_kv)·d·2B + 2·block_kv·d·4B + block_q·block_kv·4B
≈ 2.6 MiB — both inside the ~16 MiB VMEM budget.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def shrink_block(length: int, block: int) -> int:
    """The tile length the kernels use along a sequence of ``length``.

    Blocks must tile the sequence exactly, and a block's length is also the
    lane dimension of the kv-side id rows, which the TPU lowering accepts
    only as a multiple of 128 or as the whole sequence. So: a sequence no
    longer than ``block`` is one block; a longer one takes the largest
    multiple of 128 that divides it and is at most ``block`` (palette
    bucket 768 under block 512 -> 384). A longer sequence with no such
    divisor is one block too; a palette built with ``seq_align=128`` never
    produces one.
    """
    if length <= block:
        return length
    for cand in range(block - block % _LANES, 0, -_LANES):
        if length % cand == 0:
            return cand
    return length


# ----------------------------------------------------------------------
# block-level liveness (shared by kernels, benches, and tests)
# ----------------------------------------------------------------------
def _live_terms(qpos, kpos, qseg, kseg, causal, window):
    """The block-skip predicate from per-block min/max statistics.

    Works on traced scalars inside the kernels and on numpy arrays in
    :func:`live_block_mask`; `qpos`/`kpos` etc. are (min, max) pairs.
    """
    (q_pmin, q_pmax), (k_pmin, k_pmax) = qpos, kpos
    live = True
    if qseg is not None:
        (q_smin, q_smax), (k_smin, k_smax) = qseg, kseg
        live = (q_smax >= k_smin) & (k_smax >= q_smin) \
            & (k_smax >= 0) & (q_smax >= 0)
    if causal:
        live &= q_pmax >= k_pmin
        if window > 0:
            live &= (q_pmin - k_pmax) < window
    return live


def live_block_mask(q_positions, kv_positions,
                    q_segment_ids=None, kv_segment_ids=None, *,
                    causal: bool = True, window: int = 0,
                    block_q: int, block_kv: int) -> np.ndarray:
    """(B, nq, nk) bool: which (q-block, kv-block) pairs the kernels visit.

    This is the exact predicate the forward, dq, and dk/dv kernels gate
    compute on, evaluated in numpy — deterministic and machine-independent,
    so benchmarks can report the *live-block fraction* (the share of the
    quadratic block grid that reaches the MXU) without running a TPU.
    """
    qp = np.asarray(q_positions)
    kp = np.asarray(kv_positions)
    b, t = qp.shape
    s = kp.shape[1]
    block_q = shrink_block(t, block_q)
    block_kv = shrink_block(s, block_kv)
    nq, nk = t // block_q, s // block_kv

    def mm(x, n, blk):   # (B, n, 1) min / max per block
        xb = np.asarray(x).reshape(b, n, blk)
        return xb.min(axis=2), xb.max(axis=2)

    q_pmin, q_pmax = mm(qp, nq, block_q)
    k_pmin, k_pmax = mm(kp, nk, block_kv)
    qseg = kseg = None
    if q_segment_ids is not None:
        qs_min, qs_max = mm(q_segment_ids, nq, block_q)
        ks_min, ks_max = mm(kv_segment_ids, nk, block_kv)
        qseg = (qs_min[:, :, None], qs_max[:, :, None])
        kseg = (ks_min[:, None, :], ks_max[:, None, :])
    live = _live_terms(
        (q_pmin[:, :, None], q_pmax[:, :, None]),
        (k_pmin[:, None, :], k_pmax[:, None, :]),
        qseg, kseg, causal, window)
    return np.broadcast_to(np.asarray(live), (b, nq, nk))


# ----------------------------------------------------------------------
# kernel bodies (segment refs are None for the plain flash path)
#
# Per-token int data enters the kernels in two orientations: query-side
# positions/segment ids as (block_q, 1) columns, kv-side ones as
# (1, block_kv) rows, so ``q - k`` and ``q == k`` broadcast straight to the
# (block_q, block_kv) score tile. Row statistics (m, l, lse, delta) are
# (block_q, 1) columns for the same reason.
# ----------------------------------------------------------------------
def _block_stats(qpos, kpos, qseg, kseg, causal, window):
    qp = (jnp.min(qpos), jnp.max(qpos))
    kp = (jnp.min(kpos), jnp.max(kpos))
    qs = (jnp.min(qseg), jnp.max(qseg)) if qseg is not None else None
    ks = (jnp.min(kseg), jnp.max(kseg)) if kseg is not None else None
    live = _live_terms(qp, kp, qs, ks, causal, window)
    if isinstance(live, bool):        # non-causal, non-segmented: all live
        live = jnp.bool_(live)
    return live


def _element_mask(qpos, kpos, qseg, kseg, causal, window):
    """(block_q, block_kv) mask from (block_q, 1) / (1, block_kv) ids."""
    mask = None
    if qseg is not None:
        mask = (qseg == kseg) & (kseg >= 0)
    if causal:
        dpos = qpos - kpos
        cm = dpos >= 0
        if window > 0:
            cm &= dpos < window
        mask = cm if mask is None else (mask & cm)
    return mask


def _scores(q, k, sm_scale, softcap):
    """Returns (capped logits s1, tanh(s0/cap) or None for the vjp chain)."""
    s0 = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if softcap is not None:
        th = jnp.tanh(s0 / softcap)
        return softcap * th, th
    return s0, None


def _read_ids(qpos_ref, kpos_ref, qseg_ref, kseg_ref):
    qseg = qseg_ref[...] if qseg_ref is not None else None
    kseg = kseg_ref[...] if kseg_ref is not None else None
    return qpos_ref[...], kpos_ref[...], qseg, kseg


def _fwd_body(qpos_ref, kpos_ref, qseg_ref, kseg_ref,
              q_ref, k_ref, v_ref, o_ref, lse_ref,
              m_ref, l_ref, acc_ref, *,
              causal, window, softcap, sm_scale, n_kv_blocks):
    kv_idx = pl.program_id(3)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos, kpos, qseg, kseg = _read_ids(qpos_ref, kpos_ref, qseg_ref, kseg_ref)
    live = _block_stats(qpos, kpos, qseg, kseg, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)          # (bq, d)
        k = k_ref[...].astype(jnp.float32)          # (bk, d)
        v = v_ref[...].astype(jnp.float32)
        s, _ = _scores(q, k, sm_scale, softcap)
        mask = _element_mask(qpos, kpos, qseg, kseg, causal, window)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                         # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_cur

    @pl.when(kv_idx == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _p_and_ds(q, k, qpos, kpos, qseg, kseg, lse, do, v, delta,
              causal, window, softcap, sm_scale):
    """Recompute p from residuals and chain d(loss)/d(raw logits)."""
    s1, th = _scores(q, k, sm_scale, softcap)
    mask = _element_mask(qpos, kpos, qseg, kseg, causal, window)
    p = jnp.exp(s1 - lse)
    if mask is not None:
        # also zeroes fully-masked rows, whose lse is the -inf sentinel
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if softcap is not None:
        ds = ds * (1.0 - th * th)      # through s1 = cap * tanh(s0 / cap)
    return p, ds


def _dq_body(qpos_ref, kpos_ref, qseg_ref, kseg_ref,
             q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
             dq_ref, dq_acc, *,
             causal, window, softcap, sm_scale, n_kv_blocks):
    kv_idx = pl.program_id(3)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    qpos, kpos, qseg, kseg = _read_ids(qpos_ref, kpos_ref, qseg_ref, kseg_ref)
    live = _block_stats(qpos, kpos, qseg, kseg, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        _, ds = _p_and_ds(q, k, qpos, kpos, qseg, kseg, lse_ref[...], do, v,
                          delta_ref[...], causal, window, softcap, sm_scale)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when(kv_idx == n_kv_blocks - 1)
    def _finalize():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_body(qpos_ref, kpos_ref, qseg_ref, kseg_ref,
              q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              dk_ref, dv_ref, dk_acc, dv_acc, *,
              causal, window, softcap, sm_scale, n_q_blocks, group):
    g = pl.program_id(3)
    q_idx = pl.program_id(4)

    @pl.when((g == 0) & (q_idx == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    qpos, kpos, qseg, kseg = _read_ids(qpos_ref, kpos_ref, qseg_ref, kseg_ref)
    live = _block_stats(qpos, kpos, qseg, kseg, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        p, ds = _p_and_ds(q, k, qpos, kpos, qseg, kseg, lse_ref[...], do, v,
                          delta_ref[...], causal, window, softcap, sm_scale)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when((g == group - 1) & (q_idx == n_q_blocks - 1))
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _plain(body):
    """Adapter binding the (absent) segment refs of a non-ragged call."""
    def wrapped(qpos, kpos, *rest, **kw):
        return body(qpos, kpos, None, None, *rest, **kw)
    return wrapped


# ----------------------------------------------------------------------
# pallas_call builders
#
# Layout seen by the kernels (every block's last two dims are either a
# multiple of (8, 128) or the full array dims, as the TPU lowering needs):
#   q/k/v/o/do/dq/dk/dv  (B, T, H*Dp)  blocks (block, Dp) at column
#                        block ``head`` — a free reshape of (B, T, H, D),
#                        with D zero-padded up to Dp = 128·⌈D/128⌉
#   query-side ids       (B, T, 1)     blocks (block_q, 1)
#   kv-side ids          (B, 1, S)     blocks (1, block_kv)
#   lse / delta          (B, H, T, 1)  blocks (block_q, 1)
# ----------------------------------------------------------------------
def _heads_flat(x, dp):
    """(B, T, H, D) -> (B, T, H*Dp), zero-padding D to the lane width."""
    b, t, h, d = x.shape
    if d != dp:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
    return x.reshape(b, t, h * dp)


def _heads_unflat(x, h, d):
    b, t, _ = x.shape
    return x.reshape(b, t, h, -1)[..., :d]


def _id_args(q_positions, kv_positions, q_segment_ids, kv_segment_ids):
    """Query-side ids as (B, T, 1) columns, kv-side ids as (B, 1, S) rows
    (positions first, then segment ids when ragged)."""
    args = [q_positions[:, :, None], kv_positions[:, None, :]]
    if q_segment_ids is not None:
        args += [q_segment_ids[:, :, None], kv_segment_ids[:, None, :]]
    return args


def _id_specs(block_q, block_kv, qi, ki, segmented):
    """BlockSpecs for :func:`_id_args`; ``qi``/``ki`` map a grid point to
    (batch, q block) / (batch, kv block)."""
    def q_map(*g):
        b_, iq = qi(*g)
        return (b_, iq, 0)

    def k_map(*g):
        b_, ik = ki(*g)
        return (b_, 0, ik)
    specs = [pl.BlockSpec((None, block_q, 1), q_map),
             pl.BlockSpec((None, 1, block_kv), k_map)]
    return specs * 2 if segmented else specs


def _dims(q, k):
    b, t, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    return b, t, h, d, s, kvh, h // kvh, -(-d // _LANES) * _LANES


def mha_forward(q, k, v, q_positions, kv_positions,
                q_segment_ids=None, kv_segment_ids=None, *,
                causal, window=0, softcap=None,
                block_q, block_kv, interpret=False):
    """Raw forward: returns ``(o, lse)`` with lse in (B, H, T) fp32."""
    b, t, h, d, s, kvh, group, dp = _dims(q, k)
    block_q = shrink_block(t, block_q)
    block_kv = shrink_block(s, block_kv)
    nq, nk = t // block_q, s // block_kv
    segmented = q_segment_ids is not None

    body = _fwd_body if segmented else _plain(_fwd_body)
    kernel = functools.partial(
        body, causal=causal, window=window, softcap=softcap,
        sm_scale=1.0 / math.sqrt(d), n_kv_blocks=nk)

    in_specs = _id_specs(
        block_q, block_kv,
        lambda b_, h_, iq, ik: (b_, iq),
        lambda b_, h_, iq, ik: (b_, ik),
        segmented,
    ) + [
        pl.BlockSpec((None, block_q, dp), lambda b_, h_, iq, ik: (b_, iq, h_)),
        pl.BlockSpec((None, block_kv, dp),
                     lambda b_, h_, iq, ik: (b_, ik, h_ // group)),
        pl.BlockSpec((None, block_kv, dp),
                     lambda b_, h_, iq, ik: (b_, ik, h_ // group)),
    ]
    args = _id_args(q_positions, kv_positions, q_segment_ids, kv_segment_ids)
    args += [_heads_flat(x, dp) for x in (q, k, v)]

    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, dp),
                         lambda b_, h_, iq, ik: (b_, iq, h_)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, h * dp), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dp), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return _heads_unflat(o, h, d), lse[..., 0]


def mha_backward(q, k, v, q_positions, kv_positions,
                 q_segment_ids, kv_segment_ids, o, lse, do, *,
                 causal, window=0, softcap=None,
                 block_q, block_kv, interpret=False):
    """Fused backward from residuals: returns ``(dq, dk, dv)``."""
    b, t, h, d, s, kvh, group, dp = _dims(q, k)
    block_q = shrink_block(t, block_q)
    block_kv = shrink_block(s, block_kv)
    nq, nk = t // block_q, s // block_kv
    segmented = q_segment_ids is not None
    sm_scale = 1.0 / math.sqrt(d)

    # delta_i = sum_d do_i * o_i — one fused elementwise-reduce over (B,T,H,D)
    delta = jnp.einsum("bthd,bthd->bht", do.astype(jnp.float32),
                       o.astype(jnp.float32))[..., None]

    lse = lse[..., None]
    ids = _id_args(q_positions, kv_positions, q_segment_ids, kv_segment_ids)
    qf, kf, vf, dof = (_heads_flat(x, dp) for x in (q, k, v, do))

    # ---- dq: q-major, kv innermost ----
    body = _dq_body if segmented else _plain(_dq_body)
    dq_kernel = functools.partial(
        body, causal=causal, window=window, softcap=softcap,
        sm_scale=sm_scale, n_kv_blocks=nk)
    q_tile = pl.BlockSpec((None, block_q, dp),
                          lambda b_, h_, iq, ik: (b_, iq, h_))
    kv_tile = pl.BlockSpec((None, block_kv, dp),
                           lambda b_, h_, iq, ik: (b_, ik, h_ // group))
    row_stat = pl.BlockSpec((None, None, block_q, 1),
                            lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, nq, nk),
        in_specs=_id_specs(
            block_q, block_kv,
            lambda b_, h_, iq, ik: (b_, iq),
            lambda b_, h_, iq, ik: (b_, ik),
            segmented,
        ) + [q_tile, kv_tile, kv_tile, q_tile, row_stat, row_stat],
        out_specs=q_tile,
        out_shape=jax.ShapeDtypeStruct((b, t, h * dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        interpret=interpret,
    )(*ids, qf, kf, vf, dof, lse, delta)

    # ---- dk/dv: kv-major, (q-head group x q blocks) innermost ----
    body = _dkv_body if segmented else _plain(_dkv_body)
    dkv_kernel = functools.partial(
        body, causal=causal, window=window, softcap=softcap,
        sm_scale=sm_scale, n_q_blocks=nq, group=group)
    q_tile = pl.BlockSpec((None, block_q, dp),
                          lambda b_, kh, ik, g, iq: (b_, iq, kh * group + g))
    kv_tile = pl.BlockSpec((None, block_kv, dp),
                           lambda b_, kh, ik, g, iq: (b_, ik, kh))
    row_stat = pl.BlockSpec((None, None, block_q, 1),
                            lambda b_, kh, ik, g, iq: (b_, kh * group + g, iq, 0))
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, kvh, nk, group, nq),
        in_specs=_id_specs(
            block_q, block_kv,
            lambda b_, kh, ik, g, iq: (b_, iq),
            lambda b_, kh, ik, g, iq: (b_, ik),
            segmented,
        ) + [q_tile, kv_tile, kv_tile, q_tile, row_stat, row_stat],
        out_specs=[kv_tile, kv_tile],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, kvh * dp), k.dtype),
            jax.ShapeDtypeStruct((b, s, kvh * dp), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, dp), jnp.float32),
            pltpu.VMEM((block_kv, dp), jnp.float32),
        ],
        interpret=interpret,
    )(*ids, qf, kf, vf, dof, lse, delta)
    return (_heads_unflat(dq, h, d), _heads_unflat(dk, kvh, d),
            _heads_unflat(dv, kvh, d))


def _int_ct(x):
    """float0 cotangent for integer primals (positions / segment ids)."""
    return np.zeros(x.shape, jax.dtypes.float0)


# ----------------------------------------------------------------------
# public entry point (custom_vjp)
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, qpos, kpos, causal, window, softcap,
           block_q, block_kv, interpret):
    o, _ = mha_forward(q, k, v, qpos, kpos, causal=causal, window=window,
                       softcap=softcap, block_q=block_q, block_kv=block_kv,
                       interpret=interpret)
    return o


def _flash_fwd(q, k, v, qpos, kpos, causal, window, softcap,
               block_q, block_kv, interpret):
    o, lse = mha_forward(q, k, v, qpos, kpos, causal=causal, window=window,
                         softcap=softcap, block_q=block_q, block_kv=block_kv,
                         interpret=interpret)
    return o, (q, k, v, qpos, kpos, o, lse)


def _flash_bwd(causal, window, softcap, block_q, block_kv, interpret,
               res, do):
    q, k, v, qpos, kpos, o, lse = res
    dq, dk, dv = mha_backward(
        q, k, v, qpos, kpos, None, None, o, lse, do,
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, interpret=interpret)
    return dq, dk, dv, _int_ct(qpos), _int_ct(kpos)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,                  # (B, T, H, D)
    k: jax.Array,                  # (B, S, KV, D)  (GQA-native: KV <= H)
    v: jax.Array,                  # (B, S, KV, D)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float | None = None,
    q_positions: jax.Array | None = None,   # (B, T) int32
    kv_positions: jax.Array | None = None,  # (B, S) int32
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
    return _flash(q, k, v, q_positions.astype(jnp.int32),
                  kv_positions.astype(jnp.int32), causal, int(window),
                  softcap, block_q, block_kv, interpret)
