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

Relative position bias (T5): an optional per-head table of bucket biases
(``RelBias``) adds ``table[h, bucket(j − i)]`` to the score of query index
i and key index j, in the forward and in both backward passes; the dk/dv
pass also returns the table's gradient. No (T, S) bias exists outside a
tile: each tile builds its bias from the one row of offsets it spans (see
``RelBias``). Those calls are named ``flash_fwd_relbias``,
``flash_dq_relbias`` and ``flash_dkv_relbias``; calls without a table keep
the names and code they had.

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

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import relpos

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
# relative position bias (T5)
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RelBias:
    """How a tile builds its relative position bias, and d(table) from it.

    The relative position of query index i and key index j is ``j − i``,
    read off the tile's place in the grid: it equals ``kv_position −
    q_position`` for every pair the masks keep wherever positions count up
    by one within a segment, as every training row's do. A (block_q,
    block_kv) tile spans block_q + block_kv − 1 offsets ``j − i``. The tile
    looks up the bias of that one row of offsets (``width`` lanes) and turns
    it into its Toeplitz block with one strided lane roll, each row rolled
    one lane further than the row above. The table's gradient goes back:
    the tile's score gradient with its lanes reversed (an exchange matrix
    on the MXU per 128 lanes, as the TPU has no lane reversal and its
    strided roll no negative stride), rolled the same way so that each
    diagonal j − i lands in one lane, summed over rows, and the diagonals
    summed into their buckets.
    """
    n_buckets: int
    max_distance: int
    bidirectional: bool
    block_q: int
    block_kv: int

    @property
    def width(self) -> int:
        """Lanes of the offset row: block_q + block_kv − 1, rounded up."""
        return -(-(self.block_q + self.block_kv - 1) // _LANES) * _LANES

    def _onehot(self, offset, iq, ik):
        """(n_buckets, width) bool: the bucket of each lane's offset
        (``offset``, (1, width) int32) within tile (iq, ik)."""
        rel = ik * self.block_kv - iq * self.block_q + offset
        b = relpos.bucket(rel, n_buckets=self.n_buckets,
                          max_distance=self.max_distance,
                          bidirectional=self.bidirectional)
        return b == jax.lax.broadcasted_iota(
            jnp.int32, (self.n_buckets, self.width), 0)

    def _lanes(self):
        return jax.lax.broadcasted_iota(jnp.int32, (1, self.width), 1)

    def tile(self, table, iq, ik):
        """(block_q, block_kv) float32 bias from a (n_buckets, 1) table."""
        # lane m holds offset m, or m − width past the keys, so that
        # rolling row i by i puts offset j − i at its lane j
        m = self._lanes()
        offset = jnp.where(m < self.block_kv, m, m - self.width)
        row = jnp.sum(jnp.where(self._onehot(offset, iq, ik), table, 0.0),
                      axis=0, keepdims=True)
        rows = jnp.broadcast_to(row, (self.block_q, self.width))
        rows = pltpu.roll(rows, 0, 1, stride=1, stride_axis=0)
        return rows[:, :self.block_kv]

    def table_grad(self, ds, iq, ik):
        """(n_buckets, 1) float32 d(table) from a tile's score gradient."""
        x = _reverse_lanes(ds)          # lane m: key block_kv − 1 − m
        pad = self.width - self.block_kv
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((self.block_q, pad), x.dtype)], axis=1)
        # row i, lane w = m + i: offset j − i = block_kv − 1 − w
        x = pltpu.roll(x, 0, 1, stride=1, stride_axis=0)
        diag = jnp.sum(x, axis=0, keepdims=True)
        offset = self.block_kv - 1 - self._lanes()
        return jnp.sum(jnp.where(self._onehot(offset, iq, ik), diag, 0.0),
                       axis=1, keepdims=True)


def _reverse_lanes(x):
    """x with its last axis reversed: each chunk of up to 128 lanes times
    an exchange matrix, the chunks in reverse order."""
    n = x.shape[1]
    out = []
    for lo in range(0, n, _LANES):
        c = min(_LANES, n - lo)
        i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        exchange = (i + j == c - 1).astype(x.dtype)
        out.append(jax.lax.dot_general(
            x[:, lo:lo + c], exchange, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    return out[0] if len(out) == 1 else jnp.concatenate(out[::-1], axis=1)


def _rel_bias(table, max_distance, causal, softcap, block_q, block_kv):
    if table is None:
        return None
    assert softcap is None, "a relative bias with a logit softcap"
    return RelBias(int(table.shape[-1]), int(max_distance), not causal,
                   block_q, block_kv)


# ----------------------------------------------------------------------
# kernel bodies
#
# Per-token int data enters the kernels in two orientations: query-side
# positions/segment ids as (block_q, 1) columns, kv-side ones as
# (1, block_kv) rows, so ``q - k`` and ``q == k`` broadcast straight to the
# (block_q, block_kv) score tile. Row statistics (m, l, lse, delta) are
# (block_q, 1) columns for the same reason.
# ----------------------------------------------------------------------
def _refs(refs, segmented, biased, n_in, n_out, table_grad=False):
    """A kernel's refs in the builders' order: positions, segment ids
    (ragged calls), the data inputs, the bias table (biased calls); the
    outputs, d(table) (the biased dk/dv pass); the scratch buffers. What a
    call lacks is None."""
    it = iter(refs)

    def take(n):
        return [next(it) for _ in range(n)]
    ids = take(2) + (take(2) if segmented else [None, None])
    ins = take(n_in)
    table = next(it) if biased else None
    outs = take(n_out)
    dtable = next(it) if table_grad else None
    return ids, ins, table, outs, dtable, list(it)


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


def _fwd_body(*refs, segmented, rel_bias, causal, window, softcap, sm_scale,
              n_kv_blocks):
    ids, (q_ref, k_ref, v_ref), tab_ref, (o_ref, lse_ref), _, \
        (m_ref, l_ref, acc_ref) = _refs(refs, segmented,
                                        rel_bias is not None, 3, 2)
    kv_idx = pl.program_id(3)
    q_idx = pl.program_id(2) if rel_bias is not None else None

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos, kpos, qseg, kseg = _read_ids(*ids)
    live = _block_stats(qpos, kpos, qseg, kseg, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)          # (bq, d)
        k = k_ref[...].astype(jnp.float32)          # (bk, d)
        v = v_ref[...].astype(jnp.float32)
        s, _ = _scores(q, k, sm_scale, softcap)
        if rel_bias is not None:
            s = s + rel_bias.tile(tab_ref[...], q_idx, kv_idx)
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
              causal, window, softcap, sm_scale, bias=None):
    """Recompute p from residuals and chain d(loss)/d(raw logits)."""
    s1, th = _scores(q, k, sm_scale, softcap)
    if bias is not None:
        s1 = s1 + bias
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


def _dq_body(*refs, segmented, rel_bias, causal, window, softcap, sm_scale,
             n_kv_blocks):
    ids, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), tab_ref, \
        (dq_ref,), _, (dq_acc,) = _refs(refs, segmented,
                                        rel_bias is not None, 6, 1)
    kv_idx = pl.program_id(3)
    q_idx = pl.program_id(2) if rel_bias is not None else None

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    qpos, kpos, qseg, kseg = _read_ids(*ids)
    live = _block_stats(qpos, kpos, qseg, kseg, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        bias = None if rel_bias is None else rel_bias.tile(
            tab_ref[...], q_idx, kv_idx)
        _, ds = _p_and_ds(q, k, qpos, kpos, qseg, kseg, lse_ref[...], do, v,
                          delta_ref[...], causal, window, softcap, sm_scale,
                          bias)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when(kv_idx == n_kv_blocks - 1)
    def _finalize():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_body(*refs, segmented, rel_bias, causal, window, softcap, sm_scale,
              n_q_blocks, group):
    biased = rel_bias is not None
    ids, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), tab_ref, \
        (dk_ref, dv_ref), dtab_ref, (dk_acc, dv_acc) = _refs(
            refs, segmented, biased, 6, 2, table_grad=biased)
    g = pl.program_id(3)
    q_idx = pl.program_id(4)
    kv_idx = pl.program_id(2) if biased else None

    @pl.when((g == 0) & (q_idx == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if biased:
        # d(table) of this (batch, kv head) sums over every kv block
        @pl.when((kv_idx == 0) & (g == 0) & (q_idx == 0))
        def _init_table():
            dtab_ref[...] = jnp.zeros_like(dtab_ref)

    qpos, kpos, qseg, kseg = _read_ids(*ids)
    live = _block_stats(qpos, kpos, qseg, kseg, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        bias = None if not biased else rel_bias.tile(
            tab_ref[...], q_idx, kv_idx)
        p, ds = _p_and_ds(q, k, qpos, kpos, qseg, kseg, lse_ref[...], do, v,
                          delta_ref[...], causal, window, softcap, sm_scale,
                          bias)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if biased:
            nb = rel_bias.n_buckets
            dtab_ref[pl.ds(g * nb, nb), :] += rel_bias.table_grad(
                ds, q_idx, kv_idx)

    @pl.when((g == group - 1) & (q_idx == n_q_blocks - 1))
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


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
#   bias table           (H, NB, 1)    blocks (NB, 1), one head's buckets
#   d(table)             (B, KV, G*NB, 1)  one block per (batch, kv head)
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


def _table_arg(table):
    h, nb = table.shape
    return table.astype(jnp.float32).reshape(h, nb, 1)


def _dims(q, k):
    b, t, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    return b, t, h, d, s, kvh, h // kvh, -(-d // _LANES) * _LANES


def mha_forward(q, k, v, q_positions, kv_positions,
                q_segment_ids=None, kv_segment_ids=None, *,
                causal, window=0, softcap=None, sm_scale=None,
                rel_table=None, rel_max_distance=128,
                block_q, block_kv, interpret=False):
    """Raw forward: returns ``(o, lse)`` with lse in (B, H, T) fp32.
    ``sm_scale`` defaults to 1/sqrt(d); ``rel_table`` (H, n_buckets) adds
    T5's relative position bias, bucketed causally when ``causal``."""
    b, t, h, d, s, kvh, group, dp = _dims(q, k)
    block_q = shrink_block(t, block_q)
    block_kv = shrink_block(s, block_kv)
    nq, nk = t // block_q, s // block_kv
    segmented = q_segment_ids is not None
    sm_scale = 1.0 / math.sqrt(d) if sm_scale is None else sm_scale
    rel = _rel_bias(rel_table, rel_max_distance, causal, softcap,
                    block_q, block_kv)

    kernel = functools.partial(
        _fwd_body, segmented=segmented, rel_bias=rel, causal=causal,
        window=window, softcap=softcap, sm_scale=sm_scale, n_kv_blocks=nk)

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
    if rel is not None:
        in_specs.append(pl.BlockSpec((None, rel.n_buckets, 1),
                                     lambda b_, h_, iq, ik: (h_, 0, 0)))
        args.append(_table_arg(rel_table))

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
        name=None if rel is None else "flash_fwd_relbias",
    )(*args)
    return _heads_unflat(o, h, d), lse[..., 0]


def mha_backward(q, k, v, q_positions, kv_positions,
                 q_segment_ids, kv_segment_ids, o, lse, do, *,
                 causal, window=0, softcap=None, sm_scale,
                 rel_table=None, rel_max_distance=128,
                 block_q, block_kv, interpret=False):
    """Fused backward from residuals: returns ``(dq, dk, dv, dtable)``,
    ``dtable`` (H, n_buckets) fp32, None without a table. ``sm_scale`` is
    the forward's."""
    b, t, h, d, s, kvh, group, dp = _dims(q, k)
    block_q = shrink_block(t, block_q)
    block_kv = shrink_block(s, block_kv)
    nq, nk = t // block_q, s // block_kv
    segmented = q_segment_ids is not None
    rel = _rel_bias(rel_table, rel_max_distance, causal, softcap,
                    block_q, block_kv)

    # delta_i = sum_d do_i * o_i — one fused elementwise-reduce over (B,T,H,D)
    delta = jnp.einsum("bthd,bthd->bht", do.astype(jnp.float32),
                       o.astype(jnp.float32))[..., None]

    lse = lse[..., None]
    ids = _id_args(q_positions, kv_positions, q_segment_ids, kv_segment_ids)
    qf, kf, vf, dof = (_heads_flat(x, dp) for x in (q, k, v, do))
    table = [] if rel is None else [_table_arg(rel_table)]
    static = dict(segmented=segmented, rel_bias=rel, causal=causal,
                  window=window, softcap=softcap, sm_scale=sm_scale)

    # ---- dq: q-major, kv innermost ----
    dq_kernel = functools.partial(_dq_body, n_kv_blocks=nk, **static)
    q_tile = pl.BlockSpec((None, block_q, dp),
                          lambda b_, h_, iq, ik: (b_, iq, h_))
    kv_tile = pl.BlockSpec((None, block_kv, dp),
                           lambda b_, h_, iq, ik: (b_, ik, h_ // group))
    row_stat = pl.BlockSpec((None, None, block_q, 1),
                            lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    table_spec = [] if rel is None else [pl.BlockSpec(
        (None, rel.n_buckets, 1), lambda b_, h_, iq, ik: (h_, 0, 0))]
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, nq, nk),
        in_specs=_id_specs(
            block_q, block_kv,
            lambda b_, h_, iq, ik: (b_, iq),
            lambda b_, h_, iq, ik: (b_, ik),
            segmented,
        ) + [q_tile, kv_tile, kv_tile, q_tile, row_stat, row_stat]
        + table_spec,
        out_specs=q_tile,
        out_shape=jax.ShapeDtypeStruct((b, t, h * dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        interpret=interpret,
        name=None if rel is None else "flash_dq_relbias",
    )(*ids, qf, kf, vf, dof, lse, delta, *table)

    # ---- dk/dv: kv-major, (q-head group x q blocks) innermost ----
    dkv_kernel = functools.partial(_dkv_body, n_q_blocks=nq, group=group,
                                   **static)
    q_tile = pl.BlockSpec((None, block_q, dp),
                          lambda b_, kh, ik, g, iq: (b_, iq, kh * group + g))
    kv_tile = pl.BlockSpec((None, block_kv, dp),
                           lambda b_, kh, ik, g, iq: (b_, ik, kh))
    row_stat = pl.BlockSpec((None, None, block_q, 1),
                            lambda b_, kh, ik, g, iq: (b_, kh * group + g, iq, 0))
    out_specs = [kv_tile, kv_tile]
    out_shape = [
        jax.ShapeDtypeStruct((b, s, kvh * dp), k.dtype),
        jax.ShapeDtypeStruct((b, s, kvh * dp), v.dtype),
    ]
    if rel is not None:
        table_spec = [pl.BlockSpec(
            (None, rel.n_buckets, 1),
            lambda b_, kh, ik, g, iq: (kh * group + g, 0, 0))]
        out_specs.append(pl.BlockSpec(
            (None, None, group * rel.n_buckets, 1),
            lambda b_, kh, ik, g, iq: (b_, kh, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(
            (b, kvh, group * rel.n_buckets, 1), jnp.float32))
    outs = pl.pallas_call(
        dkv_kernel,
        grid=(b, kvh, nk, group, nq),
        in_specs=_id_specs(
            block_q, block_kv,
            lambda b_, kh, ik, g, iq: (b_, iq),
            lambda b_, kh, ik, g, iq: (b_, ik),
            segmented,
        ) + [q_tile, kv_tile, kv_tile, q_tile, row_stat, row_stat]
        + table_spec,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_kv, dp), jnp.float32),
            pltpu.VMEM((block_kv, dp), jnp.float32),
        ],
        interpret=interpret,
        name=None if rel is None else "flash_dkv_relbias",
    )(*ids, qf, kf, vf, dof, lse, delta, *table)
    dk, dv = outs[:2]
    dtable = None if rel is None else \
        jnp.sum(outs[2], axis=0).reshape(h, rel.n_buckets)
    return (_heads_unflat(dq, h, d), _heads_unflat(dk, kvh, d),
            _heads_unflat(dv, kvh, d), dtable)


def _int_ct(x):
    """float0 cotangent for integer primals (positions / segment ids)."""
    return np.zeros(x.shape, jax.dtypes.float0)


# ----------------------------------------------------------------------
# public entry point (custom_vjp)
#
# ``sm_scale`` is resolved before the custom_vjp call, so the forward and
# the backward are handed the one value.
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(6, 14)))
def _flash(q, k, v, qpos, kpos, table, causal, window, softcap, sm_scale,
           max_distance, block_q, block_kv, interpret):
    o, _ = mha_forward(q, k, v, qpos, kpos, causal=causal, window=window,
                       softcap=softcap, sm_scale=sm_scale, rel_table=table,
                       rel_max_distance=max_distance, block_q=block_q,
                       block_kv=block_kv, interpret=interpret)
    return o


def _flash_fwd(q, k, v, qpos, kpos, table, causal, window, softcap, sm_scale,
               max_distance, block_q, block_kv, interpret):
    o, lse = mha_forward(q, k, v, qpos, kpos, causal=causal, window=window,
                         softcap=softcap, sm_scale=sm_scale, rel_table=table,
                         rel_max_distance=max_distance, block_q=block_q,
                         block_kv=block_kv, interpret=interpret)
    return o, (q, k, v, qpos, kpos, table, o, lse)


def _flash_bwd(causal, window, softcap, sm_scale, max_distance, block_q,
               block_kv, interpret, res, do):
    q, k, v, qpos, kpos, table, o, lse = res
    dq, dk, dv, dtable = mha_backward(
        q, k, v, qpos, kpos, None, None, o, lse, do,
        causal=causal, window=window, softcap=softcap, sm_scale=sm_scale,
        rel_table=table, rel_max_distance=max_distance,
        block_q=block_q, block_kv=block_kv, interpret=interpret)
    return dq, dk, dv, _int_ct(qpos), _int_ct(kpos), dtable


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
    return _flash(q, k, v, q_positions.astype(jnp.int32),
                  kv_positions.astype(jnp.int32), rel_bias, causal,
                  int(window), softcap, _scale(sm_scale, d),
                  int(rel_max_distance), block_q, block_kv, interpret)


def _scale(sm_scale, d: int) -> float:
    return 1.0 / math.sqrt(d) if sm_scale is None else float(sm_scale)
