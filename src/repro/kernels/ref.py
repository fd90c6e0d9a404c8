"""Pure-jnp reference oracles for every Pallas kernel.

These are the ground truth the kernel tests ``assert_allclose`` against, and
the compute path used when Pallas is disabled (``impl="ref"``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, KV, D) -> (B, S, KV*n_rep, D) by head repetition (GQA)."""
    if n_rep == 1:
        return x
    b, s, kv, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, d)).reshape(
        b, s, kv * n_rep, d
    )


def attention_ref(
    q: jax.Array,                 # (B, T, H, D)
    k: jax.Array,                 # (B, S, KV, D)
    v: jax.Array,                 # (B, S, KV, D)
    *,
    causal: bool = True,
    window: int = 0,              # >0: sliding window (causal only)
    softcap: float | None = None,
    q_positions: jax.Array | None = None,   # (B, T) absolute positions
    kv_positions: jax.Array | None = None,  # (B, S)
    q_segment_ids: jax.Array | None = None,   # (B, T); -1 = padding
    kv_segment_ids: jax.Array | None = None,  # (B, S); -1 = padding
    sm_scale: float | None = None,            # default 1/sqrt(D)
    bias: jax.Array | None = None,            # (B, H, T, S) added to scores
) -> jax.Array:
    """Materialized-scores attention. Returns (B, T, H, D) in q.dtype."""
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    assert h % kv == 0, (h, kv)
    k = _repeat_kv(k, h // kv)
    v = _repeat_kv(v, h // kv)

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32))
    if sm_scale is None:
        scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    else:
        scores = scores * sm_scale
    if bias is not None:
        scores = scores + bias
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)

    mask = jnp.ones((b, t, s), dtype=bool)
    dpos = q_positions[:, :, None] - kv_positions[:, None, :]
    if causal:
        mask &= dpos >= 0
        if window > 0:
            mask &= dpos < window
    if q_segment_ids is not None and kv_segment_ids is not None:
        mask &= q_segment_ids[:, :, None] == kv_segment_ids[:, None, :]
        mask &= kv_segment_ids[:, None, :] >= 0
        mask &= q_segment_ids[:, :, None] >= 0

    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    # safe softmax (rows that are fully masked produce zeros)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    e = jnp.where(mask[:, None, :, :], e, 0.0)
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = e / jnp.maximum(l, 1e-30)
    out = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_ref_lse(
    q: jax.Array,                 # (B, T, H, D)
    k: jax.Array,                 # (B, S, KV, D)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float | None = None,
    q_positions: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    q_segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Masked per-row log-sum-exp of the attention logits, (B, H, T) fp32 —
    the oracle for the residual the Pallas forward saves for its backward.
    Rows with no unmasked key return the kernels' -inf sentinel (~NEG_INF)."""
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    k = _repeat_kv(k, h // kv)
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    mask = jnp.ones((b, t, s), dtype=bool)
    dpos = q_positions[:, :, None] - kv_positions[:, None, :]
    if causal:
        mask &= dpos >= 0
        if window > 0:
            mask &= dpos < window
    if q_segment_ids is not None and kv_segment_ids is not None:
        mask &= q_segment_ids[:, :, None] == kv_segment_ids[:, None, :]
        mask &= kv_segment_ids[:, None, :] >= 0
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    l = jnp.sum(jnp.where(mask[:, None, :, :],
                          jnp.exp(scores - m[..., None]), 0.0), axis=-1)
    return m + jnp.log(jnp.maximum(l, 1e-30))


def attention_ref_chunked(
    q, k, v, *,
    causal=True, window=0, softcap=None,
    q_positions=None, kv_positions=None,
    q_segment_ids=None, kv_segment_ids=None,
    block_q: int = 512,
):
    """Same semantics as :func:`attention_ref`, but scanned over q blocks so
    the (T, S) score matrix never materializes — the ``ref`` impl's path
    for long sequences."""
    b, t, h, d = q.shape
    if t <= block_q or t % block_q:
        return attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_positions=q_positions, kv_positions=kv_positions,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids)
    s = k.shape[1]
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    n = t // block_q

    def body(_, xs):
        qc, qp, qseg = xs
        out = attention_ref(
            qc, k, v, causal=causal, window=window, softcap=softcap,
            q_positions=qp, kv_positions=kv_positions,
            q_segment_ids=qseg, kv_segment_ids=kv_segment_ids)
        return (), out

    qs = q.reshape(b, n, block_q, h, d).swapaxes(0, 1)
    qps = q_positions.reshape(b, n, block_q).swapaxes(0, 1)
    if q_segment_ids is not None:
        qsegs = q_segment_ids.reshape(b, n, block_q).swapaxes(0, 1)
    else:
        qsegs = jnp.zeros((n, b, block_q), jnp.int32)
        kv_segment_ids = jnp.zeros((b, s), jnp.int32)
        q_segment_ids = jnp.zeros((b, t), jnp.int32)
        qsegs = q_segment_ids.reshape(b, n, block_q).swapaxes(0, 1)
    _, out = jax.lax.scan(jax.checkpoint(body), (), (qs, qps, qsegs))
    return out.swapaxes(0, 1).reshape(b, t, h, d)


def attention_ref_batchchunked(
    q, k, v, *,
    causal=True, window=0, softcap=None,
    q_positions=None, kv_positions=None,
    q_segment_ids=None, kv_segment_ids=None,
    elem_budget: int = 2048 * 2048 * 8,
):
    """Chunked over *batch rows*: the path for large-batch short-sequence
    micro-batches, where the (B, H, T, S) score tensor is big but no single
    row's (T, S) block is — q-block chunking can't help there (T is below
    its block size), so scan row groups instead. Same semantics as
    :func:`attention_ref`."""
    b, t, h, d = q.shape
    s = k.shape[1]
    rows = max(1, elem_budget // max(t * s * h, 1))
    block_b = 1
    for cand in range(1, b + 1):          # largest divisor of b <= rows
        if b % cand == 0 and cand <= rows:
            block_b = cand
    if block_b >= b:
        return attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_positions=q_positions, kv_positions=kv_positions,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids)
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if q_segment_ids is None or kv_segment_ids is None:
        # attention_ref ignores a one-sided segment arg; all-zero segments
        # reproduce that (no masking) while keeping the scan xs uniform
        q_segment_ids = jnp.zeros((b, t), jnp.int32)
        kv_segment_ids = jnp.zeros((b, s), jnp.int32)
    nb = b // block_b

    def chunk(x):  # (B, ...) -> (nb, block_b, ...)
        return x.reshape(nb, block_b, *x.shape[1:])

    def body(_, xs):
        qc, kc, vc, qp, kp, qs_, ks_ = xs
        out = attention_ref(
            qc, kc, vc, causal=causal, window=window, softcap=softcap,
            q_positions=qp, kv_positions=kp,
            q_segment_ids=qs_, kv_segment_ids=ks_)
        return (), out

    xs = tuple(chunk(x) for x in (q, k, v, q_positions, kv_positions,
                                  q_segment_ids, kv_segment_ids))
    _, out = jax.lax.scan(jax.checkpoint(body), (), xs)
    return out.reshape(b, t, h, d)


# ----------------------------------------------------------------------
# Mamba2 SSD (state-space duality)
# ----------------------------------------------------------------------
def ssd_ref(
    x: jax.Array,      # (B, T, H, P)   inputs per head
    dt: jax.Array,     # (B, T, H)      softplus-ed step sizes (>0)
    A: jax.Array,      # (H,)           negative decay rates (A < 0)
    B: jax.Array,      # (B, T, G, N)   input projections (G groups)
    C: jax.Array,      # (B, T, G, N)   output projections
    *,
    initial_state: jax.Array | None = None,  # (B, H, P, N)
    return_state: bool = False,
):
    """Naive quadratic-materialization SSD. O(T^2) memory — tests only.

    y_t = sum_{s<=t} C_t^T ( prod_{r=s+1..t} exp(A dt_r) ) B_s x_s dt_s  [+ state term]
    """
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert h % g == 0
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)  # (B,T,H,N)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    a = dtf * A[None, None, :]                        # (B,T,H) log-decay per step
    acs = jnp.cumsum(a, axis=1)                       # (B,T,H)
    # decay from s->t: exp(acs_t - acs_s), lower-triangular (t >= s)
    L = jnp.exp(
        jnp.clip(acs[:, :, None, :] - acs[:, None, :, :], -60.0, 0.0)
    )                                                  # (B,T,S,H)
    tri = jnp.tril(jnp.ones((t, t), dtype=bool))
    L = jnp.where(tri[None, :, :, None], L, 0.0)
    # scores_{t,s} = (C_t . B_s) * L_{t,s} * dt_s
    cb = jnp.einsum("bthn,bshn->btsh", Ch, Bh)
    w = cb * L * dtf[:, None, :, :]
    y = jnp.einsum("btsh,bshp->bthp", w, xf)
    state_decay = jnp.exp(jnp.clip(acs, -60.0, None))  # exp(acs_t)
    if initial_state is not None:
        s0 = initial_state.astype(jnp.float32)         # (B,H,P,N)
        y = y + jnp.einsum(
            "bthn,bhpn,bth->bthp", Ch, s0, state_decay
        )
    if not return_state:
        return y.astype(x.dtype)
    # final state: sum_s exp(acs_T - acs_s) dt_s B_s x_s  (+ decayed initial)
    dec_to_end = jnp.exp(jnp.clip(acs[:, -1:, :] - acs, -60.0, 0.0))  # (B,T,H)
    st = jnp.einsum("bth,bthn,bthp->bhpn", dec_to_end * dtf, Bh, xf)
    if initial_state is not None:
        st = st + initial_state.astype(jnp.float32) * jnp.exp(
            jnp.clip(acs[:, -1, :], -60.0, None)
        )[:, :, None, None]
    return y.astype(x.dtype), st


def attention_ref_headchunked(
    q, k, v, *,
    causal=True, window=0, softcap=None,
    q_positions=None, kv_positions=None,
    q_segment_ids=None, kv_segment_ids=None,
    block_h: int | None = None,
):
    """Chunked over *heads* instead of query blocks.

    Used when the q sequence dim is mesh-sharded (sequence-parallel attention
    for uneven-head archs): scanning over a sharded dim would reshard every
    step, but the head dim is replicated, so scanning heads keeps the score
    working set to (B, block_h, T, S) with zero cross-shard traffic."""
    b, t, h, d = q.shape
    kvh = k.shape[2]
    if h % kvh == 0 and kvh != h:
        k = _repeat_kv(k, h // kvh)
        v = _repeat_kv(v, h // kvh)
    if block_h is None:
        # largest divisor of h keeping global score elems <= 2^37
        # (~2 GiB fp32 per device once dp- and sp-sharded 256 ways)
        budget = max(1, (1 << 37) // max(b * t * k.shape[1], 1))
        block_h = 1
        for cand in range(1, h + 1):
            if h % cand == 0 and cand <= budget:
                block_h = cand
    if h <= block_h or h % block_h:
        return attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_positions=q_positions, kv_positions=kv_positions,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids)
    n = h // block_h

    def body(_, xs):
        qc, kc, vc = xs
        out = attention_ref(
            qc, kc, vc, causal=causal, window=window, softcap=softcap,
            q_positions=q_positions, kv_positions=kv_positions,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids)
        return (), out

    qs = q.reshape(b, t, n, block_h, d).transpose(2, 0, 1, 3, 4)
    ks = k.reshape(b, t, n, block_h, d).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(b, t, n, block_h, d).transpose(2, 0, 1, 3, 4)
    _, out = jax.lax.scan(jax.checkpoint(body), (), (qs, ks, vs))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, t, h, d)


def ssd_ref_chunked(
    x: jax.Array,      # (B, T, H, P)
    dt: jax.Array,     # (B, T, H)
    A: jax.Array,      # (H,)
    B: jax.Array,      # (B, T, G, N)
    C: jax.Array,      # (B, T, G, N)
    *,
    block_t: int = 128,
    return_state: bool = False,
):
    """Chunked SSD in pure jnp (scan over chunks carrying the state).

    Mirrors the Pallas kernel's algorithm; the largest intermediate is the
    per-chunk (block_t × block_t) decay matrix instead of the full (T × T)
    one — this is the XLA-visible lowering path for long sequences.
    """
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    if t % block_t or t <= block_t:
        return ssd_ref(x, dt, A, B, C, return_state=return_state)
    rep = h // g
    nc = t // block_t
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def chunkify(v):  # (B, T, ...) -> (nc, B, bt, ...)
        return v.reshape(b, nc, block_t, *v.shape[2:]).swapaxes(0, 1)

    xs = (chunkify(xf), chunkify(dtf), chunkify(Bh), chunkify(Ch))

    def body(state, xs_c):
        xc, dtc, Bc, Cc = xs_c                     # (B, bt, H, ...)
        a = dtc * A[None, None, :]                  # (B, bt, H)
        cum = jnp.cumsum(a, axis=1)
        seg = jnp.clip(cum[:, :, None, :] - cum[:, None, :, :], -60.0, 0.0)
        tri = jnp.tril(jnp.ones((block_t, block_t), dtype=bool))
        Lm = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
        cb = jnp.einsum("bthn,bshn->btsh", Cc, Bc)
        w = cb * Lm * dtc[:, None, :, :]
        y = jnp.einsum("btsh,bshp->bthp", w, xc)
        # inter-chunk contribution
        cdec = Cc * jnp.exp(jnp.clip(cum, -60.0, None))[..., None]
        y = y + jnp.einsum("bthn,bhpn->bthp", cdec, state)
        # state update
        a_tot = cum[:, -1:, :]
        dec_end = jnp.exp(jnp.clip(a_tot - cum, -60.0, 0.0)) * dtc
        upd = jnp.einsum("bth,bthn,bthp->bhpn", dec_end, Bc, xc)
        state = state * jnp.exp(jnp.clip(a_tot[:, 0, :], -60.0, None))[:, :, None, None] + upd
        return state, y

    state0 = jnp.zeros((b, h, p, n), jnp.float32)
    final_state, ys = jax.lax.scan(jax.checkpoint(body), state0, xs)
    y = ys.swapaxes(0, 1).reshape(b, t, h, p).astype(x.dtype)
    if return_state:
        return y, final_state
    return y


def ssd_decode_ref(
    x: jax.Array,      # (B, H, P)   one token
    dt: jax.Array,     # (B, H)
    A: jax.Array,      # (H,)
    B: jax.Array,      # (B, G, N)
    C: jax.Array,      # (B, G, N)
    state: jax.Array,  # (B, H, P, N)
):
    """Single-step SSM recurrence used by the decode path."""
    b, h, p = x.shape
    g, n = B.shape[1], B.shape[2]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)   # (B,H,N)
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(dtf * A[None, :])                      # (B,H)
    upd = jnp.einsum("bh,bhn,bhp->bhpn", dtf, Bh, x.astype(jnp.float32))
    new_state = state.astype(jnp.float32) * decay[:, :, None, None] + upd
    y = jnp.einsum("bhn,bhpn->bhp", Ch, new_state)
    return y.astype(x.dtype), new_state.astype(state.dtype)
