"""Interpret-mode kernel *gradient* property tests vs the jnp oracle.

The training path differentiates straight through the Pallas kernels
(``jax.custom_vjp``: fwd saves (o, lse), bwd precomputes delta and runs the
dq / dk+dv passes with the forward's block-skip predicate). Every config in
the matrix below asserts dq/dk/dv from ``jax.grad`` of
``ops.attention(..., impl="interpret")`` match the ``ref`` oracle grads —
no silent fallback to ``ref`` for ragged, window, softcap, or GQA inputs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import live_block_mask
from repro.kernels.ref import attention_ref_lse

KEY = jax.random.PRNGKey(7)

GRAD_TOL = {jnp.float32: 2e-4, jnp.bfloat16: 4e-2}


def _inputs(b, t, s, h, d, kv, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32).astype(dtype)
    ct = jax.random.normal(ks[3], (b, t, h, d), jnp.float32)
    return q, k, v, ct


def _segments(t, layout):
    if layout == "packed":
        seg_row = np.r_[np.zeros(t // 2 - 8), np.ones(t // 4),
                        2 * np.ones(t - (t // 2 - 8) - (t // 4) - 12),
                        -np.ones(12)]
    elif layout == "all_pad":
        seg_row = -np.ones(t)
    else:
        seg_row = np.zeros(t)
    return jnp.asarray(np.stack([seg_row, np.zeros(t)]), jnp.int32)


def _positions(segs):
    pos = []
    for row in np.asarray(segs):
        p, cur, cnt = [], None, 0
        for sid in row:
            if sid != cur:
                cur, cnt = sid, 0
            p.append(cnt)
            cnt += 1
        pos.append(p)
    return jnp.asarray(pos, jnp.int32)


def _grads(q, k, v, ct, impl, **kw):
    def f(q, k, v):
        out = ops.attention(q, k, v, impl=impl, **kw)
        return jnp.sum(out.astype(jnp.float32) * ct)
    return jax.grad(f, argnums=(0, 1, 2))(q, k, v)


def _assert_grads_match(q, k, v, ct, dtype, **kw):
    gi = _grads(q, k, v, ct, "interpret", **kw)
    gr = _grads(q, k, v, ct, "ref", **kw)
    tol = GRAD_TOL[dtype]
    for name, a, b in zip("qkv", gi, gr):
        assert a.dtype == b.dtype, (name, a.dtype, b.dtype)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol, rtol=tol, err_msg=f"d{name} mismatch for {kw}")


# ----------------------------------------------------------------------
# the property matrix (acceptance: every config, no ref fallback)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, None),        # plain causal
    (True, 24, None),       # sliding window (gemma2 local)
    (True, 0, 15.0),        # logit softcap
    (True, 24, 15.0),       # both
    (False, 0, None),       # bidirectional (encoder)
])
@pytest.mark.parametrize("ragged", [False, True])
def test_grad_matrix(dtype, causal, window, softcap, ragged):
    b, t, h, d, kv = 2, 96, 4, 32, 2          # GQA group 2; 96 gcd-shrinks
    q, k, v, ct = _inputs(b, t, t, h, d, kv, dtype)
    kw = dict(causal=causal, window=window, softcap=softcap,
              block_q=32, block_kv=32)
    if ragged:
        segs = _segments(t, "packed")
        pos = _positions(segs)
        kw.update(q_segment_ids=segs, kv_segment_ids=segs,
                  q_positions=pos, kv_positions=pos)
    _assert_grads_match(q, k, v, ct, dtype, **kw)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grad_all_padding_rows(dtype):
    """A row that is 100% padding (and one partially padded) must produce
    finite zero grads through the kernel backward, not NaNs from the
    fully-masked-row lse sentinel."""
    b, t, h, d = 2, 64, 2, 16
    q, k, v, ct = _inputs(b, t, t, h, d, h, dtype)
    segs = _segments(t, "all_pad")
    segs = segs.at[1, 40:].set(-1)            # row 1: trailing padding
    kw = dict(q_segment_ids=segs, kv_segment_ids=segs,
              block_q=16, block_kv=16)
    gi = _grads(q, k, v, ct, "interpret", **kw)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in gi)
    # the all-padding row's grads are exactly zero
    assert not np.asarray(gi[0], np.float32)[0].any()
    _assert_grads_match(q, k, v, ct, dtype, **kw)


def test_grad_one_sided_segments_cross_attention():
    """kv-only segment ids (cross-attention over padded encoder keys),
    t != s, GQA: ops synthesizes the q side and the kernel differentiates."""
    b, t, s, h, d, kv = 1, 32, 64, 4, 16, 2
    q, k, v, ct = _inputs(b, t, s, h, d, kv, jnp.float32)
    kv_segs = jnp.asarray(np.r_[np.zeros(40), -np.ones(24)][None], jnp.int32)
    _assert_grads_match(q, k, v, ct, jnp.float32, causal=False,
                        kv_segment_ids=kv_segs, block_q=16, block_kv=16)


def test_grad_decode_style_positions():
    """Arbitrary absolute positions (prefill against a longer cache)."""
    b, t, s, h, d = 1, 32, 64, 2, 16
    q, k, v, ct = _inputs(b, t, s, h, d, h, jnp.float32)
    qpos = jnp.broadcast_to(16 + jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    _assert_grads_match(q, k, v, ct, jnp.float32, causal=True,
                        q_positions=qpos, block_q=16, block_kv=16)


# ----------------------------------------------------------------------
# lse / delta numerics
# ----------------------------------------------------------------------
def test_forward_lse_matches_oracle():
    """The saved lse residual equals the oracle's masked logsumexp on every
    live row; fully-masked rows carry the -inf sentinel."""
    from repro.kernels.flash_attention import mha_forward
    b, t, h, d, kv = 2, 96, 4, 32, 2
    q, k, v, _ = _inputs(b, t, t, h, d, kv, jnp.float32)
    segs = _segments(t, "packed")
    pos = _positions(segs)
    o, lse = mha_forward(q, k, v, pos, pos, segs, segs, causal=True,
                         window=24, softcap=15.0, block_q=32, block_kv=32,
                         interpret=True)
    ref = attention_ref_lse(q, k, causal=True, window=24, softcap=15.0,
                            q_positions=pos, kv_positions=pos,
                            q_segment_ids=segs, kv_segment_ids=segs)
    ref = np.asarray(ref)
    live = ref > -1e29
    assert live.any() and not live.all()
    np.testing.assert_allclose(np.asarray(lse)[live], ref[live],
                               atol=1e-4, rtol=1e-4)
    assert (np.asarray(lse)[~live] < -1e29).all()


def test_backward_delta_identity():
    """delta = rowsum(do * o) equals rowsum(p * dp) — the softmax-VJP
    identity the backward relies on. Checked through the composed grads:
    scaling the cotangent scales dq linearly (softmax grads are linear in
    the upstream cotangent)."""
    b, t, h, d = 1, 64, 2, 16
    q, k, v, ct = _inputs(b, t, t, h, d, h, jnp.float32)
    g1 = _grads(q, k, v, ct, "interpret", block_q=16, block_kv=16)
    g2 = _grads(q, k, v, 2.0 * ct, "interpret", block_q=16, block_kv=16)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(2.0 * np.asarray(a), np.asarray(b_),
                                   atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------------
# impl is sticky under grad; no HBM materialization
# ----------------------------------------------------------------------
def _walk_eqns(jaxpr, fn):
    for eqn in jaxpr.eqns:
        fn(eqn)
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                _walk_eqns(sub.jaxpr, fn)
            elif hasattr(sub, "eqns"):
                _walk_eqns(sub, fn)
            elif isinstance(sub, (list, tuple)):
                for s2 in sub:
                    if hasattr(s2, "jaxpr") and hasattr(s2.jaxpr, "eqns"):
                        _walk_eqns(s2.jaxpr, fn)


def test_impl_sticky_under_grad():
    """grad of the interpret impl runs three Pallas kernels (fwd when the
    vjp re-traces, dq, dk/dv) — it must not silently re-route to ref."""
    b, t, h, d = 1, 64, 2, 16
    q, k, v, ct = _inputs(b, t, t, h, d, h, jnp.float32)

    def f(q, k, v):
        return jnp.sum(ops.attention(q, k, v, impl="interpret",
                                     block_q=16, block_kv=16) * ct)

    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    n = []
    _walk_eqns(jaxpr.jaxpr,
               lambda e: n.append(1) if e.primitive.name == "pallas_call"
               else None)
    assert sum(n) == 3, f"expected fwd+dq+dkv pallas_calls, got {sum(n)}"


def test_no_kv_repeat_or_per_head_position_repeat_in_jaxpr():
    """Acceptance: the kernel path performs no ``_repeat_kv`` K/V
    materialization and no per-head repeat of positions/segments. With
    t != s and kv < h, a repeated K/V would be the unique shape
    (b, s, h, d) and repeated positions (b*h, t)/(b*h, s) — assert no
    value of those shapes exists anywhere in the fwd+bwd jaxpr."""
    b, t, s, h, d, kv = 2, 64, 128, 4, 32, 2
    q, k, v, ct = _inputs(b, t, s, h, d, kv, jnp.float32)
    segs = jnp.zeros((b, s), jnp.int32)

    def f(q, k, v):
        out = ops.attention(q, k, v, causal=False, impl="interpret",
                            kv_segment_ids=segs)
        return jnp.sum(out * ct)

    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    bad = {(b, s, h, d), (b * h, t), (b * h, s)}
    hits = []

    def check(eqn):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            shape = tuple(getattr(aval, "shape", ()))
            if shape in bad:
                hits.append((eqn.primitive.name, shape))

    _walk_eqns(jaxpr.jaxpr, check)
    assert not hits, f"materialized repeated K/V or positions: {hits}"


# ----------------------------------------------------------------------
# block-skip predicate (shared with benches)
# ----------------------------------------------------------------------
def test_live_block_mask_matches_kernel_semantics():
    """Blocks the predicate marks dead contribute nothing: zeroing K/V in
    dead blocks leaves the output bit-identical."""
    b, t, h, d = 1, 512, 1, 16
    q, k, v, _ = _inputs(b, t, t, h, d, h, jnp.float32)
    segs = _segments(t, "packed")[:1]
    pos = _positions(segs)
    bq = bk = 128                              # the smallest legal TPU tile
    mask = live_block_mask(pos, pos, segs, segs, causal=True,
                           block_q=bq, block_kv=bk)
    assert mask.shape == (1, t // bq, t // bk)
    assert not mask.all() and mask.any()

    out = ops.attention(q, k, v, impl="interpret", q_segment_ids=segs,
                        kv_segment_ids=segs, q_positions=pos,
                        kv_positions=pos, block_q=bq, block_kv=bk)
    # zero every kv block that is dead for ALL q blocks; output unchanged
    dead_kv = ~mask[0].any(axis=0)
    kz = np.asarray(k).copy()
    vz = np.asarray(v).copy()
    for j, deadj in enumerate(dead_kv):
        if deadj:
            kz[:, j * bk:(j + 1) * bk] = 7.7
            vz[:, j * bk:(j + 1) * bk] = -3.3
    out2 = ops.attention(q, jnp.asarray(kz), jnp.asarray(vz),
                         impl="interpret", q_segment_ids=segs,
                         kv_segment_ids=segs, q_positions=pos,
                         kv_positions=pos, block_q=bq, block_kv=bk)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_block_skip_survives_nan_in_dead_blocks():
    """The backward passes must *actually* skip dead blocks, not merely
    mask them: NaNs planted in block-aligned all-padding K/V regions
    propagate through any pass that touches the block (0 · NaN = NaN in
    the ds/dp chain), so finite outputs and grads prove `pl.when(live)`
    gated the compute in fwd, dq, AND dk/dv. (The analytic live-block
    fraction in bench_attention mirrors the predicate; this is the test
    that the kernels enforce it.)"""
    b, t, h, d = 2, 512, 2, 16
    bq = 128                                   # the smallest legal TPU tile
    pad_from = 384                             # block-aligned padding start
    q, k, v, ct = _inputs(b, t, t, h, d, h, jnp.float32)
    seg = np.zeros((b, t), np.int32)
    seg[:, pad_from:] = -1
    segs = jnp.asarray(seg)
    k = k.at[:, pad_from:].set(jnp.nan)
    v = v.at[:, pad_from:].set(jnp.nan)

    out = ops.attention(q, k, v, impl="interpret", q_segment_ids=segs,
                        kv_segment_ids=segs, block_q=bq, block_kv=bq)
    assert np.isfinite(np.asarray(out)).all()

    def f(q, k, v):
        o = ops.attention(q, k, v, impl="interpret", q_segment_ids=segs,
                          kv_segment_ids=segs, block_q=bq, block_kv=bq)
        return jnp.sum(o * ct)

    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    assert np.isfinite(np.asarray(dq)).all()
    assert np.isfinite(np.asarray(dk)[:, :pad_from]).all()
    assert np.isfinite(np.asarray(dv)[:, :pad_from]).all()
    # grads w.r.t. the dead region are exactly zero, written by the
    # init-once scratch — not NaN-contaminated accumulators
    assert not np.asarray(dq)[:, pad_from:].any()


def test_ref_batchchunked_matches_unchunked():
    """The large-batch short-seq ref path (scan over row groups) is exact."""
    from repro.kernels.ref import attention_ref, attention_ref_batchchunked
    b, t, h, d = 8, 64, 2, 16
    q, k, v, _ = _inputs(b, t, t, h, d, h, jnp.float32)
    segs = jnp.tile(_segments(t, "packed")[:1], (b, 1))
    ref = attention_ref(q, k, v, q_segment_ids=segs, kv_segment_ids=segs)
    out = attention_ref_batchchunked(q, k, v, q_segment_ids=segs,
                                     kv_segment_ids=segs,
                                     elem_budget=2 * t * t * h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    # one-sided segment ids are ignored, exactly like attention_ref
    out = attention_ref_batchchunked(q, k, v, kv_segment_ids=segs,
                                     elem_budget=2 * t * t * h)
    ref = attention_ref(q, k, v, kv_segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_default_impl_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    assert ops.default_impl() == "interpret"
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    assert ops.default_impl() == "ref"
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "bogus")
    with pytest.raises(ValueError):
        ops.default_impl()
    monkeypatch.delenv("REPRO_KERNEL_IMPL")
    assert ops.default_impl() in ("pallas", "ref")
