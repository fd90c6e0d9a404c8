"""T5's relative position bias inside the Pallas attention kernels
(interpret mode) against a plain ``jnp`` float32 oracle: the forward and
the gradients of q, k, v and the (heads, buckets) table, bidirectional and
causal buckets, sequences that the tile does and does not divide, several
tiles past the 128-token far bucket, padded rows with segment ids, GQA.
And the bias never exists as a (…, T_q, T_k) array: the kernels build it a
tile at a time."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

KEY = jax.random.PRNGKey(11)
# float32 throughout: the kernels and the oracle differ by the order of
# float32 sums alone (online softmax across tiles, the table's gradient
# summed per diagonal then per bucket); 1e-5 of each result's largest
# entry is five times the largest gap read over these cases (2.1e-6)
TOL = 1e-5


def _oracle_bucket(rel, bidirectional, num_buckets=32, max_distance=128):
    """Mesh TF's ``_relative_position_bucket`` (rel = key − query)."""
    ret = 0
    n = -rel
    if bidirectional:
        num_buckets //= 2
        ret += (n < 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = jnp.maximum(n, 0)
    max_exact = num_buckets // 2
    large = max_exact + (jnp.log(n.astype(jnp.float32) / max_exact)
                         / math.log(max_distance / max_exact)
                         * (num_buckets - max_exact)).astype(jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return ret + jnp.where(n < max_exact, n, large)


def _oracle(q, k, v, table, qpos, kpos, qseg, kseg, causal, sm_scale):
    """Materialized scores with the bias from positions; fully masked rows
    give zeros."""
    h, kvh = q.shape[2], k.shape[2]
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    rel = kpos[:, None, :] - qpos[:, :, None]
    bias = jnp.moveaxis(table[:, _oracle_bucket(rel, not causal)], 0, 1)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * sm_scale + bias
    mask = (qseg[:, :, None] == kseg[:, None, :]) & (kseg[:, None, :] >= 0)
    if causal:
        mask &= rel <= 0
    s = jnp.where(mask[:, None], s, -1e30)
    e = jnp.where(mask[:, None], jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def _rows(b, t, pad, packed):
    """Segment ids and positions: each row right-padded by ``pad * row``
    tokens (id -1, position 0); ``packed`` rows hold two samples whose
    positions restart."""
    seg = np.zeros((b, t), np.int32)
    pos = np.zeros((b, t), np.int32)
    for r in range(b):
        n = t - pad * r
        if packed:
            cut = n // 3
            seg[r, cut:n] = 1
            pos[r, :cut] = np.arange(cut)
            pos[r, cut:n] = np.arange(n - cut)
        else:
            pos[r, :n] = np.arange(n)
        seg[r, n:] = -1
    return jnp.asarray(seg), jnp.asarray(pos)


CASES = {
    # name: (b, t, h, kv, block, pad, packed)
    "one_tile": (2, 128, 2, 2, 128, 0, False),
    "tiles_past_far_bucket": (1, 384, 2, 2, 128, 0, False),
    "tile_does_not_divide": (2, 200, 2, 2, 128, 0, False),
    "padded_rows": (3, 256, 2, 2, 128, 53, False),
    "packed_segments": (2, 256, 2, 2, 128, 40, True),
    "gqa": (1, 256, 4, 2, 128, 0, False),
}


@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_biased_kernels_match_oracle(case, causal):
    b, t, h, kv, block, pad, packed = CASES[case]
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, t, h, 32))
    k = jax.random.normal(ks[1], (b, t, kv, 32))
    v = jax.random.normal(ks[2], (b, t, kv, 32))
    ct = jax.random.normal(ks[3], (b, t, h, 32))
    table = jax.random.normal(ks[4], (h, 32))
    seg, pos = _rows(b, t, pad, packed)
    sm_scale = 0.3

    def kernel(q, k, v, table):
        o = ops.attention(q, k, v, causal=causal, q_segment_ids=seg,
                          kv_segment_ids=seg, q_positions=pos,
                          kv_positions=pos, sm_scale=sm_scale,
                          rel_bias=table, block_q=block, block_kv=block,
                          impl="interpret")
        return jnp.sum(o * ct), o

    def oracle(q, k, v, table):
        o = _oracle(q, k, v, table, pos, pos, seg, seg, causal, sm_scale)
        return jnp.sum(o * ct), o

    got = jax.value_and_grad(kernel, argnums=(0, 1, 2, 3), has_aux=True)
    want = jax.value_and_grad(oracle, argnums=(0, 1, 2, 3), has_aux=True)
    (_, o_got), g_got = got(q, k, v, table)
    (_, o_want), g_want = want(q, k, v, table)
    names = ("out", "dq", "dk", "dv", "dtable")
    for name, a, w in zip(names, (o_got,) + g_got, (o_want,) + g_want):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(a), w, rtol=0,
                                   atol=TOL * np.abs(w).max(),
                                   err_msg=f"{name} ({case}, causal={causal})")


def _pallas_operands(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params.get("name"),
                          [tuple(v.aval.shape) for v in eqn.invars]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_operands(sub, found)


def test_no_score_sized_bias_reaches_the_kernels():
    """With T = 384 (no other dim of that size), no operand of the three
    biased Pallas calls, and no value anywhere in the jaxpr of the forward
    and backward, has two dims of length T: the (…, T_q, T_k) bias is
    never built."""
    b, t, h, d = 1, 384, 2, 128
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, t, h, d))
    table = jax.random.normal(ks[1], (h, 32))

    def f(q, table):
        o = ops.attention(q, q, q, causal=False, sm_scale=1.0, rel_bias=table,
                          block_q=128, block_kv=128, impl="interpret")
        return jnp.sum(o)

    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(q, table)
    calls = []
    _pallas_operands(jaxpr.jaxpr, calls)
    names = sorted(str(n).split(" ")[0] for n, _ in calls)
    assert names == ["flash_dkv_relbias", "flash_dq_relbias",
                     "flash_fwd_relbias"], names
    for _, shapes in calls:
        assert all(list(s).count(t) <= 1 for s in shapes), shapes

    def walk(jx):
        for eqn in jx.eqns:
            for var in list(eqn.invars) + list(eqn.outvars):
                shape = tuple(getattr(getattr(var, "aval", None), "shape", ()))
                assert list(shape).count(t) <= 1, (eqn.primitive.name, shape)
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
    walk(jaxpr.jaxpr)


@pytest.mark.parametrize("ragged", [False, True], ids=["flash", "ragged"])
def test_backward_uses_the_forward_scale(ragged):
    """A scale other than 1/sqrt(d), without a table: the backward passes
    get the forward's scale (they no longer compute their own)."""
    b, t, h, d = 2, 128, 2, 32
    ks = jax.random.split(KEY, 4)
    q, k, v, ct = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)
    seg, pos = _rows(b, t, 21, False)
    kw = dict(q_segment_ids=seg, kv_segment_ids=seg) if ragged else {}

    def loss(impl):
        def f(q, k, v):
            o = ops.attention(q, k, v, causal=True, sm_scale=1.0, impl=impl,
                              **kw)
            return jnp.sum(o * ct)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for a, w in zip(loss("interpret"), loss("ref")):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(a), w, rtol=0,
                                   atol=TOL * np.abs(w).max())
