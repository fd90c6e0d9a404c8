"""Correctness of the §Perf hillclimb variants: they may only change
*sharding/scheduling*, never model outputs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.models import layers as L
from repro.models import model as MD
from tests.conftest import run_subprocess_devices

KEY = jax.random.PRNGKey(0)


def test_pad_heads_attention_exact():
    """Zero-padded heads are provably output-identical (EXPERIMENTS §Perf
    cell B): padded q/k rows are zero => uniform softmax over zero v => 0,
    sliced off before W_O."""
    from repro.kernels.ref import attention_ref
    cfg = reduced(get_arch("qwen2.5-32b"))
    b, s, dh = 2, 32, 16
    q = jax.random.normal(KEY, (b, s, cfg.n_heads, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, cfg.n_kv_heads, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, cfg.n_kv_heads, dh))
    orig = L.axis_size
    L.axis_size = lambda d, mesh=None: 3 if d == "tp" else orig(d, mesh)
    try:
        qp, kp, vp, hp = L._pad_heads(q, k, v, cfg)
    finally:
        L.axis_size = orig
    assert hp % 3 == 0 and hp >= cfg.n_heads
    ref = attention_ref(q, k, v, causal=True)
    pad = attention_ref(qp, kp, vp, causal=True)[:, :, :cfg.n_heads]
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pad), atol=1e-6)


def test_pad_heads_loss_unchanged_single_device():
    cfg = reduced(get_arch("qwen2.5-32b"))
    cfg_pad = dataclasses.replace(cfg, pad_heads=True)
    params = MD.init_params(KEY, cfg)
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 3))
    b = {
        "tokens": jax.random.randint(k1, (2, 32), 0, cfg.vocab),
        "labels": jax.random.randint(k2, (2, 32), 0, cfg.vocab),
        "loss_weights": jnp.ones((2, 32), jnp.float32),
        "positions": jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32)[None], (2, 32)),
        "segment_ids": jnp.zeros((2, 32), jnp.int32),
    }
    l0, _ = MD.loss_fn(params, b, cfg)
    l1, _ = MD.loss_fn(params, b, cfg_pad)
    assert abs(float(l0) - float(l1)) < 1e-5


@pytest.mark.slow
def test_pure_dp_mode_loss_equality():
    """pure_dp (model axis as extra DP) must not change the math."""
    out = run_subprocess_devices("""
import dataclasses, jax, jax.numpy as jnp
from repro.configs.base import get_arch, reduced
from repro.dist.sharding import pure_dp, set_mesh
from repro.models import model as MD
cfg = reduced(get_arch("gemma2-2b"))
params = MD.init_params(jax.random.PRNGKey(0), cfg)
k1, k2 = jax.random.split(jax.random.PRNGKey(1))
B, S = 8, 32
b = {"tokens": jax.random.randint(k1,(B,S),0,cfg.vocab),
     "labels": jax.random.randint(k2,(B,S),0,cfg.vocab),
     "loss_weights": jnp.ones((B,S),jnp.float32),
     "positions": jnp.broadcast_to(jnp.arange(S,dtype=jnp.int32)[None],(B,S)),
     "segment_ids": jnp.zeros((B,S),jnp.int32)}
l0, _ = MD.loss_fn(params, b, cfg)
mesh = jax.make_mesh((2,4), ("data","model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
with set_mesh(mesh), pure_dp(True):
    l1, _ = jax.jit(lambda p, b: MD.loss_fn(p, b, cfg))(params, b)
err = abs(float(l0) - float(l1))
assert err < 2e-3, (float(l0), float(l1))
print("PURE_DP_OK")
""")
    assert "PURE_DP_OK" in out


def test_remat_policy_loss_unchanged():
    cfg = reduced(get_arch("starcoder2-7b"))
    params = MD.init_params(KEY, cfg)
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 4))
    b = {
        "tokens": jax.random.randint(k1, (2, 32), 0, cfg.vocab),
        "labels": jax.random.randint(k2, (2, 32), 0, cfg.vocab),
        "loss_weights": jnp.ones((2, 32), jnp.float32),
        "positions": jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32)[None], (2, 32)),
        "segment_ids": jnp.zeros((2, 32), jnp.int32),
    }
    losses = []
    for pol in ("nothing", "dots", "everything"):
        cfg_p = dataclasses.replace(cfg, remat_policy=pol)
        (l, _), g = jax.value_and_grad(
            lambda p, cfg_p=cfg_p: MD.loss_fn(p, b, cfg_p),
            has_aux=True)(params)
        losses.append(float(l))
        assert np.isfinite(float(l))
    assert max(losses) - min(losses) < 1e-5
