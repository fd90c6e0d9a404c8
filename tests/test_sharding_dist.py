"""Distribution-layer tests. Multi-device cases run in subprocesses with
their own XLA_FLAGS (the test process itself stays single-device)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_arch, reduced
from repro.dist.sharding import (ambient_mesh, axis_size, set_mesh, shard,
                                 spec_for, spec_for_zero, zero1_logical)
from repro.models import layers as L
from tests.conftest import run_subprocess_devices


def test_spec_for_no_mesh_is_noop():
    assert spec_for((16, 16), ("dp", "tp")) == P()


def test_zero1_logical_no_mesh():
    assert zero1_logical((None, "tp"), (64, 64)) == (None, "tp")


def test_specs_collapse_on_one_device_mesh():
    """On a 1-device mesh every spec must collapse to fully-replicated,
    constraints must be identity, and values must round-trip through them
    unchanged."""
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = np.arange(32, dtype=np.float32).reshape(4, 8)
    with set_mesh(mesh):
        assert ambient_mesh() is mesh
        assert axis_size("dp") == 1 and axis_size("tp") == 1
        # a size-1 axis shards nothing; the mesh has no model axis at all
        assert spec_for((4, 8), ("dp", "tp")) == P()
        zlg = zero1_logical((None, "tp"), (64, 64), mesh)
        assert spec_for_zero((64, 64), zlg, mesh) == P()
        y = jax.jit(lambda a: shard(jnp.asarray(a), "dp", "tp") * 1.0)(x)
    np.testing.assert_array_equal(np.asarray(y), x)
    assert ambient_mesh() is None


@pytest.mark.parametrize("n_heads,tp,even", [
    (6, 1, True),       # no model axis: every head count is even
    (8, 4, True),       # heads divide the axis: head-parallel attention
    (6, 4, False),      # they do not: sequence-parallel attention
], ids=["axis-1", "divides", "uneven"])
def test_heads_even_rule(monkeypatch, n_heads, tp, even):
    cfg = dataclasses.replace(reduced(get_arch("qwen2.5-32b")),
                              n_heads=n_heads, n_kv_heads=2)
    orig = L.axis_size
    monkeypatch.setattr(
        L, "axis_size", lambda d, mesh=None: tp if d == "tp" else orig(d, mesh))
    assert L.heads_even(cfg) is even


@pytest.mark.slow
@pytest.mark.parametrize("arch,overrides", [
    ("qwen2.5-32b", {}),
    ("gemma2-2b", {}),
    # 6 heads on a 4-way model axis: the sequence-parallel attention path
    ("qwen2.5-32b", {"n_heads": 6, "n_kv_heads": 2}),
], ids=["qwen2.5-32b", "gemma2-2b", "uneven-heads"])
def test_sharded_loss_equals_unsharded(arch, overrides):
    """jit'd loss under a (2,4) mesh == single-device loss (GSPMD math)."""
    out = run_subprocess_devices(f"ARCH, OVERRIDES = {arch!r}, {overrides!r}" + """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_arch, reduced
from repro.models import model as MD
cfg = dataclasses.replace(reduced(get_arch(ARCH)), **OVERRIDES)
key = jax.random.PRNGKey(0)
params = MD.init_params(key, cfg)
B, S = 4, 32
k1, k2 = jax.random.split(key)
batch = {
  "tokens": jax.random.randint(k1, (B,S), 0, cfg.vocab),
  "labels": jax.random.randint(k2, (B,S), 0, cfg.vocab),
  "loss_weights": jnp.ones((B,S), jnp.float32),
  "positions": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B,S)),
  "segment_ids": jnp.zeros((B,S), jnp.int32),
}
loss0, _ = MD.loss_fn(params, batch, cfg)
from repro.dist.sharding import set_mesh
mesh = jax.make_mesh((2,4), ("data","model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
with set_mesh(mesh):
    loss1, _ = jax.jit(lambda p, b: MD.loss_fn(p, b, cfg))(params, batch)
err = abs(float(loss0) - float(loss1))
assert err < 2e-3, (float(loss0), float(loss1))
print("SHARDED_OK", float(loss0), float(loss1))
""")
    assert "SHARDED_OK" in out


@pytest.mark.slow
def test_moe_shardmap_matches_global():
    out = run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs.base import get_arch, reduced
from repro.models import layers as L
cfg = dataclasses.replace(reduced(get_arch("llama4-scout-17b-a16e")),
                          capacity_factor=8.0)
key = jax.random.PRNGKey(0)
p = L.init_moe(key, cfg)
x = jax.random.normal(jax.random.fold_in(key,1), (4, 16, cfg.d_model), jnp.float32)
y_ref, _ = L.moe_fwd(p, x, cfg)
from repro.dist.sharding import set_mesh
mesh = jax.make_mesh((2, 4), ("data","model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
with set_mesh(mesh):
    y_sm, _ = jax.jit(lambda p, x: L.moe_fwd(p, x, cfg))(p, x)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sm), atol=2e-5, rtol=2e-5)
print("MOE_OK")
""")
    assert "MOE_OK" in out


@pytest.mark.slow
def test_compiled_ppermute_pipeline():
    """dist/pipeline: 2-stage shard_map+ppermute == sequential application."""
    out = run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.dist.pipeline import pipelined_apply
mesh = jax.make_mesh((2,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
n_stages, n_micro, mb, d = 2, 4, 2, 8
key = jax.random.PRNGKey(0)
params = jax.random.normal(key, (n_stages, d, d)) * 0.3
xs = jax.random.normal(jax.random.fold_in(key, 1), (n_micro, mb, d))
def stage_fn(w, h, stage):
    return jnp.tanh(h @ w)
out = pipelined_apply(stage_fn, params, xs, mesh=mesh, n_stages=n_stages)
# reference: sequential
ref = xs
for s in range(n_stages):
    ref = jnp.tanh(jnp.einsum("nbd,de->nbe", ref, params[s]))
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
print("PIPE_OK")
""", n_devices=2)
    assert "PIPE_OK" in out

