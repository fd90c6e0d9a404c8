"""The attention kernels compile for a TPU v5e, without a chip.

The interpret-mode suites (test_kernels.py, test_kernel_grads.py) check
what the kernels compute; they cannot see what the chip's compiler
refuses, such as a block whose last two dims are not (8k, 128k) or the
full array dims. These tests compile ``flash_attention`` and
``ragged_attention`` forward and backward for one described v5e chip, at
gpt-paper's d_head 128, with 32 heads (MHA) and with 8 kv heads (GQA),
over the sequence buckets ``chip_smoke.py``'s palette produces plus 544,
a bucket that no multiple of 128 divides.

Only this file describes the chip, in a module fixture: only one process
may load the TPU library, so the call must never run at import.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D_HEAD = 128
HEADS = 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile_fwd_bwd(one_chip, seq, kv_heads, ragged):
    b = max(1, 4096 // seq)
    q = jax.ShapeDtypeStruct((b, seq, HEADS, D_HEAD), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, seq, kv_heads, D_HEAD), jnp.bfloat16,
                              sharding=one_chip)
    ids = jax.ShapeDtypeStruct((b, seq), jnp.int32, sharding=one_chip)

    def loss(q, k, v, seg, pos):
        kw = (dict(q_segment_ids=seg, kv_segment_ids=seg, q_positions=pos,
                   kv_positions=pos) if ragged else {})
        o = ops.attention(q, k, v, impl="pallas", **kw)
        return jnp.sum(o.astype(jnp.float32))

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    return step.lower(q, kv, kv, ids, ids).compile().as_text()


@pytest.mark.parametrize("ragged", [False, True], ids=["flash", "ragged"])
@pytest.mark.parametrize("seq", [128, 256, 512, 544])
def test_attention_compiles_for_v5e_mha(one_chip, seq, ragged):
    text = _compile_fwd_bwd(one_chip, seq, HEADS, ragged)
    # forward, dq and dk/dv are three Mosaic kernels
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("ragged", [False, True], ids=["flash", "ragged"])
@pytest.mark.parametrize("seq", [512, 544])
def test_attention_compiles_for_v5e_gqa(one_chip, seq, ragged):
    text = _compile_fwd_bwd(one_chip, seq, 8, ragged)
    assert text.count("tpu_custom_call") >= 3
