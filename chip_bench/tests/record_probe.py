"""Records ``data/probe.xplane.pb``, the small trace ``test_trace.py`` reads.

    PYTHONPATH=src python3 -m chip_bench.tests.record_probe <out_dir>  # TPU

Three iterations of a matmul program and the Pallas attention kernel
forward and backward under ``bench.step`` spans, each followed by a host
sleep under a ``bench.host_sleep`` span; the trace lands under
``<out_dir>/plugins/profile/``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    from repro.kernels import ops

    @jax.jit
    def mm(a, b):
        return jnp.tanh(a @ b) @ b

    def attn(q, k, v, seg):
        return ops.attention(q, k, v, causal=True, q_segment_ids=seg,
                             kv_segment_ids=seg, impl="pallas")

    attn_j = jax.jit(attn)
    grad = jax.jit(jax.grad(lambda q, k, v, seg: jnp.sum(
        attn(q, k, v, seg).astype(jnp.float32)), argnums=(0, 1, 2)))
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    q = jnp.ones((2, 512, 4, 128), jnp.bfloat16) * 0.1
    seg = jnp.zeros((2, 512), jnp.int32)
    jax.block_until_ready((mm(a, b), attn_j(q, q, q, seg),
                           grad(q, q, q, seg)))
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            r, o, g = mm(a, b), attn_j(q, q, q, seg), grad(q, q, q, seg)
        with jax.profiler.TraceAnnotation("bench.host_sleep"):
            time.sleep(0.01)
            jax.block_until_ready((r, o, g))
            time.sleep(0.005)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
