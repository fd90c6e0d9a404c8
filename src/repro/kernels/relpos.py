"""T5's bucketed relative position bias (Raffel et al. 2020; Mesh TF's
``_relative_position_bucket``).

The bias of a (query, key) pair is ``table[head, bucket(rel)]`` with
``rel = key position − query position``. Bidirectional (encoder): half the
buckets per sign, ``rel > 0`` in the upper half; distances below a quarter
of the buckets are exact, then log-spaced up to ``max_distance``, and
everything farther shares the last bucket of its sign. Causal (decoder):
all buckets over ``max(−rel, 0)``, exact below half of them.

Mesh TF computes the log-spaced buckets in float32, where the exact
boundaries (distance 16, 32 and 64 bidirectionally) hang on the last bit of
a logarithm. Here each boundary is the least integer distance the exact
real formula puts into the next bucket, found with integers once, and a
bucket is the number of boundaries at or below the distance: the same code
runs in the Pallas kernels and in ``jnp``, and gives Mesh TF's float32
buckets wherever a float32 logarithm rounds correctly.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp


@functools.lru_cache(maxsize=None)
def boundaries(n_buckets: int, max_distance: int,
               bidirectional: bool) -> tuple[int, ...]:
    """Distances at which the bucket of one sign steps up by one."""
    per_sign = n_buckets // 2 if bidirectional else n_buckets
    exact = per_sign // 2
    steps = per_sign - exact
    out = list(range(1, exact + 1))
    for k in range(1, steps):
        # least n with exact + floor(log(n / exact) / log(max_distance /
        # exact) * steps) >= exact + k, i.e. n**steps * exact**k >=
        # exact**steps * max_distance**k
        n = out[-1]
        while n ** steps * exact ** k < exact ** steps * max_distance ** k:
            n += 1
        out.append(n)
    return tuple(out)


def bucket(rel, *, n_buckets: int, max_distance: int, bidirectional: bool):
    """Bucket of each relative position ``rel`` (int32, any shape)."""
    if bidirectional:
        b = jnp.where(rel > 0, n_buckets // 2, 0).astype(jnp.int32)
        n = jnp.abs(rel)
    else:
        b = jnp.zeros(rel.shape, jnp.int32)
        n = jnp.maximum(-rel, 0)
    for t in boundaries(n_buckets, max_distance, bidirectional):
        b = b + (n >= t).astype(jnp.int32)
    return b


def bias(table, q_positions, kv_positions, *, max_distance: int,
         bidirectional: bool):
    """The (B, H, T, S) float32 bias of ``table`` (H, n_buckets): the
    ``jnp`` oracle's; the kernels never hold it."""
    rel = kv_positions[:, None, :] - q_positions[:, :, None]
    b = bucket(rel.astype(jnp.int32), n_buckets=table.shape[-1],
               max_distance=max_distance, bidirectional=bidirectional)
    return jnp.moveaxis(table.astype(jnp.float32)[:, b], 0, 1)
