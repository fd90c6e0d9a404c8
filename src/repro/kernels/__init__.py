"""Pallas compute kernels (attention + SSD) with pure-jnp oracles.

``ops`` is the dispatch layer (impl in {"pallas", "interpret", "ref"},
default from :func:`default_impl`, overridable via the
``REPRO_KERNEL_IMPL`` environment variable). The attention kernels train
through fused custom-VJP backward passes; ``ref`` stays the ground-truth
oracle.
"""
from repro.kernels.ops import (  # noqa: F401
    attention,
    default_impl,
    ssd,
    ssd_decode,
)
