"""Mesh construction.

Functions, not module-level constants — importing this module never touches
JAX device state.
"""
from __future__ import annotations

import jax


def make_stage_mesh(n_stages: int, *, axis: str = "stage"):
    """1-D pipeline-stage mesh over the first ``n_stages`` devices.

    The axis name must be one of ``repro.dist.sharding._STAGE_AXES`` so the
    ZeRO-1 ``"zero"`` logical dim resolves onto it. Built directly from the
    device list (not ``jax.make_mesh``) so a 4-stage mesh works on an
    8-device host platform without consuming the rest.
    """
    import numpy as np
    from jax.sharding import Mesh
    devs = jax.devices()
    if n_stages > len(devs):
        raise ValueError(
            f"need {n_stages} devices for {n_stages} pipeline stages, "
            f"have {len(devs)} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_stages} on CPU)")
    return Mesh(np.asarray(devs[:n_stages]), (axis,))
