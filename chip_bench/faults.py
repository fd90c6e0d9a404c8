"""Faults planted underneath the timed path, to show that ``correct``
catches them. Each is a context manager that patches the program in this
process for the duration of one run; none is used by the benchmark's own
runs.

- ``state_unchanged``: the optimizer step returns params and state as it
  got them;
- ``half_batch``: every sample of odd index is left out (its loss weights
  zeroed where its micro-batch is materialized), so the mean is taken over
  the rest;
- ``no_stage_exchange``: the gradient sent back from the last stage to the
  first is dropped (stage 0 gets zeros).
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(owner, name, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


@contextlib.contextmanager
def state_unchanged():
    from repro.dist.backend import ThreadsBackend
    from repro.train.optimizer import global_norm

    def optimizer_step(self, params, grads, opt_state, opt_cfg,
                       grad_scale=1.0):
        return params, opt_state, {"grad_norm": global_norm(grads)}
    with _patched(ThreadsBackend, "optimizer_step", optimizer_step):
        yield


@contextlib.contextmanager
def half_batch():
    import repro.train.runner as runner_mod
    materialize = runner_mod.materialize_micro_batch

    def half(spec, tokens, **kw):
        b = materialize(spec, tokens, **kw)
        odd = [row for row, i in enumerate(spec.sample_indices) if i % 2]
        b["loss_weights"][odd] = 0.0
        return b
    with _patched(runner_mod, "materialize_micro_batch", half):
        yield


@contextlib.contextmanager
def no_stage_exchange():
    import jax.numpy as jnp
    from repro.core.executor import StageCallbacks
    from repro.train.pipeline_adapter import PipelinedModel
    make = PipelinedModel.make_callbacks

    def make_callbacks(self, plan, batches, on_step=None):
        cbs, result = make(self, plan, batches, on_step=on_step)
        first = cbs[0]

        def backward(mb, g):
            return first.backward(mb, jnp.zeros_like(g))
        cbs[0] = StageCallbacks(first.forward, backward, first.step)
        return cbs, result
    with _patched(PipelinedModel, "make_callbacks", make_callbacks):
        yield


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_stage_exchange": no_stage_exchange}
