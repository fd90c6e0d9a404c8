"""Named host spans of a training step (``dynapipe.*`` profiler events).

Each span is a ``jax.profiler.TraceAnnotation``: when a profiler runs it
lands in the same trace as the device's ops, on one clock, with its keyword
args as event stats; when none runs it costs about half a microsecond.
There is no switch. Args are host values already in hand: no span reads a
device value, so none adds a sync.

Main (runner) thread, inside one ``dynapipe.iteration`` per loop pass:
``submit``, ``plan_wait``, ``materialize``, ``stage_setup`` (with a
``compile`` per stage program compiled there, with args ``stage``,
``kind`` (``fwd``, ``bwd`` or the last stage's ``fwd_bwd``), ``shape`` and
``remat``, 1 where the program checkpoints each period), ``pipeline``,
``grad_merge``, ``optimizer`` (dispatch) and ``step_sync``; ``pipeline`` is
``PipelineExecutor.run``, or the sequential path's micro-batch loop (with
its own ``device_put`` and ``loss_sync``). Stage compute threads:
``stage{j}.fwd`` / ``stage{j}.bwd`` (with ``device_put`` in stage 0's
forward and ``loss_sync`` in the last stage's) and ``recv_wait``. Planner
threads: ``plan``.
"""
from __future__ import annotations

ITERATION = "dynapipe.iteration"
SUBMIT = "dynapipe.submit"
PLAN_WAIT = "dynapipe.plan_wait"
MATERIALIZE = "dynapipe.materialize"
STAGE_SETUP = "dynapipe.stage_setup"
COMPILE = "dynapipe.compile"
PIPELINE = "dynapipe.pipeline"
GRAD_MERGE = "dynapipe.grad_merge"
OPTIMIZER = "dynapipe.optimizer"
STEP_SYNC = "dynapipe.step_sync"
DEVICE_PUT = "dynapipe.device_put"
LOSS_SYNC = "dynapipe.loss_sync"
RECV_WAIT = "dynapipe.recv_wait"
PLAN = "dynapipe.plan"

_annotation = None


def stage(j: int, kind: str) -> str:
    """``dynapipe.stage{j}.fwd`` or ``.bwd``."""
    return f"dynapipe.stage{j}.{kind}"


def span(name: str, **args):
    """A context manager for the span ``name``; ``set_metadata(**args)`` on
    it adds args known only once the span is open. jax is imported on the
    first call, so importing this module (and ``repro.core``) stays free of
    it."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name, **args)
