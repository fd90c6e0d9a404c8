"""DynaPipe reproduction: dynamic micro-batching + adaptive pipelines on JAX.

Package layout (see docs/architecture.md for the data-flow walkthrough):

- ``repro.core``    — pure-CPU planning: micro-batch construction, cost
                      models, schedules, instruction streams, comm planning.
- ``repro.dist``    — the distributed execution substrate: logical-axis
                      sharding, compiled pipeline execution, fault tolerance.
- ``repro.models``  — pure-JAX model zoo (transformer / mamba / MoE stacks).
- ``repro.train``   — optimizer, train state, checkpointing, pipeline
                      adapter, planner-driven training loop.
- ``repro.launch``  — mesh factories and the multi-pod compile dry-run.
- ``repro.kernels`` — Pallas kernels + jnp reference implementations.

The public surface re-exports lazily (PEP 562) so ``import repro`` stays
cheap and the submodule import graph keeps its layering::

    from repro import PlanAheadRunner, RunnerConfig, make_backend
"""
# public name -> defining module; resolved on first attribute access
_PUBLIC = {
    # execution backends (the ExecutionBackend protocol, ISSUE 8)
    "ExecutionBackend": "repro.dist.backend",
    "ThreadsBackend": "repro.dist.backend",
    "MeshBackend": "repro.dist.backend",
    "BackendResult": "repro.dist.backend",
    "make_backend": "repro.dist.backend",
    "make_stage_mesh": "repro.launch.mesh",
    # planning
    "PlannerConfig": "repro.core.planner",
    "plan_iteration": "repro.core.planner",
    "ExecutionPlan": "repro.core.instructions",
    "ShapePalette": "repro.core.microbatch",
    "AnalyticCostModel": "repro.core.cost_model",
    # training runtime
    "PlanAheadRunner": "repro.train.runner",
    "RunnerConfig": "repro.train.runner",
    "CompiledStepCache": "repro.train.step_cache",
    "AdamWConfig": "repro.train.optimizer",
    # data
    "MultiTaskStream": "repro.data.streams",
    "StreamConfig": "repro.data.streams",
    # model zoo
    "get_arch": "repro.configs.base",
    "reduced": "repro.configs.base",
}

__all__ = sorted(_PUBLIC)


def __getattr__(name):
    mod = _PUBLIC.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro' has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(mod), name)
