"""``repro.dist`` — the distributed execution substrate.

Everything above this package plans in *logical* terms (micro-batches,
instruction streams, logical sharding dims); everything below it is JAX
meshes and collectives. Three modules:

- :mod:`repro.dist.sharding` — logical-axis sharding (``shard``,
  ``spec_for``, ZeRO layouts) over ``jax.sharding.Mesh``.
- :mod:`repro.dist.pipeline` — pipeline execution: the compiled
  ``shard_map``+``ppermute`` device plane and the threaded host plane.
- :mod:`repro.dist.backend` — the :class:`ExecutionBackend` protocol
  unifying both planes behind ``execute_plan`` (``"threads"`` | ``"mesh"``).
- :mod:`repro.dist.fault` — heartbeat/straggler monitoring and elastic
  re-planning over the surviving replica set.
- :mod:`repro.dist.chaos` — deterministic fault injection (seeded,
  replayable fault traces) for the recovery tests and ``bench_elastic``.
- :mod:`repro.dist.cluster` — the process fault domain: one OS process per
  DP replica, socket heartbeats, coordinator election, kill -9 recovery
  (``RunnerConfig.fault_domain="process"``).
"""
from repro.dist import chaos, fault, pipeline, sharding  # noqa: F401


def __getattr__(name):
    # repro.dist.backend imports repro.train.pipeline_adapter, whose model
    # imports land back on repro.dist.sharding — importing it eagerly here
    # would re-enter this package before it finishes initializing. PEP 562
    # lazy attribute access breaks the cycle. cluster is lazy for the same
    # reason (it reaches backend/runner internals at call time).
    if name == "backend":
        import repro.dist.backend as backend
        return backend
    if name == "cluster":
        import repro.dist.cluster as cluster
        return cluster
    raise AttributeError(f"module 'repro.dist' has no attribute {name!r}")
