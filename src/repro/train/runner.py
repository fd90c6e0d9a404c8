"""Plan-ahead runtime: double-buffered planning over deterministic streams.

This is the layer that turns the fast planner (core/planner.py, PR 2) and the
execution substrate into the system the paper describes (§3, §8.5): while
iteration *k* executes, the ``PlannerPool`` is already running iteration
*k+1*'s dp_split -> adaptive schedule -> comm plan -> instruction lowering,
so planning cost never lands on the critical path. Concretely:

- **Streams, not arrays.** The runner consumes any object with
  ``batch(k) -> GlobalBatch`` (see data/streams.py). Because
  ``MultiTaskStream.batch`` is a pure function of ``(config, k)``, the only
  thing a plan-ahead submission needs is the *lengths* of batch k+j — the
  runner samples them locally and ships them to the pool (threads by
  default; ``use_processes=True`` for true CPU parallelism).
- **Double buffering.** ``lookahead`` iterations are kept in flight: plan
  k+1..k+lookahead are pending while k executes. ``plan_wait_s`` records the
  time the main loop actually blocked on a plan; together with the
  worker-measured ``planning_seconds`` it yields the *overlap fraction* —
  the share of planning work hidden behind execution.
- **Compiled-step cache.** All jitted step functions (the sequential grad
  step and every pipeline stage's fwd/bwd) live in one
  ``CompiledStepCache`` keyed by bucketed ``(mbs, seq)`` shapes, so the
  ``ShapePalette`` bound on distinct shapes is also a bound on XLA
  recompiles — measurable as the cache hit rate.
- **Synchronous fallback.** ``synchronous=True`` plans inline on the main
  thread (no pool). Both paths execute identical plans over identical
  batches with the same cached step functions, so losses are bit-identical
  — tests/test_plan_ahead.py asserts it.

Fault tolerance (ISSUE 7): the run loop survives the four fault classes in
:mod:`repro.dist.chaos` end-to-end. A failed iteration (structured
``PipelineError`` from the executor, or an injected fault on the sequential
path) is retried up to ``max_retries`` times with backoff: in-flight plans
are drained, the remaining stream is replanned, and when the fault lost
device state (``state_lost``) params/opt are restored from the newest valid
checkpoint and the stream replayed from that step — deterministic streams
make the replayed trajectory bit-equal to the fault-free one. Planner-future
timeouts/crashes resubmit instead of raising; a dead replica (missed
heartbeats) triggers an :class:`ElasticPlanManager` sweep that shrinks
``dp_size`` to the survivors and re-splits every subsequent batch over them;
all replicas' plans execute each iteration and their grads merge, so the
full-batch gradient — and thus the loss trajectory — is preserved across
topology changes. If retries are exhausted the runner writes a final
emergency checkpoint before re-raising. With ``calibrate=True`` measured
per-stage fwd/bwd timings feed an :class:`OnlineCalibrator` so the cost
model's learned scales track the real machine.
"""
from __future__ import annotations

import concurrent.futures as cf
import contextlib
import dataclasses
import time
import warnings
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.cost_model import CostModel, OnlineCalibrator
from repro.core.executor import PipelineError
from repro.core.instructions import ExecutionPlan, InstructionStore
from repro.core.planner import PlannerConfig, PlannerPool, plan_iteration
from repro.data.dataset import materialize_micro_batch
from repro.data.streams import GlobalBatch
from repro.dist.backend import ExecutionBackend, make_backend
from repro.dist.chaos import FaultSchedule, InjectedFault, LogicalClock
from repro.dist.fault import (ElasticPlanManager, StragglerMonitor,
                              make_planner_replan)
from repro.models import model as MD
from repro.models import transformer as T
from repro.train import checkpoint as CKPT
from repro.train.optimizer import AdamWConfig, init_opt_state
# Re-exported for backwards compatibility: these moved to
# train/pipeline_adapter.py so dist/backend.py can import them without a
# train.runner <-> dist.backend cycle. bench_e2e and older tests import
# them from here.
from repro.train.pipeline_adapter import (build_encdec_grad_step,  # noqa: F401
                                          build_grad_step,
                                          model_cache_namespace)
from repro.train.step_cache import CompiledStepCache


@dataclass
class RunnerConfig:
    """The one canonical run configuration (train/loop.py's ``LoopConfig``
    is a deprecated alias that forwards here)."""
    n_iters: int = 50
    backend: str = "threads"         # execution plane: "threads" | "mesh"
                                     # (see repro.dist.backend)
    lookahead: int = 1               # plans kept in flight ahead of execution
    synchronous: bool = False        # plan inline (fallback / bitwise oracle)
    use_processes: bool = False      # PlannerPool backend (see core/planner.py)
    use_executor: bool = True        # threaded pipeline vs sequential accum
    global_tokens: int = 4096        # tokens per global batch (loop entry)
    log_every: int = 10
    ckpt_every: int = 0              # 0 = off
    ckpt_dir: str = ""
    seed: int = 0
    plan_timeout: float = 300.0
    impl: Optional[str] = None       # kernel impl for every fwd/bwd step
                                     # (None = kernels.default_impl(), which
                                     # honours REPRO_KERNEL_IMPL)
    # ------------------------ fault tolerance --------------------------
    max_retries: int = 2             # per-iteration retry budget on faults
    retry_backoff_s: float = 0.05    # base backoff between retries
    drift_tolerance: float = 1.2     # apply measured speed factors to plans
                                     # only past this slowest/fastest ratio —
                                     # below it, measurement noise would
                                     # destroy plan determinism for nothing
    calibrate: bool = False          # online cost-model calibration
    exec_timeout: float = 120.0      # per-channel executor timeout
    strict_verify: bool = False      # backends statically verify each plan
                                     # (repro.analysis) and refuse ERROR-
                                     # level ones before executing; pair
                                     # with PlannerConfig.verify_plans to
                                     # also fail at plan time, off the
                                     # critical path in the planner pool
    fault_domain: str = "thread"     # "thread": faults are in-process
                                     # simulations (chaos hooks); "process":
                                     # one OS process per DP replica with
                                     # socket heartbeats, coordinator
                                     # election, and real SIGKILL injection
                                     # (repro.dist.cluster)


class DatasetStream:
    """Adapter: stateful ``MultiTaskDataset`` -> the stream protocol.

    Batches are generated in ascending iteration order on first request (the
    dataset consumes its RNG sequentially) and cached, so plan-ahead
    requests for k+1 before k executes — and repeated requests for the same
    k — are consistent. Unlike ``MultiTaskStream`` this is *not*
    regenerable across processes; it exists for API compatibility with the
    original ``train/loop.py`` entry point.
    """

    def __init__(self, dataset, samples_per_batch: int, vocab: int):
        self.dataset = dataset
        self.samples_per_batch = samples_per_batch
        self.vocab = vocab
        self._cache: dict[int, GlobalBatch] = {}
        self._next = 0
        self._min_live = 0

    def batch(self, iteration: int) -> GlobalBatch:
        if iteration < self._min_live:
            raise ValueError(
                f"batch {iteration} was evicted (oldest live: "
                f"{self._min_live}); DatasetStream hands out each batch "
                "once, in ascending order — use MultiTaskStream for "
                "random access")
        while self._next <= iteration:
            lengths, tokens, tids = self.dataset.sample_minibatch(
                self.samples_per_batch, self.vocab)
            self._cache[self._next] = GlobalBatch(
                iteration=self._next, lengths=lengths,
                task_ids=np.asarray(tids, dtype=np.int64), tokens=tokens)
            self._next += 1
        gb = self._cache[iteration]
        # requests arrive in ascending order (the runner holds its own
        # reference in _pending), so older entries are dead — evict them
        # to keep memory flat over long runs
        for it in [i for i in self._cache if i < iteration]:
            del self._cache[it]
        self._min_live = iteration
        return gb


@dataclass
class RunnerStats:
    iters: int = 0
    planning_s: float = 0.0          # total planner CPU seconds (workers)
    plan_wait_s: float = 0.0         # total main-loop seconds blocked on plans
    exec_s: float = 0.0              # total iteration wall seconds
    real_tokens: int = 0
    padded_tokens: int = 0
    overlap_planning_s: float = 0.0  # planning_s over overlappable iters (>1st)
    overlap_wait_s: float = 0.0      # plan_wait_s over the same iters
    cache: dict = field(default_factory=dict)
    mode: str = "plan-ahead"
    # ------------------------ fault tolerance --------------------------
    faults: int = 0                  # faults observed (exec + planner)
    recovery_s: float = 0.0          # wall seconds spent in recovery paths
    recoveries: list = field(default_factory=list)   # event dicts
    calibration: dict = field(default_factory=dict)  # OnlineCalibrator summary
    cluster: dict = field(default_factory=dict)      # process fault domain:
                                                     # kills/elections/orphans
                                                     # (repro.dist.cluster)

    @property
    def overlap_fraction(self) -> float:
        """Share of planning work hidden behind execution (first iteration
        excluded — there is nothing to overlap the primed plan with)."""
        if self.overlap_planning_s <= 0:
            return 0.0
        hidden = self.overlap_planning_s - self.overlap_wait_s
        return max(0.0, min(1.0, hidden / self.overlap_planning_s))

    def to_dict(self) -> dict:
        return {
            "mode": self.mode,
            "iters": self.iters,
            "planning_s": round(self.planning_s, 4),
            "plan_wait_s": round(self.plan_wait_s, 4),
            "exec_s": round(self.exec_s, 4),
            "real_tokens": self.real_tokens,
            "padded_tokens": self.padded_tokens,
            "overlap_fraction": round(self.overlap_fraction, 4),
            "cache": dict(self.cache),
            "faults": self.faults,
            "n_recoveries": len(self.recoveries),
            "recovery_s": round(self.recovery_s, 4),
            "recoveries": list(self.recoveries),
            "calibration": dict(self.calibration),
            "cluster": dict(self.cluster),
        }


def _injected_event(err: BaseException):
    """Walk the cause chain for an InjectedFault; returns its FaultEvent."""
    seen = set()
    e: Optional[BaseException] = err
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        if isinstance(e, InjectedFault):
            return e.event
        e = e.__cause__ or e.__context__
    return None


class PlanAheadRunner:
    """Drives training with planning double-buffered ahead of execution."""

    def __init__(self, cfg: ArchConfig, cost: CostModel, pcfg: PlannerConfig,
                 rcfg: RunnerConfig, stream,
                 opt_cfg: Optional[AdamWConfig] = None,
                 monitor: Optional[StragglerMonitor] = None,
                 step_cache: Optional[CompiledStepCache] = None,
                 chaos: Optional[FaultSchedule] = None, mesh=None):
        self.cfg = cfg
        self.cost = cost
        self.pcfg = pcfg
        self.rcfg = rcfg
        self.stream = stream
        self.mesh = mesh                 # stage mesh for backend="mesh"
        self.backend: Optional[ExecutionBackend] = None  # built in run()
        self.opt_cfg = opt_cfg if opt_cfg is not None else AdamWConfig(lr=3e-4)
        self.monitor = monitor
        self.chaos = chaos
        self.step_cache = step_cache if step_cache is not None \
            else CompiledStepCache()
        self.store = InstructionStore()
        self.pool: Optional[PlannerPool] = None
        self._pending: dict[int, GlobalBatch] = {}
        self._futures: dict = {}
        # positions in the alive list <-> original replica ids; shrinks on
        # replica death (ElasticPlanManager sweep)
        self._alive: list[int] = list(range(max(1, pcfg.dp_size)))
        self.elastic = (ElasticPlanManager(monitor,
                                           make_planner_replan(cost, pcfg))
                        if monitor is not None else None)
        self._calibrator = (OnlineCalibrator(cost)
                            if rcfg.calibrate else None)
        self._end = 0

    # ------------------------- planning side ---------------------------
    @staticmethod
    def _plan_lengths(gb: GlobalBatch):
        L = gb.lengths
        return L[:, 0] if not np.any(L[:, 1]) else L

    def _pcfg_now(self) -> PlannerConfig:
        p = self.pcfg
        if self.monitor is not None and p.dp_size > 1 \
                and self.monitor.drift() > self.rcfg.drift_tolerance:
            # past the drift tolerance the imbalance is real (straggler),
            # not timing noise — bake measured factors into the next plan
            all_sf = self.monitor.speed_factors()
            sf = [all_sf[r] if r < len(all_sf) else 1.0
                  for r in self._alive]
            sf = (sf + [1.0] * p.dp_size)[:p.dp_size]
            p = dataclasses.replace(p, speed_factors=sf)
        return p

    def _submit(self, it: int) -> None:
        gb = self.stream.batch(it)
        self._pending[it] = gb
        fut = self.pool.submit(
            it, self._plan_lengths(gb), self.cost, self._pcfg_now())
        if self.chaos is not None:
            ev = self.chaos.take_planner_fault(it)
            if ev is not None:
                # the real submission still runs (its store push is
                # idempotent); the *future* the main loop sees is corrupted
                # (crash) or lost (never completes) — _obtain must recover
                fut = cf.Future()
                if ev.kind.value == "planner_crash":
                    fut.set_exception(InjectedFault(ev))
        self._futures[it] = fut

    def _reset_pool(self) -> None:
        if self.pool is not None:
            with contextlib.suppress(Exception):
                self.pool.shutdown()
        self.pool = PlannerPool(
            self.store, n_workers=max(2, self.rcfg.lookahead + 1),
            use_processes=self.rcfg.use_processes)

    def _obtain(self, it: int, stats: Optional[RunnerStats] = None):
        """Returns (global_batch, replica-0 plan, IterationPlan, wait_s,
        planning_s). Planner faults (timeout, crashed/lost future, broken
        pool) resubmit with backoff instead of killing the run."""
        rcfg = self.rcfg
        if rcfg.synchronous:
            gb = self.stream.batch(it)
            t0 = time.perf_counter()
            if self.chaos is not None:
                ev = self.chaos.take_planner_fault(it)
                if ev is not None and stats is not None:
                    # inline planning: a dead planner is just re-run inline
                    stats.faults += 1
                    stats.recoveries.append(
                        {"iter": it, "kind": "planner_replanned",
                         "fault": ev.describe()})
            it_plan = plan_iteration(self._plan_lengths(gb), self.cost,
                                     self._pcfg_now())
            self.store.push(it, it_plan.replica_plans[0])
            plan = self.store.fetch(it, timeout=rcfg.plan_timeout)
            wait = time.perf_counter() - t0
        else:
            gb = self._pending.pop(it)
            t0 = time.perf_counter()
            it_plan = None
            for attempt in range(rcfg.max_retries + 1):
                fut = self._futures.pop(it)
                try:
                    it_plan = fut.result(timeout=rcfg.plan_timeout)
                    break
                except (TimeoutError, cf.TimeoutError, cf.CancelledError,
                        cf.BrokenExecutor, InjectedFault) as e:
                    if attempt >= rcfg.max_retries:
                        raise PipelineError(
                            f"plan for iteration {it} failed after "
                            f"{attempt + 1} attempts: {e!r}") from e
                    if stats is not None:
                        stats.faults += 1
                        stats.recoveries.append(
                            {"iter": it, "kind": "planner_resubmit",
                             "fault": repr(e)})
                    if isinstance(e, cf.BrokenExecutor):
                        self._reset_pool()
                    time.sleep(rcfg.retry_backoff_s * (attempt + 1))
                    self._submit(it)
                    self._pending.pop(it, None)  # gb already in hand
            plan = self.store.fetch(it, timeout=rcfg.plan_timeout)
            wait = time.perf_counter() - t0
        self.store.evict_below(it)  # executed plans are dead; keep RSS flat
        return gb, plan, it_plan, wait, it_plan.planning_seconds

    # ------------------------- execution side --------------------------
    @property
    def _encdec(self) -> bool:
        return self.cfg.family == "encdec"

    @staticmethod
    def _batch_shape(b) -> tuple:
        if "enc_tokens" in b:
            return (int(b["enc_tokens"].shape[0]),
                    int(b["enc_tokens"].shape[1]),
                    int(b["dec_tokens"].shape[1]))
        return int(b["tokens"].shape[0]), int(b["tokens"].shape[1])

    def _execute_replica(self, it: int, rep: int, plan: ExecutionPlan,
                         gb: GlobalBatch, params):
        """One replica's plan -> (grads, loss_sum, weight_sum)."""
        if not plan.micro_batches:
            return None, 0.0, 0.0   # idle replica (fewer micro-batches than dp)
        batches = {m.mb_id: materialize_micro_batch(
                       m, gb.tokens, lengths=gb.lengths)
                   for m in plan.micro_batches}
        hook = (self.chaos.executor_hook(it, replica=rep)
                if self.chaos is not None else None)
        res = self.backend.execute_plan(
            plan, params=params, batches=batches, hook=hook,
            collect_timings=self._calibrator is not None,
            timeout=self.rcfg.exec_timeout)
        if self._calibrator is not None and res.timings:
            by_id = {m.mb_id: m for m in plan.micro_batches}
            for kind, mb_id, secs in res.timings:
                m = by_id[mb_id]
                seq = (tuple(m.seq) if isinstance(m.seq, (tuple, list))
                       else m.seq)
                if kind == "f":
                    self._calibrator.observe(m.mbs, seq, fwd_s=secs)
                elif kind == "b":
                    self._calibrator.observe(m.mbs, seq, bwd_s=secs)
                else:
                    self._calibrator.observe_total(m.mbs, seq, secs)
        return res.grads, res.loss_sum, res.weight_sum

    # ------------------------- recovery side ---------------------------
    def _drain(self) -> None:
        """Cancel in-flight plans and forget buffered state — they were
        produced under a topology/speed assumption that just died."""
        if self.pool is not None:
            self.pool.drain()
        for fut in self._futures.values():
            fut.cancel()
        self._futures.clear()
        self._pending.clear()
        self.store.clear()

    def _resubmit_window(self, it: int) -> None:
        if self.rcfg.synchronous or self.pool is None:
            return
        for i in range(it, min(it + max(1, self.rcfg.lookahead), self._end)):
            if i not in self._futures:
                self._submit(i)

    def _topology_sweep(self, it: int, stats: RunnerStats) -> None:
        """The replica set changed: run an ElasticPlanManager sweep, shrink
        (or re-grow) ``dp_size`` to the survivors, drain + resubmit."""
        gb = self.stream.batch(it)
        res = self.elastic.plan(self._plan_lengths(gb))
        alive = res["alive"]
        if not alive:
            raise PipelineError(f"iteration {it}: all replicas dead")
        self._alive = list(alive)
        self.pcfg = dataclasses.replace(
            self.pcfg, dp_size=len(alive),
            speed_factors=list(res["speed_factors"]))
        if self.elastic.replan is not None:
            # keep future sweeps replanning under the surviving topology
            self.elastic.replan = make_planner_replan(self.cost, self.pcfg)
        stats.faults += len(res["dead_this_sweep"])
        stats.recoveries.append({
            "iter": it, "kind": "replica_set_change",
            "alive": list(alive), "dead": list(res["dead"]),
            "dead_this_sweep": list(res["dead_this_sweep"]),
            "recovered_this_sweep": list(res["recovered_this_sweep"]),
        })
        self._drain()
        self._resubmit_window(it)

    def _recover(self, it: int, err: BaseException, params, opt,
                 stats: RunnerStats):
        """Post-fault path: drain, maybe restore, replan. Returns
        (params, opt, resume_iteration)."""
        self._drain()
        resume = it
        ev = _injected_event(err)
        if ev is not None and ev.state_lost and self.rcfg.ckpt_dir:
            try:
                like = jax.eval_shape(lambda: {"params": params, "opt": opt})
                state, manifest = CKPT.load_latest_valid(
                    self.rcfg.ckpt_dir, like)
                params, opt = state["params"], state["opt"]
                if self.backend is not None:
                    opt = self.backend.place_opt_state(opt)
                resume = int(manifest["step"])
                stats.recoveries.append(
                    {"iter": it, "kind": "checkpoint_restore",
                     "restored_step": resume, "fault": repr(err)})
            except FileNotFoundError:
                warnings.warn(
                    f"iteration {it}: state lost but no restorable "
                    "checkpoint — retrying with in-memory params", stacklevel=2)
                stats.recoveries.append(
                    {"iter": it, "kind": "retry_no_checkpoint",
                     "fault": repr(err)})
        else:
            stats.recoveries.append(
                {"iter": it, "kind": "retry", "fault": repr(err)})
        time.sleep(self.rcfg.retry_backoff_s)
        self._resubmit_window(resume)
        return params, opt, resume

    def _emergency_save(self, it: int, params, opt) -> None:
        """Best-effort final checkpoint before the run dies — must never
        mask the original failure."""
        if not self.rcfg.ckpt_dir:
            return
        try:
            CKPT.save(self.rcfg.ckpt_dir, it, {"params": params, "opt": opt},
                      extra={"emergency": True})
        except Exception as e:   # noqa: BLE001 — reporting path
            warnings.warn(f"emergency checkpoint at iteration {it} "
                          f"failed: {e!r}", stacklevel=2)

    # ------------------------------ run --------------------------------
    def run(self):
        """Returns (params, history, stats: RunnerStats)."""
        if self.rcfg.fault_domain == "process":
            # the process fault domain replaces this whole in-process loop:
            # one OS process per DP replica, a socket coordinator doing the
            # planning, and real SIGKILL chaos delivered by the driver
            from repro.dist.cluster import run_process_cluster
            return run_process_cluster(
                self.cfg, self.cost, self.pcfg, self.rcfg, self.stream,
                opt_cfg=self.opt_cfg, chaos=self.chaos)
        rcfg, pcfg, cfg = self.rcfg, self.pcfg, self.cfg
        key = jax.random.PRNGKey(rcfg.seed)
        params = (T.init_encdec(key, cfg) if self._encdec
                  else MD.init_params(key, cfg))
        opt = init_opt_state(params, self.opt_cfg)
        start = 0
        if rcfg.ckpt_dir:
            state, start = CKPT.restore_or_init(
                rcfg.ckpt_dir, lambda: {"params": params, "opt": opt})
            if start:
                params, opt = state["params"], state["opt"]

        self.backend = make_backend(
            rcfg.backend, cfg, pcfg.n_stages, impl=rcfg.impl,
            step_cache=self.step_cache, use_executor=rcfg.use_executor,
            exec_timeout=rcfg.exec_timeout, mesh=self.mesh,
            strict=rcfg.strict_verify)
        opt = self.backend.place_opt_state(opt)

        end = start + rcfg.n_iters
        self._end = end
        if not rcfg.synchronous:
            self._reset_pool()
            for i in range(start, min(start + rcfg.lookahead, end)):
                self._submit(i)

        history = []
        stats = RunnerStats(
            mode="synchronous" if rcfg.synchronous else "plan-ahead")
        it = start
        attempts = 0
        try:
            while it < end:
                t0 = time.perf_counter()
                try:
                    if self.elastic is not None \
                            and self.monitor.alive() != self._alive:
                        t_rec = time.perf_counter()
                        self._topology_sweep(it, stats)
                        stats.recovery_s += time.perf_counter() - t_rec
                    if not rcfg.synchronous and it + rcfg.lookahead < end \
                            and (it + rcfg.lookahead) not in self._futures:
                        self._submit(it + rcfg.lookahead)
                    gb, plan, it_plan, wait_s, planning_s = \
                        self._obtain(it, stats)

                    if self._encdec and any(
                            not isinstance(m.seq, (tuple, list))
                            for m in plan.micro_batches):
                        raise ValueError(
                            "enc-dec model got a decoder-only micro-batch: "
                            "the stream must carry (enc, dec) lengths with "
                            "dec > 0 for every sample (use "
                            "encdec_fraction=1.0)")

                    # every surviving replica's plan executes here (single
                    # process stands in for the DP group) and the grads
                    # merge, so the full-batch gradient — and the loss
                    # trajectory — is invariant to how the planner split
                    # work across replicas
                    grads, loss_sum, w_sum = None, 0.0, 0.0
                    replica_s: dict[int, float] = {}
                    for pos, rplan in enumerate(it_plan.replica_plans):
                        rep = (self._alive[pos] if pos < len(self._alive)
                               else pos)
                        # replica 0 executes the store-roundtripped plan
                        # (keeps the serialization path on the hot loop);
                        # others roundtrip locally for identical semantics
                        xplan = plan if pos == 0 else \
                            ExecutionPlan.from_json(rplan.to_json())
                        rt0 = time.perf_counter()
                        g, ls, ws = self._execute_replica(
                            it, rep, xplan, gb, params)
                        replica_s[rep] = time.perf_counter() - rt0
                        loss_sum += ls
                        w_sum += ws
                        if g is not None:
                            grads = g if grads is None else jax.tree.map(
                                jnp.add, grads, g)
                except (PipelineError, InjectedFault) as e:
                    stats.faults += 1
                    attempts += 1
                    if attempts > rcfg.max_retries:
                        # retry budget exhausted — the BaseException handler
                        # below writes the emergency checkpoint
                        raise
                    t_rec = time.perf_counter()
                    params, opt, it = self._recover(it, e, params, opt,
                                                    stats)
                    stats.recovery_s += time.perf_counter() - t_rec
                    continue
                attempts = 0

                params, opt, om = self.backend.optimizer_step(
                    params, grads, opt, self.opt_cfg,
                    grad_scale=1.0 / max(w_sum, 1.0))
                dt = time.perf_counter() - t0
                if self.monitor is not None:
                    for rep in self._alive:
                        if self.chaos is not None \
                                and self.chaos.replica_silent(it, rep):
                            continue
                        self.monitor.heartbeat(
                            rep, iter_time=replica_s.get(rep, dt))
                    if isinstance(self.monitor.clock, LogicalClock):
                        self.monitor.clock.advance(1.0)

                padded = sum(
                    m.mbs * (sum(m.seq) if isinstance(m.seq, (tuple, list))
                             else m.seq)
                    for rp in it_plan.replica_plans
                    for m in rp.micro_batches)
                n_micro = sum(len(rp.micro_batches)
                              for rp in it_plan.replica_plans)
                loss = loss_sum / max(w_sum, 1.0)
                history.append({
                    "iter": it, "loss": loss, "time_s": dt,
                    "n_micro": n_micro,
                    "grad_norm": float(om["grad_norm"]),
                    "plan_wait_s": wait_s, "planning_s": planning_s,
                    "tokens": gb.total_tokens, "padded_tokens": int(padded),
                })
                stats.iters += 1
                stats.planning_s += planning_s
                stats.plan_wait_s += wait_s
                stats.exec_s += dt
                stats.real_tokens += gb.total_tokens
                stats.padded_tokens += int(padded)
                if it > start:
                    stats.overlap_planning_s += planning_s
                    stats.overlap_wait_s += wait_s

                if rcfg.log_every and it % rcfg.log_every == 0:
                    print(f"iter {it:5d}  loss {loss:8.4f}  micro-batches "
                          f"{n_micro:3d}  {dt*1e3:7.1f} ms  "
                          f"plan-wait {wait_s*1e3:6.1f} ms", flush=True)
                if rcfg.ckpt_dir and rcfg.ckpt_every \
                        and (it + 1) % rcfg.ckpt_every == 0:
                    CKPT.save(rcfg.ckpt_dir, it + 1,
                              {"params": params, "opt": opt})
                it += 1
        except BaseException:
            # anything that escapes the retry loop (including retries
            # exhausted above) leaves a final restart point behind
            self._emergency_save(it, params, opt)
            raise
        finally:
            if self.pool is not None:
                self.pool.shutdown()
                self.pool = None
        stats.cache = self.step_cache.stats()
        if self._calibrator is not None:
            stats.calibration = self._calibrator.summary()
        return params, history, stats
