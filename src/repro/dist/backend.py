"""ExecutionBackend: one API over both execution planes (paper §5–§6).

The planner emits :class:`~repro.core.instructions.ExecutionPlan`s; *how*
a plan turns into gradients is a backend choice, selected by
``RunnerConfig.backend``:

- ``"threads"`` (:class:`ThreadsBackend`) — today's host plane: one Python
  thread per stage interprets the instruction stream over rendezvous
  channels (``core/executor.py``), or the sequential grad-accumulation
  fallback when the model/stage split rules out the threaded pipeline.
  Supports ragged micro-batches and encoder-decoder models.
- ``"mesh"`` (:class:`MeshBackend`) — the compiled device plane: each
  palette shape group of a plan's micro-batches compiles into **one**
  ``shard_map`` + ``lax.ppermute`` forward+backward shift register
  (:func:`repro.dist.pipeline.pipelined_grads`) over a real device mesh
  whose first axis is the pipeline-stage axis. Micro-batches enter the ring
  in the §6 comm plan's injection order, so the deadlock-free p2p send
  sequence the simulator proved is exactly the collective-permute sequence
  XLA executes, interleaved with stage compute inside the compiled loop.
  ZeRO-1 optimizer-state sharding (:func:`~repro.dist.sharding.zero1_logical`
  over the stage axis) layers underneath via :meth:`place_opt_state` /
  :meth:`optimizer_step`.

Recompile bounding: mesh steps are cached in the shared
``CompiledStepCache`` under ``("mesh", …, mbs, seq, M)`` where ``(mbs,
seq)`` is the palette bucket and ``M`` the group's micro-batch count padded
up to a power of two with all-masked dummy micro-batches (zero loss
weights ⇒ exactly-zero loss and gradient contributions). Distinct compiled
mesh programs are therefore at most ``palette.n_shapes() × (log2(M_max)+1)``
— the palette bound times a log factor, asserted in
tests/test_exec_backend.py.

Both backends share one signature::

    backend.execute_plan(plan, params=…, batches=…) -> BackendResult

and the threads backend additionally accepts ``callbacks=`` — the raw
host-plane entry point that ``dist/pipeline.py::execute_plan`` used to be.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import spans
from repro.core.executor import (PipelineExecutor, StageCallbacks,
                                reject_bad_plan)
from repro.core.instructions import ExecutionPlan, Instr, Op
from repro.dist.pipeline import injection_order, pipelined_grads
from repro.dist.sharding import spec_for_zero, zero1_logical
from repro.models import layers as L
from repro.models import model as MD
from repro.models import transformer as T
from repro.train.optimizer import adamw_update
from repro.train.pipeline_adapter import (EncDecPipelinedModel,
                                          PipelinedModel, _xent_sum,
                                          build_encdec_grad_step,
                                          build_grad_step,
                                          model_cache_namespace)
from repro.train.step_cache import CompiledStepCache


@dataclass
class BackendResult:
    """What executing one replica's plan produced.

    ``timings`` entries are ``(kind, mb_id, seconds)`` with ``kind`` one of
    ``"f"``/``"b"`` (per-stage forward/backward, threads pipeline) or
    ``"total"`` (whole fwd+bwd for the micro-batch) — the calibrator input.
    ``loss_sum`` is a float, or the threads pipeline's
    :class:`~repro.train.pipeline_adapter.LossSum`, still on the device
    until ``float()`` reads it. ``meta["pipeline_syncs"]`` counts the
    pipeline's waits on the device (one per timed callback).
    """
    grads: Any
    loss_sum: float
    weight_sum: float
    timings: list = field(default_factory=list)
    meta: dict = field(default_factory=dict)


class ExecutionBackend:
    """Protocol both execution planes implement.

    ``execute_plan(plan, *, params, batches, hook=None,
    collect_timings=False, callbacks=None, timeout=None)`` runs one
    replica's plan and returns a :class:`BackendResult`. ``hook(stage,
    instr)`` is the fault-injection/observation hook (``dist/chaos.py``);
    backends call it per issued instruction so chaos schedules and
    straggler injection work identically on either plane.

    :meth:`place_opt_state` / :meth:`optimizer_step` let a backend own the
    optimizer's memory layout (the mesh backend ZeRO-1-shards state over
    the pipeline axis); the defaults are the plain single-device path.
    """

    name = "abstract"

    def execute_plan(self, plan: ExecutionPlan, *, params=None, batches=None,
                     callbacks=None, hook=None, collect_timings: bool = False,
                     timeout: Optional[float] = None) -> BackendResult:
        raise NotImplementedError

    def place_opt_state(self, opt_state):
        """Place optimizer state for this backend (default: leave as-is)."""
        return opt_state

    def optimizer_step(self, params, grads, opt_state, opt_cfg,
                       grad_scale: float = 1.0):
        """Apply one optimizer update to ``grads * grad_scale`` (default:
        eager AdamW)."""
        grads = jax.tree.map(lambda g: g * grad_scale, grads)
        return adamw_update(params, grads, opt_state, opt_cfg)


def _scaled_adamw(opt_cfg, *, donate: bool):
    """jit of ``adamw_update`` over ``grads * scale``. A Python-float scale
    is weakly typed, so bf16 grads scale in bf16 exactly as an eager
    ``g * scale`` would."""
    def adamw_step(params, grads, opt_state, scale):
        grads = jax.tree.map(lambda g: g * scale, grads)
        return adamw_update(params, grads, opt_state, opt_cfg)
    return jax.jit(adamw_step, donate_argnums=(0, 1, 2) if donate else ())


def _timed_callbacks(cbs: list[StageCallbacks], records: list, lock):
    """Wrap every stage's fwd/bwd with wall timers that wait for what the
    callback returns (block_until_ready, so dispatch isn't mistaken for
    compute): one wait per record. Records ("f"/"b", mb_id, s) under
    ``lock`` — callbacks run on stage threads. The last stage's forward
    runs its forward and backward in one program, so it records
    ("total", mb_id, s) and its backward, which only hands the stashed
    gradient on, records nothing."""
    def timed(call, kind):
        def run(mb_id, *a):
            t0 = time.perf_counter()
            out = call(mb_id, *a)
            jax.block_until_ready(out)
            with lock:
                records.append((kind, mb_id, time.perf_counter() - t0))
            return out
        return run

    last = len(cbs) - 1
    return [StageCallbacks(timed(cb.forward, "total" if j == last else "f"),
                           cb.backward if j == last
                           else timed(cb.backward, "b"), cb.step)
            for j, cb in enumerate(cbs)]


class ThreadsBackend(ExecutionBackend):
    """Host plane: threaded pipeline executor, or sequential accumulation.

    The pipeline path engages when ``use_executor`` and the model's period
    stack splits evenly over ``n_stages`` (plus the enc/dec-boundary rule
    for encoder-decoder models); otherwise plans execute as a sequential
    per-micro-batch grad loop with identical math. Ragged micro-batch
    shapes are fine on either path — this is the backend that keeps
    DynaPipe's variable-shape generality.
    """

    name = "threads"

    def __init__(self, cfg: ArchConfig, n_stages: int,
                 impl: Optional[str] = None,
                 step_cache: Optional[CompiledStepCache] = None, *,
                 use_executor: bool = True, exec_timeout: float = 120.0,
                 strict: bool = False):
        self.cfg = cfg
        self.n_stages = n_stages
        self.impl = impl
        self.step_cache = step_cache if step_cache is not None \
            else CompiledStepCache()
        self.exec_timeout = exec_timeout
        self.strict = strict
        self._opt_steps: dict = {}
        if cfg.family == "encdec":
            # total periods = enc + dec; the layout also requires the stage
            # boundary to coincide with the enc/dec split
            pipelined = use_executor and n_stages > 1 \
                and (2 * cfg.n_periods) % n_stages == 0 \
                and cfg.n_periods % ((2 * cfg.n_periods) // n_stages) == 0
            self.pm = (EncDecPipelinedModel(cfg, None, n_stages, impl=impl,
                                            step_cache=self.step_cache)
                       if pipelined else None)
        else:
            pipelined = (use_executor and n_stages > 1
                         and cfg.n_periods % n_stages == 0)
            self.pm = (PipelinedModel(cfg, None, n_stages, impl=impl,
                                      step_cache=self.step_cache)
                       if pipelined else None)

    def optimizer_step(self, params, grads, opt_state, opt_cfg,
                       grad_scale: float = 1.0):
        """AdamW compiled once, with params, grads and optimizer state
        donated: the update happens in place, where an eager one would hold
        a second copy of the fp32 state (gigabytes at published widths).
        The arrays passed in are consumed."""
        key = repr(opt_cfg)
        if key not in self._opt_steps:
            self._opt_steps[key] = _scaled_adamw(opt_cfg, donate=True)
        return self._opt_steps[key](params, grads, opt_state, grad_scale)

    def _grad_fn(self, shape: tuple):
        """shape: (mbs, seq) decoder-only or (mbs, enc, dec) enc-dec."""
        key = ("grad", model_cache_namespace(self.cfg), self.impl) + shape
        build = (build_encdec_grad_step if len(shape) == 3
                 else build_grad_step)
        return self.step_cache.get(
            key, lambda: build(self.cfg, impl=self.impl))

    @staticmethod
    def _batch_shape(b) -> tuple:
        if "enc_tokens" in b:
            return (int(b["enc_tokens"].shape[0]),
                    int(b["enc_tokens"].shape[1]),
                    int(b["dec_tokens"].shape[1]))
        return int(b["tokens"].shape[0]), int(b["tokens"].shape[1])

    def execute_plan(self, plan: ExecutionPlan, *, params=None, batches=None,
                     callbacks=None, hook=None, collect_timings: bool = False,
                     timeout: Optional[float] = None) -> BackendResult:
        timeout = timeout if timeout is not None else self.exec_timeout
        if self.strict:
            reject_bad_plan(plan, "ThreadsBackend")
        if callbacks is not None:
            # raw host-plane mode: caller owns the stage callbacks (what
            # dist/pipeline.py::execute_plan exposes)
            PipelineExecutor(plan, callbacks, timeout=timeout,
                             hook=hook).run()
            return BackendResult(None, 0.0, 0.0)
        if not plan.micro_batches:
            return BackendResult(None, 0.0, 0.0)

        if self.pm is not None:
            pm = self.pm
            with spans.span(spans.STAGE_SETUP):
                pm.set_params(params)
                cbs, result = pm.make_callbacks(plan, batches)
                records: list = []
                if collect_timings:
                    cbs = _timed_callbacks(cbs, records, threading.Lock())
            PipelineExecutor(plan, cbs, timeout=timeout, hook=hook).run()
            del cbs     # drops the stage param slices before the merge
            with spans.span(spans.GRAD_MERGE):
                # the merge's output is allocated when it is dispatched:
                # first let the last stage's last program finish and free
                # its temporaries and param slice; stage 0's last backward
                # is still queued, so the device does not drain
                jax.block_until_ready(result["loss_sum"].parts[-1])
                grads = pm.merge_stage_grads(result["stage_grads"])
            return BackendResult(grads, result["loss_sum"],
                                 result["weight_sum"], records,
                                 {"pipeline_syncs": len(records)})

        with spans.span(spans.PIPELINE):
            return self._execute_sequential(batches, params, hook,
                                            collect_timings)

    def _execute_sequential(self, batches, params, hook,
                            collect_timings: bool) -> BackendResult:
        grads, loss_sum, w_sum = None, 0.0, 0.0
        timings: list = []
        for mb_id in sorted(batches):
            if hook is not None:
                # sequential path has no stage threads; model it as one
                # stage-0 forward per micro-batch so stage-0 faults (and
                # stragglers) inject identically
                hook(0, Instr(Op.FORWARD, mb_id))
            with spans.span(spans.DEVICE_PUT):
                b = {k: jnp.asarray(v) for k, v in batches[mb_id].items()}
            t0 = time.perf_counter()
            ls, ws, g = self._grad_fn(self._batch_shape(b))(params, b)
            with spans.span(spans.LOSS_SYNC):
                loss_sum += float(ls)  # float() syncs: t0..here is compute
                w_sum += float(ws)
            if collect_timings:
                timings.append(("total", mb_id, time.perf_counter() - t0))
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        return BackendResult(grads, loss_sum, w_sum, timings)


def _mesh_stage_fn(cfg: ArchConfig, k: int, impl):
    """The uniform SPMD stage transform for :func:`pipelined_grads`.

    Every stage runs embed → its period slice → final norm → summed xent,
    and ``jnp.where`` masks select the stage's actual role: stage 0 feeds
    the embedding into the stack (later stages feed the ppermuted
    activation), and only the last stage's loss receives cotangent 1 in the
    backward ring, so intermediate stages' norm/head work contributes
    exact-zero gradients. The per-stage *math that matters* is identical to
    the host plane's ``_stage_apply`` — same ``stack_fwd`` slice semantics
    (``remat=True`` stage-granular checkpointing), same ``_xent_sum`` loss
    — which is what the bit-identity parity tests pin down.
    """
    sub_cfg = dataclasses.replace(cfg, n_layers=k * len(cfg.layer_pattern))

    def stage_fn(stack_w, shared, h_buf, batch, stage, last):
        emb = MD.embed_inputs(shared, batch, cfg)
        h = jnp.where(stage == 0, emb.astype(h_buf.dtype), h_buf)
        h, _, _ = T.stack_fwd(stack_w, h, sub_cfg,
                              positions=batch["positions"],
                              segment_ids=batch["segment_ids"],
                              impl=impl, remat=True)
        hn = L.rms_norm(h, shared["final_norm"], cfg.norm_eps)
        head = shared.get("head", shared.get("embed"))
        loss_sum, w_sum = _xent_sum(head, hn, batch["labels"],
                                    batch["loss_weights"], cfg)
        return h, loss_sum, w_sum
    return stage_fn


def _dummy_micro_batch(mbs: int, seq: int) -> dict:
    """All-masked filler micro-batch: zero loss weights make its loss and
    every gradient contribution exactly zero (the xent cotangent is
    ``w * (softmax - onehot)`` with ``w = 0``), so padding a shape group to
    its power-of-two bucket never perturbs the real result bitwise."""
    return {
        "tokens": np.zeros((mbs, seq), np.int32),
        "labels": np.zeros((mbs, seq), np.int32),
        "loss_weights": np.zeros((mbs, seq), np.float32),
        "positions": np.zeros((mbs, seq), np.int32),
        "segment_ids": np.full((mbs, seq), -1, np.int32),
    }


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


_BATCH_KEYS = ("tokens", "labels", "loss_weights", "positions",
               "segment_ids")


class MeshBackend(ExecutionBackend):
    """Device plane: plans compile to shard_map+ppermute shift registers.

    Decoder-only token models for now — the enc-dec (he, hd) ring payload
    and the adapter input modes stay on the threads backend (raised as
    ``NotImplementedError`` so a config mistake is loud, not silent).

    Per-micro-batch losses are summed host-side in ascending ``mb_id``
    order — the same order as the threads backend's sequential path, which
    is what makes the two backends' iteration losses comparable bit-for-bit
    on a 1-device mesh.
    """

    name = "mesh"

    def __init__(self, cfg: ArchConfig, n_stages: int,
                 impl: Optional[str] = None,
                 step_cache: Optional[CompiledStepCache] = None, *,
                 mesh: Optional[Mesh] = None, strict: bool = False):
        self.strict = strict
        if cfg.family == "encdec":
            raise NotImplementedError(
                "MeshBackend runs decoder-only models; the enc-dec pipeline "
                "executes on the threads backend (backend='threads')")
        if cfg.input_mode != "tokens":
            raise NotImplementedError(
                f"MeshBackend supports input_mode='tokens' "
                f"(got {cfg.input_mode!r})")
        if cfg.n_periods % n_stages:
            raise ValueError(
                f"{cfg.name}: n_periods {cfg.n_periods} not divisible by "
                f"{n_stages} stages")
        if mesh is None:
            from repro.launch.mesh import make_stage_mesh
            mesh = make_stage_mesh(n_stages)
        self.cfg = cfg
        self.n_stages = n_stages
        self.impl = impl
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        if int(mesh.shape[self.axis]) != n_stages:
            raise ValueError(
                f"stage axis {self.axis!r} has size {mesh.shape[self.axis]}, "
                f"expected n_stages={n_stages}")
        self.k = cfg.n_periods // n_stages
        self.step_cache = step_cache if step_cache is not None \
            else CompiledStepCache()
        dev_ids = tuple(int(d.id) for d in np.asarray(mesh.devices).flat)
        # full model identity + mesh identity: a shared cache must never
        # hand one mesh's compiled program to another
        self._ns = (repr(cfg), n_stages, impl, self.axis, dev_ids)
        self._act_dtype = L._dtype(cfg)

    # ------------------------- param placement -------------------------
    def _place_params(self, params):
        """(stage_stack, shared): the period stack reshaped (S, k, …) and
        device_put sharded over the stage axis — each stage holds only its
        own slice, the real pipeline-parallel placement — and everything
        else replicated."""
        S, k = self.n_stages, self.k
        stack = jax.tree.map(
            lambda a: jnp.reshape(jnp.asarray(a), (S, k) + a.shape[1:]),
            params["stack"])
        stack = jax.device_put(
            stack, NamedSharding(self.mesh, P(self.axis)))
        shared = {key: v for key, v in params.items() if key != "stack"}
        shared = jax.device_put(shared, NamedSharding(self.mesh, P()))
        return stack, shared

    def _group_step(self, mbs: int, seq: int, m_pad: int):
        key = ("mesh", *self._ns, mbs, seq, m_pad)
        cfg, k, S, mesh, axis = (self.cfg, self.k, self.n_stages, self.mesh,
                                 self.axis)
        impl, act_dtype = self.impl, self._act_dtype

        def build():
            stage_fn = _mesh_stage_fn(cfg, k, impl)
            h_spec = jax.ShapeDtypeStruct((mbs, seq, cfg.d_model), act_dtype)

            def step(stack, shared, bstack):
                lv, wv, gw, gsh = pipelined_grads(
                    stage_fn, stack, shared, bstack, mesh=mesh, n_stages=S,
                    h_spec=h_spec)
                # (S, k, …) per-stage grads back to the (n_periods, …)
                # full-params layout (the concat in merge_stage_grads)
                g_stack = jax.tree.map(
                    lambda a: jnp.reshape(a, (S * k,) + a.shape[2:]), gw)
                return lv, wv, g_stack, gsh
            return jax.jit(step)
        return self.step_cache.get(key, build)

    # ------------------------- plan execution --------------------------
    def execute_plan(self, plan: ExecutionPlan, *, params=None, batches=None,
                     callbacks=None, hook=None, collect_timings: bool = False,
                     timeout: Optional[float] = None) -> BackendResult:
        if self.strict:
            reject_bad_plan(plan, "MeshBackend")
        if callbacks is not None:
            raise ValueError(
                "the mesh backend compiles plans into shard_map programs; "
                "callback-driven execution is the threads backend's host "
                "plane (backend='threads')")
        if not plan.micro_batches:
            return BackendResult(None, 0.0, 0.0)
        order = injection_order(plan)
        ids = sorted(m.mb_id for m in plan.micro_batches)
        if sorted(order) != ids:
            raise ValueError("plan injection order does not cover its "
                             "micro-batches")
        if hook is not None:
            # one stage-0 forward event per micro-batch, in ring order, so
            # chaos schedules fire identically to the host plane
            for mb_id in order:
                hook(0, Instr(Op.FORWARD, mb_id))

        # palette shape groups in first-appearance ring order; within a
        # group, micro-batches keep the §6 injection order — that order is
        # exactly the sequence of ppermute sends the compiled ring issues
        groups: dict[tuple, list[int]] = {}
        for mb_id in order:
            b = batches[mb_id]
            shape = (int(b["tokens"].shape[0]), int(b["tokens"].shape[1]))
            groups.setdefault(shape, []).append(mb_id)

        stack, shared = self._place_params(params)
        loss_by_mb: dict[int, float] = {}
        w_by_mb: dict[int, float] = {}
        grads = None
        timings: list = []
        meta = {"groups": []}
        for (mbs, seq), members in groups.items():
            m_real = len(members)
            m_pad = _next_pow2(m_real)
            pad = [_dummy_micro_batch(mbs, seq)] * (m_pad - m_real)
            bstack = {
                key: np.stack([np.asarray(batches[i][key])
                               for i in members]
                              + [d[key] for d in pad])
                for key in _BATCH_KEYS}
            fn = self._group_step(mbs, seq, m_pad)
            t0 = time.perf_counter()
            out = fn(stack, shared, bstack)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            lv, wv, g_stack, g_sh = out
            lv = np.asarray(lv)
            wv = np.asarray(wv)
            for pos, mb_id in enumerate(members):
                loss_by_mb[mb_id] = float(lv[pos])
                w_by_mb[mb_id] = float(wv[pos])
                if collect_timings:
                    timings.append(("total", mb_id, dt / m_real))
            g = dict(g_sh, stack=g_stack)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            meta["groups"].append(
                {"mbs": mbs, "seq": seq, "n_micro": m_real, "m_pad": m_pad})

        # ascending mb_id, matching the threads sequential accumulation
        loss_sum = 0.0
        w_sum = 0.0
        for mb_id in ids:
            loss_sum += loss_by_mb[mb_id]
            w_sum += w_by_mb[mb_id]
        return BackendResult(grads, loss_sum, w_sum, timings, meta)

    # ---------------------- ZeRO-1 optimizer layer ---------------------
    def place_opt_state(self, opt_state):
        """ZeRO-1: shard every optimizer-state leaf over the pipeline-stage
        axis (``zero1_logical`` picks the largest divisible dim; leaves
        nothing divides stay replicated). Master weights, m and v each hold
        1/S per device — the paper's optimizer-memory term drops by the
        stage count without changing any update math."""
        mesh = self.mesh

        def place(x):
            x = jnp.asarray(x)
            if x.ndim == 0:
                return jax.device_put(x, NamedSharding(mesh, P()))
            zl = zero1_logical((None,) * x.ndim, x.shape, mesh)
            return jax.device_put(
                x, NamedSharding(mesh, spec_for_zero(x.shape, zl, mesh)))
        return jax.tree.map(place, opt_state)

    def optimizer_step(self, params, grads, opt_state, opt_cfg,
                       grad_scale: float = 1.0):
        """AdamW under jit so XLA partitions the update over the ZeRO
        shards: each device updates only its 1/S slice of (master, m, v)
        and the new params materialize from the sharded master."""
        key = ("mesh_opt", *self._ns, repr(opt_cfg))
        fn = self.step_cache.get(
            key, lambda: _scaled_adamw(opt_cfg, donate=False))
        return fn(params, grads, opt_state, grad_scale)


def make_backend(name: str, cfg: ArchConfig, n_stages: int, *,
                 impl: Optional[str] = None,
                 step_cache: Optional[CompiledStepCache] = None,
                 use_executor: bool = True, exec_timeout: float = 120.0,
                 mesh: Optional[Mesh] = None,
                 strict: bool = False) -> ExecutionBackend:
    """Backend factory keyed by ``RunnerConfig.backend``. ``strict=True``
    makes either backend statically verify every plan (repro.analysis)
    and refuse ERROR-level ones with :class:`PlanRejectedError`."""
    if name == "threads":
        return ThreadsBackend(cfg, n_stages, impl=impl, step_cache=step_cache,
                              use_executor=use_executor,
                              exec_timeout=exec_timeout, strict=strict)
    if name == "mesh":
        return MeshBackend(cfg, n_stages, impl=impl, step_cache=step_cache,
                           mesh=mesh, strict=strict)
    if name == "process":
        raise ValueError(
            "the process backend is not built by the factory: it needs a "
            "live cluster coordinator (sockets, membership, election) — "
            "set RunnerConfig.fault_domain='process' and the runner routes "
            "through repro.dist.cluster.run_process_cluster instead")
    raise ValueError(f"unknown execution backend {name!r}; "
                     "expected 'threads' or 'mesh'")
