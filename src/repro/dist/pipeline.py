"""Compiled pipeline-parallel execution over a device mesh (paper §5–§6).

Two execution planes implement the same instruction semantics:

- **Host plane** (``core/executor.py``): one Python thread per stage
  interprets an :class:`~repro.core.instructions.ExecutionPlan` against
  rendezvous channels — supports *ragged* micro-batches (every micro-batch
  its own padded shape), which is DynaPipe's whole point. Use
  :func:`execute_plan` / the training loop for that.
- **Device plane** (this module's :func:`pipelined_apply`): when one
  iteration's micro-batches share a shape (the ShapePalette buckets them),
  the pipeline compiles to a single ``shard_map`` program whose stages talk
  through ``lax.ppermute`` — XLA's collective-permute, i.e. real P2P
  send/recv on the interconnect. The *order* in which micro-batches enter
  the ring is taken from the plan's per-stage instruction stream, so the
  deadlock-free ordering computed by ``core/comm_plan.py`` is what the
  compiled collective sequence executes.

``pipelined_apply`` is a GPipe-style shift register: with ``S`` stages and
``M`` micro-batches it runs ``M + S - 1`` ticks; at tick ``t`` stage ``s``
holds micro-batch ``t - s``, computes, and ppermutes its output to stage
``s + 1``. Stage ``s`` owns ``stage_params[s]`` (the leading axis of every
param leaf is the stage axis and is sharded over the mesh's first axis).
Warm-up/drain ticks compute on don't-care values that never reach a valid
output slot.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.executor import PipelineExecutor, StageCallbacks
from repro.core.instructions import ExecutionPlan, Op


def injection_order(plan: ExecutionPlan) -> list[int]:
    """Micro-batch ids in the order stage 0 launches forwards — the ring
    entry order the §6 comm plan proved deadlock-free.

    The planner records the schedule's cluster-permuted order in
    ``plan.meta["injection_order"]`` (core/schedule.py's
    ``cluster_permute_order``); that is the authoritative source. The
    fallback scan of stage 0's instruction stream recovers the same order
    for hand-built plans, but ``build_instructions`` breaks time ties by
    global sequence number, which can disagree with the schedule's
    permutation on tied launch times — so the meta entry wins when present,
    keeping the compiled ring in lockstep with the simulator's timeline."""
    meta_order = plan.meta.get("injection_order") if plan.meta else None
    if meta_order:
        return [int(i) for i in meta_order]
    return [ins.micro_batch for ins in plan.per_stage[0]
            if ins.op is Op.FORWARD]


def _sequential(stage_fn, stage_params, xs, n_stages):
    """1-device fallback: same math, no collectives."""
    h = xs
    for s in range(n_stages):
        w = jax.tree.map(lambda a, s=s: a[s], stage_params)
        h = jax.vmap(lambda hb, w=w, s=s: stage_fn(w, hb, s))(h)
    return h


def pipelined_apply(
    stage_fn: Callable,
    stage_params,
    inputs: jax.Array,
    *,
    mesh: Optional[Mesh] = None,
    n_stages: Optional[int] = None,
    plan: Optional[ExecutionPlan] = None,
) -> jax.Array:
    """Run ``inputs`` through ``n_stages`` pipeline stages on ``mesh``.

    Args:
      stage_fn: ``stage_fn(stage_weights, h, stage) -> h_out`` — pure,
        shape/dtype-preserving per-stage transform. ``stage`` is a traced
        scalar stage index.
      stage_params: pytree whose leaves carry a leading ``n_stages`` axis
        (stage ``s`` computes with leaf ``[s]``).
      inputs: ``(n_micro, micro_batch, ...)`` stack of equal-shape
        micro-batches (bucket ragged ones with the ShapePalette first; truly
        ragged streams run on the host plane via :func:`execute_plan`).
      mesh: mesh whose *first* axis is the stage axis. ``None`` or a size-1
        stage axis selects the sequential fallback.
      n_stages: defaults to the stage-axis size (or the params' leading dim
        in fallback mode).
      plan: optional :class:`ExecutionPlan`; its stage-0 instruction stream
        fixes the order micro-batches enter the ring. Results are returned
        in the original micro-batch order regardless.

    Returns an array shaped like ``inputs``: micro-batch ``i`` fully
    transformed by stages ``0..n_stages-1`` in sequence.
    """
    axis = mesh.axis_names[0] if mesh is not None else None
    if n_stages is None:
        n_stages = (mesh.shape[axis] if mesh is not None
                    else jax.tree.leaves(stage_params)[0].shape[0])
    n_micro = inputs.shape[0]

    order = None
    if plan is not None:
        if plan.n_stages != n_stages:
            raise ValueError(f"plan has {plan.n_stages} stages, mesh/params "
                             f"give {n_stages}")
        order = np.asarray(injection_order(plan))
        if sorted(order.tolist()) != list(range(n_micro)):
            raise ValueError("plan injection order does not cover inputs")
        inputs = inputs[order]

    if mesh is None or mesh.shape[axis] <= 1:
        out = _sequential(stage_fn, stage_params, inputs, n_stages)
    else:
        if mesh.shape[axis] != n_stages:
            raise ValueError(
                f"stage axis {axis!r} has size {mesh.shape[axis]}, expected "
                f"n_stages={n_stages}")
        out = _pipelined_shardmap(stage_fn, stage_params, inputs, mesh, axis,
                                  n_stages)
    if order is not None:
        out = out[np.argsort(order)]
    return out


def _pipelined_shardmap(stage_fn, stage_params, xs, mesh, axis, n_stages):
    n_micro = xs.shape[0]
    fwd = [(i, i + 1) for i in range(n_stages - 1)]

    def local_fn(w_local, xs_full):
        # w_local: this stage's slice (leading axis length 1); xs replicated
        w = jax.tree.map(lambda a: a[0], w_local)
        stage = jax.lax.axis_index(axis)
        last = n_stages - 1

        def tick(t, carry):
            buf, outs = carry
            x0 = jax.lax.dynamic_index_in_dim(
                xs_full, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            h_in = jnp.where(stage == 0, x0, buf)
            h = stage_fn(w, h_in, stage)
            # the value at the last stage at tick t is micro-batch t - last
            mb = t - last
            idx = jnp.clip(mb, 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
            new = jnp.where((stage == last) & (mb >= 0), h, cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, new, idx, 0)
            # P2P hand-off to the next stage (last stage's send is dropped;
            # stage 0 receives zeros it never reads)
            buf = jax.lax.ppermute(h, axis, perm=fwd)
            return buf, outs

        buf0 = jnp.zeros_like(xs_full[0])
        outs0 = jnp.zeros_like(xs_full)
        _, outs = jax.lax.fori_loop(0, n_micro + n_stages - 1, tick,
                                    (buf0, outs0))
        # only the last stage wrote real values; psum replicates them
        return jax.lax.psum(outs, axis)

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
    run = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs, out_specs=P(),
                        check_vma=False)
    return run(stage_params, xs)


def pipelined_grads(
    stage_fn: Callable,
    stage_params,
    shared_params,
    batch_stack,
    *,
    mesh: Mesh,
    n_stages: int,
    h_spec: jax.ShapeDtypeStruct,
):
    """Forward **and backward** GPipe shift register — one compiled
    ``shard_map`` program computing the summed loss and its parameter
    gradients for a stack of equal-shape micro-batches.

    This is the device plane's training step: ``M`` micro-batches ride a
    ``M + S - 1``-tick forward ring (stage ``s`` computes micro-batch
    ``t - s`` at tick ``t``, hands its activation to ``s + 1`` via
    ``lax.ppermute`` — real P2P on the interconnect, issued in exactly the
    order the caller stacked the micro-batches, i.e. the §6 comm-plan
    injection order), then an equal-length backward ring in the reverse
    direction: per tick, ``jax.vjp`` recomputes the stage forward from the
    stashed stage input (stage-granular activation checkpointing, the same
    policy as the host plane's ``train/pipeline_adapter.py``) and the
    incoming cotangent ppermutes from stage ``s + 1`` to ``s``.

    Args:
      stage_fn: ``stage_fn(stage_weights, shared, h_buf, batch, stage, last)
        -> (h_out, loss_sum, weight_sum)`` — a *uniform* per-stage transform
        (``stage`` is a traced scalar): every stage runs the same program
        and selects its role with ``jnp.where`` masks (first stage embeds,
        last stage gets loss cotangent 1, see ``dist/backend.py``), which is
        what makes the per-stage params homogeneous enough to shard with a
        single ``P(stage_axis)`` spec.
      stage_params: pytree with a leading ``n_stages`` axis, sharded over the
        mesh's first axis (stage ``s`` computes with leaf ``[s]``).
      shared_params: pytree replicated to every stage (embedding, final
        norm, LM head); its gradient contributions are psum-reduced over
        the stage axis in mesh order — the collective analogue of the host
        plane's ``merge_stage_grads`` summation.
      batch_stack: pytree of ``(M, ...)`` arrays, **already in ring
        (injection) order**; replicated.
      mesh: mesh whose first axis is the stage axis (size ``n_stages``;
        size 1 degenerates to a single-stage program over the same code
        path — the 1-device-parity configuration).
      h_spec: ShapeDtypeStruct of the inter-stage activation payload.

    Returns ``(loss_vec, weight_vec, stage_grads, shared_grads)``:
      per-micro-batch ``(M,)`` f32 loss/weight sums (position ``i`` is the
      ``i``-th *stacked* micro-batch — warm-up/drain garbage never lands in
      a valid slot), gradients w.r.t. ``stage_params`` (leading stage axis,
      sharded) and ``shared_params`` (replicated). Within a stage,
      micro-batch gradients accumulate in ring order — matching the order
      the host executor's FIFO backward stream accumulates them.
    """
    axis = mesh.axis_names[0]
    if mesh.shape[axis] != n_stages:
        raise ValueError(
            f"stage axis {axis!r} has size {mesh.shape[axis]}, expected "
            f"n_stages={n_stages}")
    n_micro = jax.tree.leaves(batch_stack)[0].shape[0]
    fwd = [(i, i + 1) for i in range(n_stages - 1)]
    rev = [(i + 1, i) for i in range(n_stages - 1)]
    n_ticks = n_micro + n_stages - 1
    last = n_stages - 1

    def local_fn(w_local, shared, bstack):
        w = jax.tree.map(lambda a: a[0], w_local)
        stage = jax.lax.axis_index(axis)

        def slice_mb(m):
            idx = jnp.clip(m, 0, n_micro - 1)
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0,
                                                       keepdims=False),
                bstack)

        # ------------------------- forward ring -------------------------
        def fwd_tick(t, carry):
            buf, stash, loss_vec, w_vec = carry
            m = t - stage                      # micro-batch at this stage
            valid = (m >= 0) & (m < n_micro)
            idx = jnp.clip(m, 0, n_micro - 1)
            b = slice_mb(m)
            h, ls, ws = stage_fn(w, shared, buf, b, stage, last)
            # stash the stage *input* for the backward vjp recompute;
            # warm-up/drain garbage never overwrites a valid slot
            cur = jax.lax.dynamic_index_in_dim(stash, idx, 0, keepdims=False)
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, jnp.where(valid, buf, cur), idx, 0)
            write = valid & (stage == last)
            lv = jax.lax.dynamic_index_in_dim(loss_vec, idx, 0,
                                              keepdims=False)
            wv = jax.lax.dynamic_index_in_dim(w_vec, idx, 0, keepdims=False)
            loss_vec = jax.lax.dynamic_update_index_in_dim(
                loss_vec, jnp.where(write, ls, lv), idx, 0)
            w_vec = jax.lax.dynamic_update_index_in_dim(
                w_vec, jnp.where(write, ws, wv), idx, 0)
            # plan-ordered P2P hand-off (last stage's send is dropped;
            # stage 0 receives zeros it never reads)
            buf = jax.lax.ppermute(h, axis, perm=fwd)
            return buf, stash, loss_vec, w_vec

        buf0 = jnp.zeros(h_spec.shape, h_spec.dtype)
        stash0 = jnp.zeros((n_micro,) + tuple(h_spec.shape), h_spec.dtype)
        zvec = jnp.zeros((n_micro,), jnp.float32)
        _, stash, loss_vec, w_vec = jax.lax.fori_loop(
            0, n_ticks, fwd_tick, (buf0, stash0, zvec, zvec))

        # ------------------------- backward ring ------------------------
        # stage s handles micro-batch m = u - (last - s) at tick u, so the
        # cotangent it needs arrived from stage s+1 (which handled the same
        # m one tick earlier) via the reversed ppermute.
        def bwd_tick(u, carry):
            gbuf, gw_acc, gsh_acc = carry
            m = u - (last - stage)
            valid = (m >= 0) & (m < n_micro)
            idx = jnp.clip(m, 0, n_micro - 1)
            b = slice_mb(m)
            x = jax.lax.dynamic_index_in_dim(stash, idx, 0, keepdims=False)

            def f(w_, shared_, x_):
                return stage_fn(w_, shared_, x_, b, stage, last)

            (h, ls, ws), vjp = jax.vjp(f, w, shared, x)
            g_h = jnp.where(stage == last, jnp.zeros_like(h), gbuf)
            g_ls = jnp.where((stage == last) & valid, 1.0, 0.0).astype(
                ls.dtype)
            d_w, d_sh, d_x = vjp((g_h, g_ls, jnp.zeros_like(ws)))
            gw_acc = jax.tree.map(
                lambda a, g: a + jnp.where(valid, g, jnp.zeros_like(g)),
                gw_acc, d_w)
            gsh_acc = jax.tree.map(
                lambda a, g: a + jnp.where(valid, g, jnp.zeros_like(g)),
                gsh_acc, d_sh)
            gbuf = jax.lax.ppermute(
                jnp.where(valid, d_x, jnp.zeros_like(d_x)), axis, perm=rev)
            return gbuf, gw_acc, gsh_acc

        _, gw, gsh = jax.lax.fori_loop(
            0, n_ticks, bwd_tick,
            (jnp.zeros(h_spec.shape, h_spec.dtype),
             jax.tree.map(jnp.zeros_like, w),
             jax.tree.map(jnp.zeros_like, shared)))

        # loss/weight live only on the last stage; shared-param grads are
        # summed across stages in mesh order (= merge_stage_grads order)
        loss_vec = jax.lax.psum(loss_vec, axis)
        w_vec = jax.lax.psum(w_vec, axis)
        gsh = jax.lax.psum(gsh, axis)
        gw = jax.tree.map(lambda a: a[None], gw)
        return loss_vec, w_vec, gw, gsh

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P(), P())
    out_specs = (P(), P(), P(axis), P())
    run = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)
    return run(stage_params, shared_params, batch_stack)


def execute_plan(plan: ExecutionPlan, callbacks: list[StageCallbacks],
                 timeout: float = 60.0) -> None:
    """Host-plane entry point: interpret a (possibly ragged) ExecutionPlan
    with the threaded stage executor. Thin alias over
    :class:`~repro.core.executor.PipelineExecutor`.

    This is the low-level form; prefer the unified
    :class:`repro.dist.backend.ExecutionBackend` protocol —
    ``ThreadsBackend.execute_plan(plan, callbacks=...)`` is this call, and
    the same signature with ``params=/batches=`` runs either plane."""
    PipelineExecutor(plan, callbacks, timeout=timeout).run()
