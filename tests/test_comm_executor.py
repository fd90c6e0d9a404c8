"""Communication planning (§6) and the threaded instruction executor:
deadlock-freedom by construction, deadlock reproduction for naive plans,
and pipeline-vs-sequential gradient equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_arch, reduced
from repro.core import comm_plan
from repro.core.cost_model import AnalyticCostModel
from repro.core.executor import DeadlockError, PipelineExecutor, StageCallbacks
from repro.core.instructions import ExecutionPlan, MicroBatchSpec
from repro.core.planner import PlannerConfig, plan_iteration
from repro.core.schedule import schedule_adaptive
from repro.core.shapes import ShapePalette
from repro.core.simulator import simulate
from repro.data.dataset import materialize_micro_batch
from repro.data.synthetic import MultiTaskDataset
from repro.models import model as MD
from repro.train.pipeline_adapter import PipelinedModel, _xent_sum


def _random_scenario(seed):
    rng = np.random.default_rng(seed)
    m, c = int(rng.integers(4, 10)), int(rng.integers(3, 6))
    tf = rng.uniform(0.5, 5.0, size=(m, c))
    am = rng.uniform(0.5, 2.0, size=(m, c))
    order = schedule_adaptive(m, c, am, float(am.sum()))
    sim = simulate(order, tf, 2 * tf, act_mem=am)
    specs = [MicroBatchSpec(i, [i], 1, 64, float(tf[i, 0]), 2 * float(tf[i, 0]),
                            float(am[i, 0])) for i in range(m)]
    return order, sim, specs


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000))
def test_planned_comm_always_consistent(seed):
    """§6 guarantee: co-scheduled send/recv order is identical on both ends
    of every stage pair — for any schedule/time profile."""
    order, sim, specs = _random_scenario(seed)
    streams = comm_plan.build_instructions(order, specs, sim, d_model=8)
    assert comm_plan.check_order_consistency(streams) == []


def test_naive_comm_frequently_inconsistent():
    bad = 0
    for seed in range(30):
        order, sim, specs = _random_scenario(seed)
        naive = comm_plan.build_instructions(order, specs, sim, d_model=8,
                                             naive=True)
        if comm_plan.check_order_consistency(naive):
            bad += 1
    assert bad >= 20, f"expected most naive plans inconsistent, got {bad}/30"


def _dummy_callbacks(c):
    def fwd(j):
        def f(mb, h_in=None):
            return jnp.zeros((2, 2)) if j + 1 < c else None
        return f

    def bwd(j):
        def b(mb, g):
            return jnp.zeros((2, 2)) if j > 0 else None
        return b
    return [StageCallbacks(fwd(j), bwd(j), lambda: None) for j in range(c)]


def test_executor_deadlocks_on_naive_plan():
    """The rendezvous in-order channels reproduce the paper's Fig. 8
    deadlock when fed a naive plan, and run clean on the §6 plan."""
    for seed in range(30):
        order, sim, specs = _random_scenario(seed)
        naive = comm_plan.build_instructions(order, specs, sim, d_model=8,
                                             naive=True)
        if not comm_plan.check_order_consistency(naive):
            continue
        c = len(order)
        plan = ExecutionPlan(n_stages=c, micro_batches=specs, per_stage=naive)
        with pytest.raises(DeadlockError):
            PipelineExecutor(plan, _dummy_callbacks(c), timeout=1.0).run()
        good = comm_plan.build_instructions(order, specs, sim, d_model=8)
        plan2 = ExecutionPlan(n_stages=c, micro_batches=specs, per_stage=good)
        PipelineExecutor(plan2, _dummy_callbacks(c), timeout=10.0).run()
        return
    pytest.skip("no inconsistent naive scenario found")


@pytest.mark.parametrize("n_stages", [2, 4])
def test_pipeline_grads_match_sequential(n_stages):
    """End-to-end: threaded DynaPipe executor == sequential accumulation."""
    cfg = dataclasses.replace(reduced(get_arch("gpt-paper")), n_layers=4)
    ds = MultiTaskDataset(n_tasks=8, max_len=96, seed=1)
    lengths, tokens, _ = ds.sample_minibatch(24, cfg.vocab)
    cm = AnalyticCostModel(cfg, n_stages=n_stages)
    pal = ShapePalette.build(min_seq=16, max_seq=128, seq_align=16, max_mbs=8)
    pcfg = PlannerConfig(n_stages=n_stages, device_mem=1e12,
                         d_model=cfg.d_model, palette=pal)
    it = plan_iteration(lengths[:, 0], cm, pcfg)
    plan = it.replica_plans[0]
    assert len(plan.micro_batches) >= 2
    batches = {m.mb_id: materialize_micro_batch(m, tokens)
               for m in plan.micro_batches}
    params = MD.init_params(jax.random.PRNGKey(0), cfg)

    pm = PipelinedModel(cfg, params, n_stages=n_stages)
    cbs, result = pm.make_callbacks(plan, batches)
    PipelineExecutor(plan, cbs, timeout=60).run()
    grads_pipe = pm.merge_stage_grads(result["stage_grads"])
    loss_pipe = float(result["loss_sum"]) / result["weight_sum"]

    def ref_loss(p, b):
        h, _, _ = MD.forward(p, b, cfg, mode="train")
        return _xent_sum(p.get("head", p["embed"]), h, b["labels"],
                         b["loss_weights"], cfg)

    gacc, ls, ws = None, 0.0, 0.0
    for b in batches.values():
        b = {k: jnp.asarray(v) for k, v in b.items()}
        (l, w), g = jax.value_and_grad(ref_loss, has_aux=True)(params, b)
        ls += float(l)
        ws += float(w)
        gacc = g if gacc is None else jax.tree.map(jnp.add, gacc, g)

    assert abs(loss_pipe - ls / ws) < 1e-5
    for a, b in zip(jax.tree.leaves(grads_pipe), jax.tree.leaves(gacc)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        denom = max(np.abs(b).max(), 1e-6)
        assert np.abs(a - b).max() / denom < 2e-2


def test_execution_plan_roundtrip():
    order, sim, specs = _random_scenario(3)
    streams = comm_plan.build_instructions(order, specs, sim, d_model=8)
    plan = ExecutionPlan(n_stages=len(order), micro_batches=specs,
                         per_stage=streams, predicted_makespan=sim.makespan,
                         predicted_peak_mem=sim.peak_mem)
    plan2 = ExecutionPlan.from_json(plan.to_json())
    assert plan2.n_stages == plan.n_stages
    assert [i.op for s in plan2.per_stage for i in s] == \
           [i.op for s in plan.per_stage for i in s]
    assert [m.mb_id for m in plan2.micro_batches] == \
           [m.mb_id for m in plan.micro_batches]
