"""Planner end-to-end + training loop integration."""
import dataclasses

import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.core.cost_model import AnalyticCostModel
from repro.core.instructions import InstructionStore, Op, RecomputePolicy
from repro.core.planner import (PlannerConfig, PlannerPool, plan_iteration,
                                plan_iteration_dynamic_recompute)
from repro.core.shapes import ShapePalette
from repro.train.loop import LoopConfig, train
from repro.train.optimizer import AdamWConfig


def _lengths(n=48, seed=0, max_len=2048):
    rng = np.random.default_rng(seed)
    return np.sort(np.clip(rng.lognormal(5.0, 1.1, n).astype(int), 4, max_len))


def test_plan_iteration_covers_all_samples():
    cfg = get_arch("gpt-paper")
    cm = AnalyticCostModel(cfg, n_stages=4)
    pcfg = PlannerConfig(n_stages=4, dp_size=2, d_model=cfg.d_model,
                         palette=ShapePalette.build(max_seq=2048))
    it = plan_iteration(_lengths(), cm, pcfg)
    seen = sorted(i for m in it.micro_batches for i in it.ordering[m.indices])
    assert seen == list(range(48))
    assert 0 < it.padding_efficiency <= 1
    assert len(it.replica_plans) == 2
    for plan in it.replica_plans:
        ops = [i.op for s in plan.per_stage for i in s]
        assert Op.FORWARD in ops and Op.BACKWARD in ops
        assert plan.predicted_makespan > 0


def test_plan_respects_memory():
    cfg = get_arch("gpt-paper")
    cm = AnalyticCostModel(cfg, n_stages=4)
    pcfg = PlannerConfig(n_stages=4, device_mem=2e9, d_model=cfg.d_model,
                         palette=ShapePalette.build(max_seq=2048))
    it = plan_iteration(_lengths(), cm, pcfg)
    for plan in it.replica_plans:
        assert max(plan.predicted_peak_mem) <= 2e9 * 1.001


def test_dynamic_recompute_picks_cheapest_that_fits():
    cfg = get_arch("gpt-paper")
    pcfg = PlannerConfig(n_stages=4, device_mem=64e9, d_model=cfg.d_model,
                         palette=ShapePalette.build(max_seq=2048))
    it = plan_iteration_dynamic_recompute(_lengths(), cfg, pcfg)
    pol_loose = it.replica_plans[0].recompute
    pcfg2 = dataclasses.replace(pcfg, device_mem=1.2e9)
    it2 = plan_iteration_dynamic_recompute(_lengths(), cfg, pcfg2)
    pol_tight = it2.replica_plans[0].recompute
    order = [RecomputePolicy.NONE, RecomputePolicy.SELECTIVE, RecomputePolicy.FULL]
    assert order.index(pol_tight) >= order.index(pol_loose)


def test_planner_pool_overlap():
    cfg = get_arch("gpt-paper")
    cm = AnalyticCostModel(cfg, n_stages=2)
    pcfg = PlannerConfig(n_stages=2, d_model=cfg.d_model,
                         palette=ShapePalette.build(max_seq=2048))
    store = InstructionStore()
    pool = PlannerPool(store, n_workers=2)
    for it in range(3):
        pool.submit(it, _lengths(seed=it), cm, pcfg)
    for it in range(3):
        plan = store.fetch(it, timeout=60)
        assert plan.n_stages == 2
    pool.shutdown()


@pytest.mark.slow
def test_training_loss_decreases_sequential():
    cfg = dataclasses.replace(reduced(get_arch("gpt-paper")), n_layers=2)
    cm = AnalyticCostModel(cfg, n_stages=1)
    pal = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32, max_mbs=16)
    pcfg = PlannerConfig(n_stages=1, d_model=cfg.d_model, palette=pal)
    lcfg = LoopConfig(n_iters=30, global_tokens=2048, use_executor=False,
                      log_every=0)
    _, hist = train(cfg, cm, pcfg, lcfg, opt_cfg=AdamWConfig(lr=1e-2))
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first - 0.2, (first, last)


@pytest.mark.slow
def test_training_with_pipeline_executor():
    cfg = dataclasses.replace(reduced(get_arch("gpt-paper")), n_layers=2)
    cm = AnalyticCostModel(cfg, n_stages=2)
    pal = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32, max_mbs=8)
    pcfg = PlannerConfig(n_stages=2, d_model=cfg.d_model, palette=pal)
    lcfg = LoopConfig(n_iters=6, global_tokens=1024, use_executor=True,
                      log_every=0)
    _, hist = train(cfg, cm, pcfg, lcfg, opt_cfg=AdamWConfig(lr=1e-2))
    assert all(np.isfinite(h["loss"]) for h in hist)


@pytest.mark.slow
def test_checkpoint_restart_resumes(tmp_path):
    cfg = dataclasses.replace(reduced(get_arch("gpt-paper")), n_layers=2)
    cm = AnalyticCostModel(cfg, n_stages=1)
    pal = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32, max_mbs=16)
    pcfg = PlannerConfig(n_stages=1, d_model=cfg.d_model, palette=pal)
    lcfg = LoopConfig(n_iters=4, global_tokens=1024, use_executor=False,
                      ckpt_dir=str(tmp_path), ckpt_every=2, log_every=0)
    train(cfg, cm, pcfg, lcfg)
    # restart: loop resumes from step 4
    lcfg2 = dataclasses.replace(lcfg, n_iters=2)
    _, hist = train(cfg, cm, pcfg, lcfg2)
    assert hist[0]["iter"] == 4

