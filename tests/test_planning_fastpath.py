"""Vectorized planning fast path: dp_split == dp_split_reference, batched
cost models == scalar cost models, LUT caching, process-pool planning."""
import pickle

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_arch
from repro.core.cost_model import AnalyticCostModel, CostModel, ProfiledCostModel
from repro.core.instructions import InstructionStore, RecomputePolicy
from repro.core.microbatch import (GroupCostLUT, dp_split, dp_split_reference,
                                   group_cost_lut, iteration_time,
                                   order_samples)
from repro.core.planner import PlannerConfig, PlannerPool
from repro.core.recompute import BWD_OVERHEAD, cost_model_for
from repro.core.shapes import ShapePalette

CFG = get_arch("gpt-paper")
PAL = ShapePalette.build(min_seq=32, max_seq=4096, seq_align=32, max_mbs=64)


class ToyCost(CostModel):
    """Scalar-only model: exercises the base-class stage_times_batch loop."""

    def stage_fwd_time(self, mbs, seq, tp=1):
        s = seq if not isinstance(seq, tuple) else sum(seq)
        return float(mbs * s) + 1e-3

    def stage_act_memory(self, mbs, seq, tp=1):
        s = seq if not isinstance(seq, tuple) else sum(seq)
        return float(mbs * s)


def _assert_same_split(a, b, c, dp):
    assert iteration_time(a, c, dp) == iteration_time(b, c, dp)
    assert [m.indices for m in a] == [m.indices for m in b]
    assert ([(m.mbs, m.seq, m.t_fwd, m.t_bwd, m.mem) for m in a]
            == [(m.mbs, m.seq, m.t_fwd, m.t_bwd, m.mem) for m in b])


# ----------------------------------------------------------------------
# dp_split fast path == reference
# ----------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=3900), min_size=1, max_size=48),
       st.integers(min_value=1, max_value=6),
       st.integers(min_value=1, max_value=3),
       st.booleans(), st.booleans(), st.booleans(), st.booleans())
def test_fast_matches_reference(lengths, c, dp, use_palette, use_analytic,
                                two_d, tight_mem):
    rng = np.random.default_rng(len(lengths) * 31 + c)
    L = np.sort(np.asarray(lengths))
    if two_d:
        L = np.stack([L, np.sort(rng.integers(0, 2000, len(L)))], axis=1)
    cost = AnalyticCostModel(CFG, n_stages=c) if use_analytic else ToyCost()
    mem_limit = float("inf")
    if tight_mem:
        # tight but single-sample feasible (the DP's hard floor)
        worst = max(cost.stage_act_memory(1, (4096, 2048)),
                    cost.stage_act_memory(1, 4096))
        mem_limit = worst * 1.5
    kw = dict(mem_limit=mem_limit, dp_size=dp,
              palette=PAL if use_palette else None,
              t_max_interval=1e-9, max_group=16)
    fast = dp_split(L, cost, c, **kw)
    ref = dp_split_reference(L, cost, c, **kw)
    _assert_same_split(fast, ref, c, dp)


def test_fast_matches_reference_profiled():
    pm = ProfiledCostModel.profile(
        lambda m, s: (m * s * 1e-6, 2 * m * s * 1e-6, m * s * 4.0),
        mbs_grid=(1, 2, 4, 8, 16), seq_grid=(32, 128, 512, 2048))
    rng = np.random.default_rng(7)
    L = np.sort(np.clip(rng.lognormal(4.5, 1.0, 64).astype(int), 4, 4000))
    for pal in (None, PAL):
        fast = dp_split(L, pm, 4, palette=pal, t_max_interval=1e-9)
        ref = dp_split_reference(L, pm, 4, palette=pal, t_max_interval=1e-9)
        _assert_same_split(fast, ref, 4, 1)


def test_fast_matches_reference_default_interval():
    """The paper's 5us interval (coarse candidates) must also agree."""
    rng = np.random.default_rng(3)
    L = np.sort(np.clip(rng.lognormal(5.0, 1.1, 96).astype(int), 4, 2048))
    cm = AnalyticCostModel(CFG, n_stages=4)
    for pal in (None, ShapePalette.build(max_seq=2048)):
        fast = dp_split(L, cm, 4, palette=pal)
        ref = dp_split_reference(L, cm, 4, palette=pal)
        _assert_same_split(fast, ref, 4, 1)


def test_palette_overflow_single_sample_raises():
    small = ShapePalette.build(min_seq=32, max_seq=64, seq_align=32, max_mbs=8)
    L = np.array([16, 500])           # 500 > max bucket 64
    with pytest.raises(ValueError):
        dp_split(L, ToyCost(), 2, palette=small, t_max_interval=1e-9)
    with pytest.raises(ValueError):
        dp_split_reference(L, ToyCost(), 2, palette=small, t_max_interval=1e-9)


# ----------------------------------------------------------------------
# batched cost-model API
# ----------------------------------------------------------------------
def test_analytic_batch_bitwise_equals_scalar():
    """The batch path mirrors the scalar roofline expression-for-expression
    (deliberately not scalar-delegates-to-batch, so the scalar reference
    benchmark keeps its original cost profile) — this contract must hold
    bitwise for every registered architecture (attn/local/mamba/moe paths)."""
    from repro.configs.base import ARCH_IDS
    rng = np.random.default_rng(0)
    k = 32
    for arch in ARCH_IDS:
        cm = AnalyticCostModel(get_arch(arch), n_stages=4)
        mbs = rng.integers(1, 600, k)
        enc = rng.integers(1, 16384, k)
        dec = np.where(rng.random(k) < 0.5, 0, rng.integers(0, 8192, k))
        tf, tb, mem = cm.stage_times_batch(mbs, np.stack([enc, dec], axis=1))
        for i in range(k):
            s = (int(enc[i]), int(dec[i])) if dec[i] else int(enc[i])
            assert tf[i] == cm.stage_fwd_time(int(mbs[i]), s), arch
            assert tb[i] == cm.stage_bwd_time(int(mbs[i]), s), arch
            assert mem[i] == cm.stage_act_memory(int(mbs[i]), s), arch


def test_profiled_batch_equals_scalar_and_precomputed_logs():
    pm = ProfiledCostModel.profile(
        lambda m, s: (m * s * 1e-6, 2 * m * s * 1e-6, m * s * 4.0))
    assert np.array_equal(pm._log2_mbs_grid, np.log2(pm.mbs_grid))
    assert np.array_equal(pm._log2_seq_grid, np.log2(pm.seq_grid))
    rng = np.random.default_rng(1)
    mbs = rng.integers(1, 40, 32)
    seq = rng.integers(8, 2000, 32)
    tf, tb, mem = pm.stage_times_batch(mbs, seq)
    for i in range(32):
        assert tf[i] == pm.stage_fwd_time(int(mbs[i]), int(seq[i]))
        assert tb[i] == pm.stage_bwd_time(int(mbs[i]), int(seq[i]))
        assert mem[i] == pm.stage_act_memory(int(mbs[i]), int(seq[i]))


def test_cost_model_for_scales_batched_bwd():
    for policy, mult in BWD_OVERHEAD.items():
        cm = cost_model_for(CFG, 4, policy)
        tf, tb, _ = cm.stage_times_batch([8], [1024])
        assert tb[0] == mult * (2.0 * tf[0])
        assert tb[0] == cm.stage_bwd_time(8, 1024)


# ----------------------------------------------------------------------
# LUT cache behaviour
# ----------------------------------------------------------------------
def test_group_cost_lut_cache_hit_path():
    rng = np.random.default_rng(2)
    L = np.sort(np.clip(rng.lognormal(4.5, 1.0, 48).astype(int), 4, 4000))
    cm = AnalyticCostModel(CFG, n_stages=4)
    lut = group_cost_lut(cm)
    assert group_cost_lut(cm) is lut          # per-model singleton
    dp_split(L, cm, 4, palette=PAL, t_max_interval=1e-9)
    misses_after_first = lut.misses
    assert misses_after_first > 0 and len(lut) == misses_after_first
    hits_before = lut.hits
    dp_split(L, cm, 4, palette=PAL, t_max_interval=1e-9)
    # regression: the second identical iteration must be answered from cache
    assert lut.misses == misses_after_first
    assert lut.hits > hits_before


def test_group_cost_lut_registry_does_not_leak_models():
    import gc

    from repro.core import microbatch as mb
    rng = np.random.default_rng(6)
    L = np.sort(rng.integers(8, 512, 24))
    before = len(mb._GROUP_LUTS)
    for _ in range(3):
        cm = AnalyticCostModel(CFG, n_stages=2)
        dp_split(L, cm, 2, t_max_interval=1e-9, max_group=8)
        del cm
    gc.collect()
    # LUTs hold their model weakly, so dead models must leave the registry
    assert len(mb._GROUP_LUTS) <= before


def test_group_cost_lut_values_match_direct_calls():
    cm = AnalyticCostModel(CFG, n_stages=2)
    lut = GroupCostLUT(cm)
    cnt = np.array([1, 8, 64], dtype=np.int64)
    enc = np.array([128, 512, 2048], dtype=np.int64)
    dec = np.array([0, 256, 0], dtype=np.int64)
    tf, tb, mem = lut.lookup(cnt, enc, dec)
    tf2, tb2, mem2 = lut.lookup(cnt, enc, dec)   # pure hit path
    assert lut.hits == 3 and lut.misses == 3
    for arrs in ((tf, tf2), (tb, tb2), (mem, mem2)):
        assert np.array_equal(*arrs)
    for i in range(3):
        s = (int(enc[i]), int(dec[i])) if dec[i] else int(enc[i])
        assert tf[i] == cm.stage_fwd_time(int(cnt[i]), s)


# ----------------------------------------------------------------------
# ordering + pools
# ----------------------------------------------------------------------
def test_tsp_ordering_valid_and_deterministic():
    rng = np.random.default_rng(4)
    L = np.stack([rng.integers(1, 2048, 300), rng.integers(0, 512, 300)], 1)
    o1 = order_samples(L, "tsp")
    o2 = order_samples(L, "tsp")
    assert sorted(o1.tolist()) == list(range(300))
    assert np.array_equal(o1, o2)
    # greedy tour starts at the smallest total-length sample
    assert o1[0] == int(np.argmin(L.sum(1)))


def test_planner_pool_process_backend():
    rng = np.random.default_rng(5)
    lengths = np.sort(np.clip(rng.lognormal(5.0, 1.1, 32).astype(int), 4, 2048))
    cm = AnalyticCostModel(CFG, n_stages=2)
    pcfg = PlannerConfig(n_stages=2, d_model=CFG.d_model,
                         palette=ShapePalette.build(max_seq=2048))
    # everything a process-pool submission pickles must round-trip
    for obj in (cm, pcfg, cost_model_for(CFG, 2, RecomputePolicy.FULL)):
        assert pickle.loads(pickle.dumps(obj)) is not None
    store = InstructionStore()
    pool = PlannerPool(store, n_workers=2, use_processes=True)
    try:
        futs = [pool.submit(i, lengths, cm, pcfg) for i in range(2)]
        for i, f in enumerate(futs):
            it = f.result(timeout=300)
            assert it.replica_plans[0].n_stages == 2
            assert store.fetch(i, timeout=60).n_stages == 2
    finally:
        pool.shutdown()


def test_planner_process_workers_run_on_cpu(monkeypatch):
    """Planner worker processes never reach for a device: they start with
    JAX_PLATFORMS=cpu even when the parent's environment has none, so on
    a TPU host they cannot take the chip the parent holds."""
    import os

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    pool = PlannerPool(InstructionStore(), n_workers=1, use_processes=True)
    try:
        assert pool.pool.submit(os.getenv, "JAX_PLATFORMS").result(
            timeout=300) == "cpu"
    finally:
        pool.shutdown()
