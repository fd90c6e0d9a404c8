"""End-to-end training throughput: padding vs packing vs dynamic micro-batching.

The paper's bottom line (Fig. 11/15) is that per-iteration dynamic
micro-batching beats static padding and packing on heavy-tailed multi-task
workloads. This benchmark measures it on real JAX CPU compute over the
deterministic skewed-length ``MultiTaskStream``, in two scenarios:

- ``--scenario gpt`` — decoder-only causal LM (default).
- ``--scenario t5``  — the paper's flagship **encoder-decoder** workload:
  2D (enc, dec) lengths, separate padded enc/dec arrays, dec-side loss,
  and the dynamic mode running the enc-dec *pipeline* (encoder stages
  feeding decoder+cross-attention stages through the threaded executor).

Modes per scenario:

- **padding**  — every sample padded to the stream max length(s), fixed
  micro-batch rows (the naive baseline of paper §2.1).
- **packing**  — first-fit-decreasing packing into max-length rows
  (the MLM+DS baseline, §2.2), segment-ids prevent cross-attention; the
  t5 variant packs (enc, dec) pairs with matched segment ids on both sides.
- **dynamic**  — the plan-ahead runtime (``train/runner.PlanAheadRunner``):
  DP micro-batching over a ``ShapePalette``, planning double-buffered
  behind execution; reports the planner-overlap fraction and
  compiled-step cache stats.

All modes run the same model, optimizer, and stream, twice over the same
batch set (epoch 0 warms compiles and plans; epoch 1 is timed), and report
**real tokens/sec** — non-pad tokens processed per wall second, the number
that actually pays for gradients. Records go to ``BENCH_e2e.json`` /
``BENCH_e2e_t5.json`` (``--smoke``: a smaller grid to
``BENCH_e2e[_t5]_smoke.json``, used by CI and
``benchmarks/check_regression.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch, reduced
from repro.core.cost_model import AnalyticCostModel
from repro.core.instructions import MicroBatchSpec
from repro.core.packing import pack_encdec_first_fit, pack_first_fit
from repro.core.planner import PlannerConfig
from repro.core.shapes import ShapePalette
from repro.data.dataset import (
    materialize_micro_batch,
    materialize_packed_encdec_rows,
    materialize_packed_rows,
)
from repro.data.streams import MultiTaskStream, StreamConfig
from repro.models import model as MD
from repro.models import transformer as T
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state
from repro.train.runner import (
    PlanAheadRunner,
    RunnerConfig,
    build_encdec_grad_step,
    build_grad_step,
    model_cache_namespace,
)
from repro.train.step_cache import CompiledStepCache

REPO_ROOT = Path(__file__).resolve().parents[1]

MAX_LEN = 512
MAX_DEC = 128          # t5 scenario: stream dec lengths cap at max_len // 4
ROWS_PER_MB = 8


def bench_json_path(scenario: str, smoke: bool,
                    backend: str = "threads") -> Path:
    tag = "" if scenario == "gpt" else f"_{scenario}"
    if backend != "threads":
        tag += f"_{backend}"
    return REPO_ROOT / f"BENCH_e2e{tag}{'_smoke' if smoke else ''}.json"


class RepeatStream:
    """Replays ``inner.batch(it % period)`` so epoch 1 re-executes epoch 0's
    batches with warm compiles/plans — the steady state being measured."""

    def __init__(self, inner, period: int):
        self.inner = inner
        self.period = period

    def batch(self, iteration: int):
        return self.inner.batch(iteration % self.period)


def tiny_model(scenario: str = "gpt", vocab: int = 2048):
    if scenario == "t5":
        cfg = dataclasses.replace(reduced(get_arch("t5-paper")), n_layers=2)
        return dataclasses.replace(cfg, name="t5-bench-e2e", vocab=vocab,
                                   d_model=128, n_heads=4, d_head=32,
                                   d_ff=256)
    cfg = reduced(get_arch("gpt-paper"))
    return dataclasses.replace(cfg, name="gpt-bench-e2e", vocab=vocab,
                               d_model=128, n_heads=4, d_head=32, d_ff=256)


def make_stream(scenario: str, global_tokens: int, seed: int = 0):
    return MultiTaskStream(StreamConfig(
        n_tasks=32, global_tokens=global_tokens, max_len=MAX_LEN,
        vocab=2048, tail_fraction=0.1, tail_alpha=1.2,
        encdec_fraction=1.0 if scenario == "t5" else 0.0, seed=seed))


def _grad_fn(cache: CompiledStepCache, cfg, shape):
    # the runner's own step builders, so the bench measures the system's math
    key = ("grad", model_cache_namespace(cfg)) + shape
    build = build_encdec_grad_step if len(shape) == 3 else build_grad_step
    return cache.get(key, lambda: build(cfg))


def _padded_size(b) -> int:
    if "enc_tokens" in b:
        return int(np.prod(b["enc_tokens"].shape)
                   + np.prod(b["dec_tokens"].shape))
    return int(np.prod(b["tokens"].shape))


def _pad_rows(b: dict, pad: int) -> dict:
    """Append ``pad`` fully-masked rows so every micro-batch keeps one
    compiled shape (segment ids -1, everything else 0)."""
    return {k: np.concatenate(
        [v, np.repeat(v[-1:] * 0 + (-1 if k.endswith("segment_ids") else 0),
                      pad, axis=0)])
        for k, v in b.items()}


def _baseline_batches(mode: str, scenario: str, gb) -> list[dict]:
    encdec = scenario == "t5"
    if mode == "padding":
        idxs = list(range(gb.n_samples))
        chunks = [idxs[i:i + ROWS_PER_MB]
                  for i in range(0, len(idxs), ROWS_PER_MB)]
        seq = (MAX_LEN, MAX_DEC) if encdec else MAX_LEN
        return [materialize_micro_batch(
            MicroBatchSpec(mb_id=i, sample_indices=chunk, mbs=ROWS_PER_MB,
                           seq=seq, t_fwd=0.0, t_bwd=0.0, mem=0.0),
            gb.tokens, lengths=gb.lengths) for i, chunk in enumerate(chunks)]
    if mode == "packing":
        batches = []
        if encdec:
            rows = pack_encdec_first_fit(gb.lengths, MAX_LEN, MAX_DEC)
            for i in range(0, len(rows), ROWS_PER_MB):
                chunk = rows[i:i + ROWS_PER_MB]
                b = materialize_packed_encdec_rows(
                    chunk, gb.tokens, gb.lengths, MAX_LEN, MAX_DEC)
                if len(chunk) < ROWS_PER_MB:
                    b = _pad_rows(b, ROWS_PER_MB - len(chunk))
                batches.append(b)
            return batches
        rows = pack_first_fit(gb.lengths, MAX_LEN)
        for i in range(0, len(rows), ROWS_PER_MB):
            chunk = rows[i:i + ROWS_PER_MB]
            b = materialize_packed_rows(chunk, gb.tokens, MAX_LEN)
            if len(chunk) < ROWS_PER_MB:
                b = _pad_rows(b, ROWS_PER_MB - len(chunk))
            batches.append(b)
        return batches
    raise ValueError(mode)


def run_baseline(mode: str, stream, cfg, n_iters: int,
                 scenario: str = "gpt") -> dict:
    """Static baselines: fixed-shape micro-batches, same step math as the
    runner's sequential path. Two epochs; epoch 1 timed."""
    params = (T.init_encdec(jax.random.PRNGKey(0), cfg)
              if scenario == "t5" else MD.init_params(jax.random.PRNGKey(0),
                                                      cfg))
    opt_cfg = AdamWConfig(lr=3e-4)
    opt = init_opt_state(params, opt_cfg)
    cache = CompiledStepCache()
    wall = 0.0
    real_tokens = padded_tokens = 0
    losses = []
    for step in range(2 * n_iters):
        gb = stream.batch(step)
        batches = _baseline_batches(mode, scenario, gb)

        t0 = time.perf_counter()
        grads, loss_sum, w_sum = None, 0.0, 0.0
        for b in batches:
            jb = {k: jnp.asarray(v) for k, v in b.items()}
            # the runner's own shape convention, so cache keys stay in sync
            fn = _grad_fn(cache, cfg, PlanAheadRunner._batch_shape(jb))
            ls, ws, g = fn(params, jb)
            loss_sum += float(ls)
            w_sum += float(ws)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        grads = jax.tree.map(
            lambda g, w=w_sum: g * (1.0 / max(w, 1.0)), grads)
        params, opt, _ = adamw_update(params, grads, opt, opt_cfg)
        dt = time.perf_counter() - t0

        if step >= n_iters:  # epoch 1: timed
            wall += dt
            real_tokens += gb.total_tokens
            padded_tokens += sum(_padded_size(b) for b in batches)
            losses.append(loss_sum / max(w_sum, 1.0))
    return {
        "mode": mode,
        "iters": n_iters,
        "wall_s": round(wall, 4),
        "real_tokens": real_tokens,
        "padded_tokens": padded_tokens,
        "padding_efficiency": round(real_tokens / max(padded_tokens, 1), 4),
        "tokens_per_s": round(real_tokens / max(wall, 1e-9), 1),
        "loss_last": round(losses[-1], 4) if losses else None,
        "compiled_steps": len(cache),
    }


def run_dynamic(stream, cfg, n_iters: int, lookahead: int = 1,
                n_stages: int = 1, use_executor: bool = False,
                backend: str = "threads") -> dict:
    """The plan-ahead runtime over the same stream (two epochs, 2nd timed).
    ``n_stages > 1`` with ``use_executor`` drives the threaded pipeline
    executor (the t5 scenario's enc-dec pipeline); ``backend="mesh"``
    compiles the plans into the shard_map+ppermute device plane."""
    cost = AnalyticCostModel(cfg, n_stages=n_stages)
    pal = ShapePalette.build(min_seq=64, max_seq=MAX_LEN, seq_align=64,
                             max_mbs=16)
    pcfg = PlannerConfig(n_stages=n_stages, d_model=cfg.d_model, palette=pal)
    rcfg = RunnerConfig(n_iters=2 * n_iters, lookahead=lookahead,
                        use_executor=use_executor, log_every=0,
                        backend=backend)
    cache = CompiledStepCache()
    runner = PlanAheadRunner(cfg, cost, pcfg, rcfg,
                             RepeatStream(stream, n_iters),
                             step_cache=cache)
    _, history, stats = runner.run()
    timed = history[n_iters:]
    wall = sum(h["time_s"] for h in timed)
    real_tokens = sum(h["tokens"] for h in timed)
    padded_tokens = sum(h["padded_tokens"] for h in timed)
    plan_wait = sum(h["plan_wait_s"] for h in timed)
    planning = sum(h["planning_s"] for h in timed)
    return {
        "mode": "dynamic",
        "iters": n_iters,
        "wall_s": round(wall, 4),
        "real_tokens": real_tokens,
        "padded_tokens": padded_tokens,
        "padding_efficiency": round(real_tokens / max(padded_tokens, 1), 4),
        "tokens_per_s": round(real_tokens / max(wall, 1e-9), 1),
        "planner_overlap_fraction": round(
            max(0.0, min(1.0, (planning - plan_wait) / planning))
            if planning > 0 else 0.0, 4),
        "plan_wait_s": round(plan_wait, 4),
        "planning_s": round(planning, 4),
        "cache": stats.cache,
        "loss_last": round(timed[-1]["loss"], 4) if timed else None,
        # mesh recompile bound: distinct compiled ring programs vs the
        # palette × log2(M)-buckets ceiling (check_regression gates on this)
        "mesh_steps_compiled": cache.count("mesh"),
        "mesh_step_bound": (
            len(pal.mbs_buckets) * len(pal.seq_buckets)
            * (int(np.log2(max(
                max((h["n_micro"] for h in history), default=1), 1))) + 1)),
    }


def main(smoke: bool = False, scenario: str = "gpt", stages: int = 0,
         backend: str = "threads"):
    n_iters = 4 if smoke else 12
    global_tokens = 4096 if smoke else 8192
    cfg = tiny_model(scenario)
    stream = make_stream(scenario, global_tokens)
    print(f"stream: {stream.length_stats(n_iters)}", flush=True)
    if backend == "mesh":
        if scenario != "gpt":
            raise SystemExit("backend=mesh runs the decoder-only scenario")
        if stages == 0:
            # as many pipeline stages as the device pool allows (CI forces
            # 4 virtual CPU devices via XLA_FLAGS)
            stages = max(s for s in (1, 2, 4, 8)
                         if s <= len(jax.devices()))
        cfg = dataclasses.replace(
            cfg, n_layers=stages * len(cfg.layer_pattern))
    elif stages == 0:
        # t5 default: the 2-stage enc-dec pipeline (encoder stage feeding
        # the decoder+cross-attn stage through the threaded executor)
        stages = 2 if scenario == "t5" else 1

    records = []
    for mode in ("padding", "packing"):
        rec = run_baseline(mode, RepeatStream(stream, n_iters), cfg, n_iters,
                           scenario=scenario)
        print(json.dumps(rec), flush=True)
        records.append(rec)
    rec = run_dynamic(stream, cfg, n_iters, n_stages=stages,
                      use_executor=backend == "threads" and stages > 1,
                      backend=backend)
    print(json.dumps(rec), flush=True)
    records.append(rec)

    by_mode = {r["mode"]: r for r in records}
    ratio = by_mode["dynamic"]["tokens_per_s"] / max(
        by_mode["padding"]["tokens_per_s"], 1e-9)
    summary = {
        "mode": "_summary",
        "scenario": scenario,
        "backend": backend,
        "n_stages": stages,
        "n_devices": len(jax.devices()),
        "dynamic_over_padding": round(ratio, 3),
        "dynamic_over_packing": round(
            by_mode["dynamic"]["tokens_per_s"]
            / max(by_mode["packing"]["tokens_per_s"], 1e-9), 3),
        "planner_overlap_fraction":
            by_mode["dynamic"]["planner_overlap_fraction"],
        "loss_last": by_mode["dynamic"]["loss_last"],
        "mesh_steps_compiled": by_mode["dynamic"]["mesh_steps_compiled"],
        "mesh_step_bound": by_mode["dynamic"]["mesh_step_bound"],
        "smoke": smoke,
    }
    print(json.dumps(summary), flush=True)
    records.append(summary)

    out = bench_json_path(scenario, smoke, backend)
    out.write_text(json.dumps(records, indent=2) + "\n")
    print(f"wrote {out}", flush=True)
    if backend == "mesh":
        # virtual devices timeshare the same cores, so mesh throughput vs
        # the single-device padding baseline is machine noise — gate the
        # machine-independent invariants instead (check_regression.py adds
        # the cross-run ratio non-degradation gate)
        if summary["mesh_steps_compiled"] > summary["mesh_step_bound"]:
            raise SystemExit(
                f"mesh recompiles {summary['mesh_steps_compiled']} exceed "
                f"palette bound {summary['mesh_step_bound']}")
        if summary["loss_last"] is None \
                or not np.isfinite(summary["loss_last"]):
            raise SystemExit("mesh backend produced a non-finite loss")
    elif ratio <= 1.0:
        raise SystemExit(
            f"dynamic micro-batching did NOT beat padding: {ratio:.3f}x")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI variant (writes BENCH_e2e*_smoke.json)")
    ap.add_argument("--scenario", choices=("gpt", "t5"), default="gpt",
                    help="gpt: decoder-only; t5: the paper's enc-dec "
                         "pipeline workload")
    ap.add_argument("--stages", type=int, default=0,
                    help="pipeline stages for the dynamic mode "
                         "(0 = scenario default: gpt 1, t5 2; mesh: as "
                         "many as the device pool divides)")
    ap.add_argument("--backend", choices=("threads", "mesh"),
                    default="threads",
                    help="execution backend for the dynamic mode "
                         "(mesh = compiled shard_map+ppermute pipeline)")
    main(**vars(ap.parse_args()))
