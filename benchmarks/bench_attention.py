"""Attention-kernel benchmark: live-block fractions + fwd/bwd timing.

Two sections:

1. **Live-block fraction** (the gated metric — deterministic and
   machine-independent): run the real planner (order_samples -> dp_split
   over a ``ShapePalette``) on the deterministic skewed ``MultiTaskStream``,
   materialize every micro-batch's positions/segment ids, and evaluate the
   *exact block-skip predicate the Pallas kernels gate compute on*
   (``repro.kernels.flash_attention.live_block_mask``) over the
   (q-block, kv-block) grid. Reported per pass:

     - ``fwd``      — the forward kernel's grid,
     - ``bwd_dq``   — the q-major dq pass (same predicate),
     - ``bwd_dkv``  — the kv-major dk/dv pass (same predicate);

   backward runs the predicate twice over ~2x the FLOPs, so cross-sample
   skipping there is worth double the forward's savings. All three passes
   carry the same per-(q-block, kv-block) predicate by construction, so
   their fractions coincide; that the compiled kernels *enforce* it is
   proven by the NaN-poisoning test in ``tests/test_kernel_grads.py``.
   The padded pad-to-max baseline batch is reported alongside for
   contrast. These numbers depend only on (stream config, palette, cost
   model) — never on the machine — and are regression-gated by
   ``benchmarks/check_regression.py`` against
   ``benchmarks/baselines/BENCH_attention_smoke.json``.

2. **Timing** (informational, NOT gated — tracks host speed): best-of-k
   wall time of ``ops.attention`` forward and ``jax.grad`` fwd+bwd per
   impl. ``ref`` always runs; the kernel impl is ``pallas`` on TPU and
   ``interpret`` elsewhere (the interpreter measures kernel *semantics*,
   not speed). ``REPRO_KERNEL_IMPL`` narrows the set.

Usage:
    python -m benchmarks.bench_attention            # full grid
    python -m benchmarks.bench_attention --smoke    # CI variant
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch, reduced
from repro.core.cost_model import AnalyticCostModel
from repro.core.planner import PlannerConfig, plan_iteration
from repro.core.shapes import ShapePalette
from repro.data.dataset import materialize_micro_batch
from repro.data.streams import MultiTaskStream, StreamConfig
from repro.kernels import ops
from repro.kernels.flash_attention import live_block_mask, shrink_block

REPO_ROOT = Path(__file__).resolve().parents[1]

MAX_LEN = 512
BLOCK = 128


def make_stream(global_tokens: int, seed: int = 0) -> MultiTaskStream:
    return MultiTaskStream(StreamConfig(
        n_tasks=32, global_tokens=global_tokens, max_len=MAX_LEN,
        vocab=2048, tail_fraction=0.1, tail_alpha=1.2, seed=seed))


def planner_micro_batches(stream, n_iters: int):
    """Plan ``n_iters`` iterations and materialize every micro-batch's
    (positions, segment_ids) — the shapes the training kernels actually
    see."""
    cost = AnalyticCostModel(reduced(get_arch("gpt-paper")), n_stages=1)
    pal = ShapePalette.build(min_seq=64, max_seq=MAX_LEN, seq_align=64,
                             max_mbs=16)
    pcfg = PlannerConfig(n_stages=1, d_model=128, palette=pal)
    out = []
    for it in range(n_iters):
        gb = stream.batch(it)
        plan = plan_iteration(gb.lengths, cost, pcfg)
        for rp in plan.replica_plans:
            for spec in rp.micro_batches:
                out.append(materialize_micro_batch(spec, gb.tokens))
    return out


def padded_batches(stream, n_iters: int, rows_per_mb: int = 8):
    """The pad-to-max baseline: same samples, every row padded to
    MAX_LEN, fixed row count per micro-batch."""
    out = []
    for it in range(n_iters):
        gb = stream.batch(it)
        n = len(gb.tokens)
        for lo in range(0, n, rows_per_mb):
            rows = gb.tokens[lo:lo + rows_per_mb]
            b = len(rows)
            pos = np.zeros((b, MAX_LEN), np.int32)
            seg = np.full((b, MAX_LEN), -1, np.int32)
            for r, tok in enumerate(rows):
                ln = min(len(tok), MAX_LEN)
                pos[r, :ln] = np.arange(ln)
                seg[r, :ln] = 0
            out.append({"positions": pos, "segment_ids": seg})
    return out


def live_block_stats(batches, block_q: int, block_kv: int) -> dict:
    """Aggregate (q-block, kv-block) pair liveness across micro-batches
    under the kernels' skip predicate. Pairs are weighted by their block
    area so differently-bucketed micro-batches aggregate fairly (the
    metric is then "fraction of masked-score elements whose block reaches
    the MXU"). ``live_over_ideal`` normalizes the surviving block area by
    the exact causal per-segment work Σ l·(l+1)/2 — the quadratic-overhead
    multiple the kernels actually pay after block skipping (1.0 = perfect;
    without skipping, padding pays the full grid)."""
    total = 0
    live = 0
    ideal = 0
    for mb in batches:
        pos = mb["positions"]
        seg = np.asarray(mb["segment_ids"])
        t = pos.shape[1]
        bq = shrink_block(t, block_q)
        bk = shrink_block(t, block_kv)
        mask = live_block_mask(pos, pos, seg, seg, causal=True,
                               block_q=bq, block_kv=bk)
        area = bq * bk
        total += mask.size * area
        live += int(mask.sum()) * area
        for row in seg:
            for sid in np.unique(row[row >= 0]):
                ln = int((row == sid).sum())
                ideal += ln * (ln + 1) // 2
    frac = live / max(total, 1)
    return {
        "pairs_weighted_total": total,
        "pairs_weighted_live": live,
        "ideal_causal_elems": ideal,
        "live_over_ideal": live / max(ideal, 1),
        "fwd": {"live_fraction": frac},
        # dq is q-major, dk/dv kv-major over the q-head group — both carry
        # the forward's predicate per (q-block, kv-block) pair, so the
        # skipped fraction is identical in every pass; backward just runs
        # it twice over ~2x the FLOPs.
        "bwd_dq": {"live_fraction": frac},
        "bwd_dkv": {"live_fraction": frac},
    }


def timing_section(smoke: bool) -> list[dict]:
    b, t, h, d, kv = (2, 256, 4, 32, 2) if smoke else (4, 512, 8, 64, 4)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, kv, d))
    v = jax.random.normal(ks[2], (b, t, kv, d))
    ct = jax.random.normal(ks[3], (b, t, h, d))
    seg = np.zeros((b, t), np.int32)
    seg[:, 3 * t // 4:] = -1
    seg = jnp.asarray(seg)

    kernel_impl = "pallas" if jax.default_backend() == "tpu" else "interpret"
    impls = ["ref", kernel_impl]

    records = []
    for impl in impls:
        def fwd(q, k, v, impl=impl):
            return ops.attention(q, k, v, impl=impl, q_segment_ids=seg,
                                 kv_segment_ids=seg, block_q=BLOCK,
                                 block_kv=BLOCK)

        def loss(q, k, v):
            return jnp.sum(fwd(q, k, v) * ct)

        f_jit = jax.jit(fwd)
        g_jit = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        jax.block_until_ready(f_jit(q, k, v))       # compile
        jax.block_until_ready(g_jit(q, k, v))
        reps = 2 if impl == "interpret" else 5
        tf = min(_timed(lambda f=f_jit: f(q, k, v)) for _ in range(reps))
        tg = min(_timed(lambda g=g_jit: g(q, k, v)) for _ in range(reps))
        records.append({
            "impl": impl, "b": b, "t": t, "h": h, "d": d, "kv_heads": kv,
            "fwd_s": tf, "fwd_bwd_s": tg,
            "note": ("interpreter semantics, not kernel speed"
                     if impl == "interpret" else ""),
        })
        print(f"[timing] {impl:9s} fwd {tf * 1e3:8.2f} ms   "
              f"fwd+bwd {tg * 1e3:8.2f} ms")
    return records


def _timed(fn) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI variant: smaller grid, separate JSON")
    ap.add_argument("--no-timing", action="store_true",
                    help="skip the (informational) timing section")
    args = ap.parse_args()

    n_iters = 2 if args.smoke else 8
    global_tokens = 8192 if args.smoke else 32768
    stream = make_stream(global_tokens)

    dyn = planner_micro_batches(stream, n_iters)
    pad = padded_batches(stream, n_iters)

    scenarios = []
    for name, batches in (("dynamic", dyn), ("padding", pad)):
        stats = live_block_stats(batches, BLOCK, BLOCK)
        rec = {"name": name, "block_q": BLOCK, "block_kv": BLOCK,
               "n_micro_batches": len(batches), **stats}
        scenarios.append(rec)
        print(f"[live-blocks] {name:8s} mbs={len(batches):3d}  "
              f"fwd {stats['fwd']['live_fraction']:.4f}  "
              f"bwd_dq {stats['bwd_dq']['live_fraction']:.4f}  "
              f"bwd_dkv {stats['bwd_dkv']['live_fraction']:.4f}  "
              f"live/ideal {stats['live_over_ideal']:.3f}")

    record = {
        "max_len": MAX_LEN,
        "n_iters": n_iters,
        "global_tokens": global_tokens,
        "scenarios": scenarios,
        "timing": [] if args.no_timing else timing_section(args.smoke),
    }
    out = REPO_ROOT / ("BENCH_attention_smoke.json" if args.smoke
                       else "BENCH_attention.json")
    out.write_text(json.dumps(record, indent=2) + "\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
