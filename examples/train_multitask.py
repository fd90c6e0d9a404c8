"""End-to-end driver: train a ~100M-param GPT on a deterministic multi-task
stream with the full DynaPipe stack — the plan-ahead runtime double-buffers
planning (dp_split -> adaptive schedule -> comm plan -> instruction lowering
for iteration k+1 while k executes), micro-batch shapes are palette-bucketed
so compiled steps are cached, and the threaded pipeline executor runs the
per-stage instruction streams.

    PYTHONPATH=src python examples/train_multitask.py [--iters 200] [--small]

``--small`` shrinks to a seconds-scale smoke configuration; ``--sync``
disables plan-ahead (inline planning — same losses, no overlap);
``--processes`` plans in worker processes instead of threads.
"""
import argparse
import dataclasses

from repro.configs.base import ArchConfig, LayerSpec
from repro.core.cost_model import AnalyticCostModel
from repro.core.planner import PlannerConfig
from repro.core.shapes import ShapePalette
from repro.data.streams import MultiTaskStream, StreamConfig
from repro.train.optimizer import AdamWConfig
from repro.train.runner import PlanAheadRunner, RunnerConfig


def model_100m() -> ArchConfig:
    # ~105M params: 8L, d=512, 8H, ffn 2048, vocab 32k (GPT-2-small-ish)
    return ArchConfig(
        name="gpt-100m", family="dense", n_layers=8, d_model=512,
        n_heads=8, n_kv_heads=8, d_head=64, d_ff=2048, vocab=32000,
        layer_pattern=(LayerSpec("attn"),), mlp_gated=False, act="gelu",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--sync", action="store_true",
                    help="plan inline instead of plan-ahead")
    ap.add_argument("--processes", action="store_true",
                    help="PlannerPool process backend (true CPU overlap)")
    ap.add_argument("--lookahead", type=int, default=1)
    ap.add_argument("--impl", default=None,
                    choices=["pallas", "interpret", "ref"],
                    help="kernel impl for every fwd/bwd step (default: "
                         "kernels.default_impl(), i.e. pallas on TPU, ref "
                         "elsewhere; REPRO_KERNEL_IMPL also overrides)")
    ap.add_argument("--ckpt-dir", default="/tmp/dynapipe_ckpt")
    args = ap.parse_args()

    cfg = model_100m()
    if args.small:
        cfg = dataclasses.replace(cfg, n_layers=2, d_model=128, n_heads=4,
                                  n_kv_heads=4, d_head=32, d_ff=512, vocab=2048)
        args.iters = min(args.iters, 30)
    print(f"model: {cfg.n_params()/1e6:.1f}M params, "
          f"{args.stages} pipeline stages, "
          f"{'synchronous' if args.sync else 'plan-ahead'} planning")

    max_seq = 512
    palette = ShapePalette.build(min_seq=32, max_seq=max_seq, seq_align=32,
                                 max_mbs=32)
    stream = MultiTaskStream(StreamConfig(
        n_tasks=16, global_tokens=8192, max_len=max_seq, vocab=cfg.vocab,
        tail_fraction=0.08, seed=0))
    print(f"stream: {stream.length_stats(4)}")

    cost = AnalyticCostModel(cfg, n_stages=args.stages)
    pcfg = PlannerConfig(n_stages=args.stages, device_mem=16e9,
                         d_model=cfg.d_model, palette=palette)
    rcfg = RunnerConfig(n_iters=args.iters, lookahead=args.lookahead,
                        synchronous=args.sync, use_processes=args.processes,
                        use_executor=args.stages > 1, impl=args.impl,
                        ckpt_dir=args.ckpt_dir, ckpt_every=50, log_every=10)
    runner = PlanAheadRunner(cfg, cost, pcfg, rcfg, stream,
                             opt_cfg=AdamWConfig(lr=3e-4))
    params, hist, stats = runner.run()

    first = sum(h["loss"] for h in hist[:10]) / min(10, len(hist))
    last = sum(h["loss"] for h in hist[-10:]) / min(10, len(hist))
    mb_counts = [h["n_micro"] for h in hist]
    print(f"\nloss {first:.3f} -> {last:.3f} over {len(hist)} iters "
          f"({'improved' if last < first else 'NOT improved'})")
    print(f"micro-batches/iter: min={min(mb_counts)} max={max(mb_counts)} "
          f"(dynamic, per-iteration planning)")
    s = stats.to_dict()
    print(f"tokens/s: {stats.real_tokens / max(stats.exec_s, 1e-9):,.0f} real "
          f"(padding efficiency "
          f"{stats.real_tokens / max(stats.padded_tokens, 1):.2f})")
    print(f"planner overlap: {s['overlap_fraction']:.1%} of "
          f"{s['planning_s']:.2f}s planning hidden; "
          f"compiled steps: {s['cache']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
