"""Records ``data/runner.xplane.pb``, the traced run ``test_phases.py`` reads.

    PYTHONPATH=src python3 -m chip_bench.tests.record_runner_probe OUT  # TPU

The plan-ahead runner at toy widths (gpt-paper's structure, 2 layers as 2
pipeline stages, the jnp attention) for ``ITERS`` iterations over two
batches in turn, so that every program is compiled before the trace
starts; the profiler runs over the last ``TRACED`` iterations and the one
before, and they lie between ``bench.window_open`` (at the top of the first,
as the benchmark's feed marks it) and ``bench.window_close`` (after the
last update is ready). The trace lands under ``OUT/plugins/profile/``.
"""
import dataclasses
import sys

import jax

ITERS, TRACED = 6, 2


def main(out_dir: str) -> None:
    from repro.configs.base import get_arch, reduced
    from repro.core.cost_model import AnalyticCostModel
    from repro.core.planner import PlannerConfig
    from repro.core.shapes import ShapePalette
    from repro.data.streams import MultiTaskStream, StreamConfig
    from repro.train.runner import PlanAheadRunner, RunnerConfig

    cfg = dataclasses.replace(reduced(get_arch("gpt-paper")), n_layers=2)
    stream = MultiTaskStream(StreamConfig(n_tasks=8, global_tokens=512,
                                          max_len=128, vocab=cfg.vocab,
                                          seed=3))
    first = ITERS - TRACED

    class Feed:
        """The runner asks for batch k + 1 at the top of iteration k."""

        def batch(self, k):
            if k == first:
                opts = jax.profiler.ProfileOptions()
                opts.host_tracer_level = 1
                opts.python_tracer_level = 0
                jax.profiler.start_trace(out_dir, profiler_options=opts)
            elif k == first + 1:
                with jax.profiler.TraceAnnotation("bench.window_open"):
                    pass
            return stream.batch(k % 2)

    palette = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32,
                                 max_mbs=8)
    runner = PlanAheadRunner(
        cfg, AnalyticCostModel(cfg, n_stages=2),
        PlannerConfig(n_stages=2, d_model=cfg.d_model, palette=palette),
        RunnerConfig(n_iters=ITERS, log_every=0, impl="ref"), Feed())
    params, _, _ = runner.run()
    jax.block_until_ready(params)
    with jax.profiler.TraceAnnotation("bench.window_close"):
        pass
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
