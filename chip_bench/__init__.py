"""Chip benchmark of the plan-ahead trainer: one cell per run, on a TPU.

    python3 -m chip_bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout root names the cells; each cell's
configuration, traffic mix and per-layer metric readers are files of their
own under this directory (``configs/``, ``traffic/``, ``metrics/``,
``references/``), found by name.
"""
