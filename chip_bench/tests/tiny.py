"""Tiny cells for CPU tests: the real configurations' structure at toy
widths (the kernels run as the jnp reference or in interpret mode)."""
from __future__ import annotations

import copy
import json

from chip_bench import spec

TINY_WIDTHS = {"d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_head": 16,
               "d_ff": 128, "vocab": 512}
# The limits of ``correct`` at these widths, set as the chip's are (PERF.md
# section 2) from CPU readings: gpt's first-step loss gap reads at most
# 4.3e-5 on sound runs and at least 1.75e-4 under the fp8 control (four
# seeds); its gradient gap reads at most 2.6e-3 sound and
# at least 4.7e-3 under the control, t5's 4.3e-3 and 1.29e-2 (nine seeds).
# The weight-change gap does not separate the control at these widths and
# is held only loosely.
TINY_LIMITS = {
    "gpt-paper-2L": {"loss_gap": 1e-4, "grad_gap": 4e-3, "delta_gap": 5e-2},
    "t5-paper-1enc1dec": {"loss_gap": 1e-2, "grad_gap": 7e-3,
                          "delta_gap": 5e-2},
}


def tiny_config(name: str) -> dict:
    with open(spec.BENCH_DIR / "configs" / f"{name}.json") as f:
        config = json.load(f)
    config["program"]["replace"].update(TINY_WIDTHS)
    config["model"].update(TINY_WIDTHS)
    config["limits"] = TINY_LIMITS[name]
    return config


def tiny_traffic(name: str = "flan-mix") -> dict:
    with open(spec.BENCH_DIR / "traffic" / f"{name}.json") as f:
        tr = json.load(f)
    tr = copy.deepcopy(tr)
    tr["max_len"] = 128
    tr["tokens_per_iteration"] = 512
    if "fixed" in tr["enc"]:
        tr["enc"]["fixed"] = 128
    else:
        tr["enc"].update(mean_range=[8, 128], clip=[4, 128])
        tr["dec"].update(mean_range=[4, 32], clip=[2, 32])
    return tr


def tiny_cell(config: str = "gpt-paper-2L", traffic: str = "flan-mix"):
    return spec.Cell(name=f"tiny.{config}.{traffic}", chips=1,
                     config=tiny_config(config), traffic_name=traffic,
                     traffic=tiny_traffic(traffic))
