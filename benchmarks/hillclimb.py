"""§Perf hillclimbing harness (assignment deliverable g, perf loop).

Lowers a cell under named config variants, re-derives the three roofline
terms from the compiled HLO, and prints before/after per variant — the
measurement half of the hypothesis → change → measure → validate loop whose
log lives in EXPERIMENTS.md §Perf.

  PYTHONPATH=src python -m benchmarks.hillclimb --arch gemma2-2b \
      --shape train_4k --variants baseline,attn_replicated
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import time
from pathlib import Path


from repro.configs.base import SHAPES, get_arch
from repro.core.cost_model import V5E
from repro.dist.sharding import set_mesh
from repro.launch import hlo_cost
from repro.launch.dryrun import _lower_cell
from repro.launch.mesh import make_production_mesh
from repro.train.optimizer import AdamWConfig

OUT = Path(__file__).resolve().parents[1] / "experiments" / "hillclimb"

VARIANTS = {
    "baseline": {},
    "pure_dp": {"pure_dp": True},
    "pure_dp+pad_heads": {"pure_dp": True, "pad_heads": True},
    "attn_replicated": {"attn_tp": False},
    "pad_heads": {"pad_heads": True},
    "remat_dots": {"remat_policy": "dots"},
    "remat_none": {"remat_policy": "everything"},
    "pad_heads+remat_dots": {"pad_heads": True, "remat_policy": "dots"},
    "attn_replicated+remat_dots": {"attn_tp": False, "remat_policy": "dots"},
}


def attention_score_traffic(cfg, shape, n_chips: int) -> float:
    """Per-device HBM bytes of materialized attention probabilities in the
    lowered jnp path (fwd write+read, p@v read, bwd recompute + dP + dV
    chains ~ 10 passes of the f32 score tensor, causal halves it). The
    Pallas flash kernel (kernels/flash_attention.py) keeps these in VMEM —
    this is the analytic credit for running it on real TPU."""
    if not cfg.has_attn:
        return 0.0
    attn_layers = sum(1 for s in cfg.pattern_layers if s.mixer.startswith("attn"))
    b, t = shape.global_batch, shape.seq_len
    s = t
    if shape.kind == "decode":
        return 0.0   # q length 1: scores are tiny
    passes = 10.0 if shape.kind == "train" else 3.0
    causal = 0.5 if cfg.causal else 1.0
    return passes * causal * b * cfg.n_heads * t * s * 4.0 * attn_layers / n_chips


def measure(arch: str, shape_name: str, variant: str, multi_pod=False) -> dict:
    cfg = dataclasses.replace(get_arch(arch), **VARIANTS[variant])
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    t0 = time.time()
    with set_mesh(mesh):
        compiled = _lower_cell(cfg, shape, mesh, AdamWConfig()).compile()
        hc = hlo_cost.analyze(compiled.as_text())
        ma = compiled.memory_analysis()
    flash_credit = attention_score_traffic(cfg, shape, n_chips)
    terms = {
        "compute_s": hc.flops / V5E.peak_flops,
        "memory_s": hc.hbm_bytes / V5E.hbm_bw,
        "collective_s": hc.total_coll_bytes / V5E.ici_bw,
    }
    mem_flash = max(hc.hbm_bytes - flash_credit, 0.0) / V5E.hbm_bw
    dom = max(terms, key=terms.get)
    bound_flash = max(terms["compute_s"], mem_flash, terms["collective_s"])
    rec = {
        "arch": arch, "shape": shape_name, "variant": variant,
        **terms, "dominant": dom,
        "memory_flash_s": mem_flash,
        "bound_s": max(terms.values()),
        "bound_flash_s": bound_flash,
        "roofline_fraction": terms["compute_s"] / max(terms.values()),
        "roofline_fraction_flash": terms["compute_s"] / bound_flash
        if bound_flash else 0.0,
        "coll_by_kind": hc.coll_link_bytes,
        "arg_gb": ma.argument_size_in_bytes / 1e9,
        "compile_s": round(time.time() - t0, 1),
    }
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{arch}__{shape_name}__{variant}.json").write_text(
        json.dumps(rec, indent=1))
    print(f"{variant:28s} compute={terms['compute_s']:8.3f}s "
          f"memory={terms['memory_s']:8.3f}s (flash {mem_flash:7.3f}s) "
          f"coll={terms['collective_s']:8.3f}s dom={dom:10s} "
          f"frac={rec['roofline_fraction']:.2f} "
          f"frac_flash={rec['roofline_fraction_flash']:.2f}", flush=True)
    print(f"{'':28s} coll by kind: "
          + " ".join(f"{k}={v:.2e}" for k, v in hc.coll_link_bytes.items()),
          flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--variants", default="baseline")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    print(f"== hillclimb {args.arch} x {args.shape} ==", flush=True)
    for v in args.variants.split(","):
        try:
            measure(args.arch, args.shape, v.strip(), args.multi_pod)
        except Exception as e:
            print(f"{v:28s} FAILED: {e}", flush=True)


if __name__ == "__main__":
    main()
