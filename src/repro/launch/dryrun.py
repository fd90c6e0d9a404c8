import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each runnable assignment cell this lowers the right step function
(train_step / prefill / decode) onto the production mesh with full-size
ShapeDtypeStruct inputs (no allocation), compiles it, and records:

  - memory_analysis(): per-device argument/output/temp/peak bytes (proves fit)
  - cost_analysis(): per-device HLO FLOPs & bytes accessed
  - collective traffic: parsed from the optimized HLO text, per collective
    kind, converted to per-device ICI link bytes (ring-algorithm estimates;
    see ``collective_link_bytes``)

Results are dumped as JSON under experiments/dryrun/ for EXPERIMENTS.md
§Dry-run and the §Roofline derivation (benchmarks/roofline.py).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import (ARCH_IDS, SHAPES, ArchConfig, ShapeSpec,
                                cell_supported, get_arch)
from repro.dist.sharding import set_mesh, spec_for
from repro.launch.mesh import make_production_mesh
from repro.models import model as MD
from repro.models import transformer as T
from repro.train.optimizer import AdamWConfig, adamw_update
from repro.train import train_state as TS

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# ----------------------------------------------------------------------
# input specs (assignment step 2): ShapeDtypeStruct stand-ins, no allocation
# ----------------------------------------------------------------------
def batch_specs(cfg: ArchConfig, shape: ShapeSpec):
    """(ShapeDtypeStruct tree, logical-dims tree) for one step's batch."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    bf16 = jnp.bfloat16
    sds = jax.ShapeDtypeStruct

    if shape.kind == "train":
        specs = {
            "labels": sds((b, s), i32),
            "loss_weights": sds((b, s), f32),
            "positions": sds((b, s), i32),
            "segment_ids": sds((b, s), i32),
        }
        logical = {k: ("dp", None) for k in specs}
        if cfg.input_mode == "frames":
            specs["frames"] = sds((b, s, cfg.d_model), bf16)
            specs["mask"] = sds((b, s), jnp.bool_)
            logical["frames"] = ("dp", None, None)
            logical["mask"] = ("dp", None)
        elif cfg.input_mode == "mixed":
            p = cfg.n_patches
            specs["patches"] = sds((b, p, cfg.d_model), bf16)
            specs["tokens"] = sds((b, s - p), i32)
            logical["patches"] = ("dp", None, None)
            logical["tokens"] = ("dp", None)
        else:
            specs["tokens"] = sds((b, s), i32)
            logical["tokens"] = ("dp", None)
        return specs, logical

    if shape.kind == "prefill":
        specs = {"positions": sds((b, s), i32)}
        logical = {"positions": ("dp", None)}
        if cfg.input_mode == "frames":
            specs["frames"] = sds((b, s, cfg.d_model), bf16)
            specs["mask"] = sds((b, s), jnp.bool_)
            logical["frames"] = ("dp", None, None)
            logical["mask"] = ("dp", None)
        elif cfg.input_mode == "mixed":
            p = cfg.n_patches
            specs["patches"] = sds((b, p, cfg.d_model), bf16)
            specs["tokens"] = sds((b, s - p), i32)
            logical["patches"] = ("dp", None, None)
            logical["tokens"] = ("dp", None)
        else:
            specs["tokens"] = sds((b, s), i32)
            logical["tokens"] = ("dp", None)
        return specs, logical

    # decode: one new token against a seq_len cache
    cache_shapes = jax.eval_shape(
        partial(T.init_cache, cfg, b, s, dtype=jnp.bfloat16))
    cache_logical = T.cache_logical(cfg)
    specs = {
        "tokens": sds((b, 1), i32),
        "positions": sds((b, 1), i32),
        "cache": cache_shapes,
        "cache_pos": sds((), i32),
    }
    logical = {
        "tokens": ("dp", None),
        "positions": ("dp", None),
        "cache": cache_logical,
        "cache_pos": (),
    }
    return specs, logical


def _leafy(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def spec_tree(shapes_tree, logical_tree, mesh):
    return jax.tree.map(
        lambda sh, lg: spec_for(tuple(sh.shape), tuple(lg), mesh),
        shapes_tree, logical_tree, is_leaf=_leafy)


# ----------------------------------------------------------------------
# step functions
# ----------------------------------------------------------------------
def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig, mesh):
    st_shapes = TS.state_shapes(cfg, opt_cfg)
    zero_spec = TS.state_spec_tree(cfg, st_shapes, mesh)["opt"]["m"]

    def train_step(state, batch):
        def lf(p):
            return MD.loss_fn(p, batch, cfg, impl="ref", remat=True)
        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(
            state["params"])
        # ZeRO-1: force the DP reduction into reduce-scatter form
        grads = jax.tree.map(
            lambda g, sp: jax.lax.with_sharding_constraint(g, sp),
            grads, zero_spec)
        new_params, new_opt, om = adamw_update(
            state["params"], grads, state["opt"], opt_cfg)
        return ({"params": new_params, "opt": new_opt},
                {"loss": loss, **om})

    return train_step


def make_prefill_step(cfg: ArchConfig):
    def prefill_step(params, batch):
        return MD.prefill(params, batch, cfg, impl="ref")
    return prefill_step


def make_decode_step(cfg: ArchConfig):
    def decode_step(params, batch):
        return MD.decode(params, batch, cfg, impl="ref")
    return decode_step


# ----------------------------------------------------------------------
# collective accounting
# ----------------------------------------------------------------------
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_COLL_RE = re.compile(
    r"(\w[\w.\-]*) = (?:\(([^)]*)\)|(\S+)) (all-gather|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute)[^(]*\(",
)
_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def bf16_upcast_correction(hlo_text: str) -> int:
    """CPU-backend artifact estimator (see EXPERIMENTS.md §Dry-run notes).

    The CPU emitter cannot issue bf16 dots, so XLA inserts f32 converts of
    bf16 weight stacks which LICM hoists out of the scan-over-periods loop —
    whole-model-sized f32 temp buffers that DO NOT EXIST on TPU (the MXU
    consumes bf16 directly). We sum f32 convert outputs >= 32 MiB in the
    ENTRY computation (hoisted = allocated once, live across the loop) and
    report ``temp_bytes - correction`` as the TPU-comparable estimate.
    """
    entry = hlo_text.find("ENTRY ")
    if entry < 0:
        return 0
    total = 0
    for line in hlo_text[entry:].splitlines():
        if "convert" not in line:
            continue
        m = re.search(r"= f32\[([\d,]+)\]\S* (?:convert|fusion)\(", line)
        if not m:
            continue
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        if n * 4 >= (32 << 20):
            total += n * 4
    return total


def collective_link_bytes(hlo_text: str) -> dict:
    """Per-device ICI bytes per collective kind (ring-algorithm estimates):

      all-gather:        out·(g-1)/g     all-reduce:  2·out·(g-1)/g
      reduce-scatter:    out·(g-1)      all-to-all:  out·(g-1)/g
      collective-permute: out
    """
    per_kind: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(4)
        out_bytes = _shape_bytes(m.group(2) or m.group(3))
        gm = _GROUPS_RE.search(line)
        g = int(gm.group(2)) if gm else 2
        if kind == "all-gather":
            link = out_bytes * (g - 1) / g
        elif kind == "all-reduce":
            link = 2 * out_bytes * (g - 1) / g
        elif kind == "reduce-scatter":
            link = out_bytes * (g - 1)
        elif kind == "all-to-all":
            link = out_bytes * (g - 1) / g
        else:
            link = out_bytes
        per_kind[kind] = per_kind.get(kind, 0.0) + link
        counts[kind] = counts.get(kind, 0) + 1
    return {"link_bytes": per_kind, "counts": counts,
            "total_link_bytes": sum(per_kind.values())}


# ----------------------------------------------------------------------
# one cell
# ----------------------------------------------------------------------
def _lower_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, opt_cfg: AdamWConfig):
    from repro.dist.sharding import pure_dp
    with pure_dp(cfg.pure_dp):
        return _lower_cell_inner(cfg, shape, mesh, opt_cfg)


def _lower_cell_inner(cfg: ArchConfig, shape: ShapeSpec, mesh, opt_cfg: AdamWConfig):
    bshapes, blogical = batch_specs(cfg, shape)
    bspec = spec_tree(bshapes, blogical, mesh)
    bshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), bspec)

    if shape.kind == "train":
        st_shapes = TS.state_shapes(cfg, opt_cfg)
        st_spec = TS.state_spec_tree(cfg, st_shapes, mesh)
        st_shard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), st_spec)
        fn = make_train_step(cfg, opt_cfg, mesh)
        return jax.jit(
            fn, in_shardings=(st_shard, bshard),
            out_shardings=(st_shard, None),
            donate_argnums=(0,),
        ).lower(st_shapes, bshapes)
    p_shapes = jax.eval_shape(
        lambda: MD.init_params(jax.random.PRNGKey(0), cfg))
    p_spec = TS.params_spec_tree(cfg, p_shapes, mesh)
    p_shard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), p_spec)
    if shape.kind == "prefill":
        fn = make_prefill_step(cfg)
        return jax.jit(fn, in_shardings=(p_shard, bshard)).lower(
            p_shapes, bshapes)
    fn = make_decode_step(cfg)
    out_shard = (None, bshard["cache"])
    return jax.jit(
        fn, in_shardings=(p_shard, bshard),
        out_shardings=out_shard,
        donate_argnums=(1,),
    ).lower(p_shapes, bshapes)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True, verbose: bool = True) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "runnable": ok, "skip_reason": why if not ok else "",
    }
    if not ok:
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    opt_cfg = AdamWConfig()

    with set_mesh(mesh):
        lowered = _lower_cell(cfg, shape, mesh, opt_cfg)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        peak_bytes = ma.peak_memory_in_bytes
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        text = compiled.as_text()
        coll = collective_link_bytes(text)
        upcast = bf16_upcast_correction(text)
        # trip-count-aware costs (cost_analysis counts loop bodies once —
        # see hlo_cost.py; these are the numbers §Roofline uses)
        from repro.launch import hlo_cost
        hc = hlo_cost.analyze(text)

        # TPU-comparable temp estimate: recompile with f32-native weights
        # (no bf16->f32 dot-operand converts exist, so no hoisted whole-model
        # f32 copies — structurally what the TPU backend compiles) and halve.
        # Exact args/flops/collectives still come from the bf16 compile.
        cfg32 = dataclasses.replace(cfg, dtype="float32")
        temp_tpu_est = None
        try:
            c32 = _lower_cell(cfg32, shape, mesh, opt_cfg).compile()
            temp_tpu_est = c32.memory_analysis().temp_size_in_bytes / 2
        except Exception as e:  # fall back to the parse-based correction
            temp_tpu_est = max(ma.temp_size_in_bytes - upcast, 0)

    rec.update({
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes": peak_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "cpu_bf16_upcast_bytes": upcast,
            "temp_tpu_est_bytes": temp_tpu_est,
            "device_bytes_est": (ma.argument_size_in_bytes
                                 + ma.output_size_in_bytes
                                 - ma.alias_size_in_bytes + temp_tpu_est),
        },
        "cost": {
            "flops_per_device": ca.get("flops", 0.0),
            "bytes_per_device": ca.get("bytes accessed", 0.0),
            # trip-count-aware (authoritative for §Roofline):
            "hlo_flops_per_device": hc.flops,
            "hlo_hbm_bytes_per_device": hc.hbm_bytes,
        },
        "collectives": coll,
        "collectives_trip_aware": {
            "link_bytes": hc.coll_link_bytes,
            "counts": hc.coll_counts,
            "total_link_bytes": hc.total_coll_bytes,
        },
        "model": {
            "n_params": cfg.n_params(),
            "n_params_active": cfg.n_params_active(),
        },
    })
    if save:
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        tag = f"{arch}__{shape_name}__{rec['mesh']}.json"
        (OUT_DIR / tag).write_text(json.dumps(rec, indent=1))
    if verbose:
        mem_gb = rec["memory"]["device_bytes_est"] / 1e9
        print(f"[OK] {arch:26s} {shape_name:12s} {rec['mesh']:8s} "
              f"mem/dev≈{mem_gb:6.2f}GB  flops/dev={hc.flops:.3e}  "
              f"hbm={hc.hbm_bytes:.3e}B coll={hc.total_coll_bytes:.3e}B  "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)", flush=True)
    return rec


def reanalyze_cell(arch: str, shape_name: str, multi_pod: bool) -> None:
    """Recompile (bf16 only) and refresh the cost/collective fields of an
    existing dry-run JSON — used when the HLO cost parser improves."""
    tag = f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}.json"
    path = OUT_DIR / tag
    rec = json.loads(path.read_text())
    if not rec.get("runnable"):
        return
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    with set_mesh(mesh):
        compiled = _lower_cell(cfg, shape, mesh, AdamWConfig()).compile()
        text = compiled.as_text()
        from repro.launch import hlo_cost
        hc = hlo_cost.analyze(text)
    rec["cost"]["hlo_flops_per_device"] = hc.flops
    rec["cost"]["hlo_hbm_bytes_per_device"] = hc.hbm_bytes
    rec["collectives_trip_aware"] = {
        "link_bytes": hc.coll_link_bytes,
        "counts": hc.coll_counts,
        "total_link_bytes": hc.total_coll_bytes,
    }
    path.write_text(json.dumps(rec, indent=1))
    print(f"[reanalyzed] {tag}: flops={hc.flops:.3e} hbm={hc.hbm_bytes:.3e} "
          f"coll={hc.total_coll_bytes:.3e}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--reanalyze", action="store_true")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    archs = ARCH_IDS[:10] if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}.json"
                if args.skip_existing and (OUT_DIR / tag).exists():
                    print(f"[skip existing] {tag}", flush=True)
                    continue
                try:
                    if args.reanalyze:
                        reanalyze_cell(arch, shape, mp)
                        continue
                    run_cell(arch, shape, mp)
                except Exception as e:
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"[FAIL] {arch} {shape} multi_pod={mp}: {e}",
                          flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES")
        for f in failures:
            print("  ", f[:3], f[3][:200])
        raise SystemExit(1)
    print("\nALL DRY-RUN CELLS PASSED")


if __name__ == "__main__":
    main()
