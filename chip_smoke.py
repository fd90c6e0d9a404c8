"""Chip smoke test: the plan-driven trainer on a TPU at gpt-paper widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips of one host

The model is gpt-paper (DynaPipe Table 1): d_model 4096, 32 heads of 128,
d_ff 16384, vocab 50304, GELU, untied head, at those published widths with
random weights from a seed. Only the depth is cut, to one layer per
pipeline stage: the other layers would be further stages on further chips.

One chip: 2 layers as 2 pipeline stages (0.816 B parameters; bf16 params
and grads with fp32 master, m and v take about 13 GB of the chip's 16 GB).
It trains a few iterations of 8192 tokens (max_len 512) through the objects
``examples/train_multitask.py`` uses: ``MultiTaskStream``, ``ShapePalette``,
``AnalyticCostModel``, ``PlannerConfig(n_stages=2)`` and ``PlanAheadRunner``
with plan-ahead planning on threads, the threaded pipeline executor and
``impl=None``, which resolves to the Pallas kernels on a TPU. Then one
micro-batch's loss and grad norm through the Pallas kernels are compared
with the jnp reference, in float32 at the highest matmul precision.

Four chips: gpt-paper at 4 layers (1.22 B parameters) runs one plan
through ``MeshBackend`` over a 4-stage mesh, and its loss and gradients are
compared with the sequential single-device path on chip 0.

The script exits non-zero without a result line when JAX finds no TPU,
when it runs outside a checkout of the repository, or when a check fails.
Its last line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

try:
    import jax
    import jax.numpy as jnp

    from repro.configs.gpt_paper import CONFIG as GPT_PAPER
    from repro.core.cost_model import AnalyticCostModel
    from repro.core.planner import PlannerConfig, plan_iteration
    from repro.core.shapes import ShapePalette
    from repro.data.dataset import materialize_micro_batch
    from repro.data.streams import MultiTaskStream, StreamConfig
    from repro.dist.backend import MeshBackend, ThreadsBackend
    from repro.kernels.ops import default_impl
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_stage_mesh
    from repro.models import model as MD
    from repro.train.optimizer import AdamWConfig, global_norm
    from repro.train.pipeline_adapter import build_grad_step
    from repro.train.runner import PlanAheadRunner, RunnerConfig
except ImportError as e:     # only this file, without the repository
    _IMPORT_ERROR: ImportError | None = e
else:
    _IMPORT_ERROR = None

N_STAGES = 2
ITERS = 3
GLOBAL_TOKENS = 8192
MAX_LEN = 512

# Pallas vs jnp reference, float32, default_matmul_precision("highest").
# The two differ in summation order only: the kernels run softmax online
# over kv blocks and accumulate in VMEM, the reference normalizes once.
# Over 2 layers and one micro-batch that is ~1e-6 relative on the loss and
# ~1e-5 on the grad norm; the bounds leave 100x for the MXU's f32 passes,
# whose rounding the precision flag may not reach inside a kernel. A wrong
# mask, scale or softmax statistic moves either by far more than that.
REF_LOSS_RTOL = 1e-4
REF_GNORM_RTOL = 1e-3

# MeshBackend vs the sequential path, both bf16. Same math per layer; the
# programs differ (a shard_map ring of per-stage slices vs one whole-model
# step), so XLA fuses and rounds bf16 intermediates differently: ~2^-8
# relative per element. The global relative error of the gradient averages
# those; the per-sum loss differs far less.
MESH_LOSS_RTOL = 1e-3
MESH_GRAD_RTOL = 2e-2


def gpt_paper(n_layers: int):
    """gpt-paper at its published widths, depth cut to ``n_layers``."""
    return dataclasses.replace(GPT_PAPER, n_layers=n_layers)


@contextlib.contextmanager
def count_compiles():
    """Counts XLA compiles and their seconds while the block runs."""
    box = {"count": 0, "seconds": 0.0}

    def on_event(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box["count"] += 1
            box["seconds"] += seconds
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def _stream_and_plan(cfg, n_stages, palette, global_tokens, max_len, seed):
    stream = MultiTaskStream(StreamConfig(
        n_tasks=16, global_tokens=global_tokens, max_len=max_len,
        vocab=cfg.vocab, tail_fraction=0.08, seed=seed))
    cost = AnalyticCostModel(cfg, n_stages=n_stages)
    pcfg = PlannerConfig(n_stages=n_stages, device_mem=16e9,
                         d_model=cfg.d_model, palette=palette)
    return stream, cost, pcfg


def _first_plan(stream, cost, pcfg):
    """Iteration 0's plan for replica 0 and its materialized batches."""
    gb = stream.batch(0)
    plan = plan_iteration(gb.lengths[:, 0], cost, pcfg).replica_plans[0]
    batches = {m.mb_id: materialize_micro_batch(m, gb.tokens,
                                                lengths=gb.lengths)
               for m in plan.micro_batches}
    return plan, batches


def train(cfg, *, iters=ITERS, global_tokens=GLOBAL_TOKENS, max_len=MAX_LEN,
          impl=None, seed=0) -> dict:
    """Trains ``cfg`` as N_STAGES pipeline stages through PlanAheadRunner,
    then checks one micro-batch against the reference. Returns a report."""
    palette = ShapePalette.build(min_seq=max_len // 4, max_seq=max_len,
                                 seq_align=max_len // 4, ratio=2, max_mbs=32)
    stream, cost, pcfg = _stream_and_plan(cfg, N_STAGES, palette,
                                          global_tokens, max_len, seed)
    rcfg = RunnerConfig(n_iters=iters, impl=impl, seed=seed, log_every=1)
    runner = PlanAheadRunner(cfg, cost, pcfg, rcfg, stream,
                             opt_cfg=AdamWConfig(lr=3e-4))
    with count_compiles() as compiles:
        t0 = time.perf_counter()
        params, history, stats = runner.run()
        train_s = time.perf_counter() - t0
    report = {
        "impl": impl if impl is not None else default_impl(),
        "n_params": sum(int(x.size) for x in jax.tree.leaves(params)),
        "losses": [h["loss"] for h in history],
        "grad_norms": [h["grad_norm"] for h in history],
        "n_micro": [h["n_micro"] for h in history],
        "iter_s": [h["time_s"] for h in history],
        "train_s": train_s,
        "compiles": compiles["count"],
        "compile_s": compiles["seconds"],
        "faults": stats.faults,
        "recoveries": len(stats.recoveries),
        "memory": _memory(jax.devices()[0]),
        "stage_programs": [p.as_text()
                           for kind in ("fwd", "bwd", "fwd_bwd")
                           for p in runner.step_cache.entries(kind)],
    }
    del params, runner        # free the training state before the check
    plan, batches = _first_plan(stream, cost, pcfg)
    big = max(plan.micro_batches, key=lambda m: m.mbs * m.seq)
    report["ref_batch"] = (big.mbs, big.seq)
    report["ref"] = ref_agreement(cfg, batches[big.mb_id], report["impl"],
                                  seed)
    return report


def ref_agreement(cfg, batch, impl, seed) -> dict:
    """Loss and grad norm of one micro-batch through ``impl`` and through
    the jnp reference, float32, highest matmul precision."""
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params = MD.init_params(jax.random.PRNGKey(seed), cfg32)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    out = {}
    with jax.default_matmul_precision("highest"):
        for name in (impl, "ref"):
            loss_sum, w_sum, grads = build_grad_step(cfg32, impl=name)(
                params, batch)
            out[name] = {"loss": float(loss_sum / w_sum),
                         "grad_norm": float(global_norm(grads))}
            del grads
    return out


def _rel(a, b) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def check_training(report: dict, impl: str) -> list[str]:
    """The checks a training run must pass on any platform."""
    fails = []
    if report["impl"] != impl:
        fails.append(f"kernel impl resolved to {report['impl']!r}, "
                     f"not {impl!r}")
    if not report["losses"] or not all(map(math.isfinite,
                                           report["losses"])):
        fails.append(f"non-finite losses {report['losses']}")
    if report["faults"] or report["recoveries"]:
        fails.append(f"{report['faults']} faults and {report['recoveries']} "
                     "recoveries: a step failed and was retried")
    ref, kern = report["ref"]["ref"], report["ref"][report["impl"]]
    if not _rel(kern["loss"], ref["loss"]) <= REF_LOSS_RTOL:
        fails.append(f"loss {kern['loss']} vs reference {ref['loss']}: "
                     f"beyond rtol {REF_LOSS_RTOL}")
    if not _rel(kern["grad_norm"], ref["grad_norm"]) <= REF_GNORM_RTOL:
        fails.append(f"grad norm {kern['grad_norm']} vs reference "
                     f"{ref['grad_norm']}: beyond rtol {REF_GNORM_RTOL}")
    return fails


def one_chip() -> list[str]:
    cfg = gpt_paper(N_STAGES)
    print(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}x"
          f"{cfg.d_head} d_ff={cfg.d_ff} vocab={cfg.vocab} act={cfg.act} "
          f"tied={cfg.tie_embeddings}; {cfg.n_layers} layers as {N_STAGES} "
          f"pipeline stages; {ITERS} iterations of {GLOBAL_TOKENS} tokens, "
          f"max_len {MAX_LEN}", flush=True)
    report = train(cfg)
    custom = ["tpu_custom_call" in text for text in report["stage_programs"]]
    ref = report["ref"]
    print(f"params: {report['n_params']:,}")
    print(f"kernel impl: {report['impl']}")
    print(f"losses: {report['losses']}")
    print(f"grad norms: {report['grad_norms']}")
    print(f"micro-batches per iteration: {report['n_micro']}")
    print(f"iteration seconds: {report['iter_s']}")
    print(f"compiles: {report['compiles']} taking {report['compile_s']:.1f}"
          f" s; training took {report['train_s']:.1f} s")
    print(f"memory: {report['memory']}")
    print(f"stage programs with tpu_custom_call: {sum(custom)} of "
          f"{len(custom)}")
    print(f"faults: {report['faults']}, recoveries: {report['recoveries']}")
    kern = ref[report["impl"]]
    print(f"reference check on micro-batch {report['ref_batch']}: {ref}; "
          f"loss rel err {_rel(kern['loss'], ref['ref']['loss']):.3e}, "
          f"grad norm rel err "
          f"{_rel(kern['grad_norm'], ref['ref']['grad_norm']):.3e}")
    fails = check_training(report, "pallas")
    if not custom or not all(custom):
        fails.append("a compiled stage program has no tpu_custom_call")
    return fails


def four_chips(seed=0, global_tokens=4096, max_len=MAX_LEN) -> list[str]:
    """One plan through MeshBackend on 4 chips vs the sequential path on
    chip 0: loss and gradients, no optimizer state."""
    n = 4
    devices = jax.devices()
    if len(devices) < n:
        return [f"--four-chips needs {n} devices, JAX sees {len(devices)}"]
    cfg = gpt_paper(n)
    palette = ShapePalette.build(min_seq=max_len, max_seq=max_len,
                                 seq_align=128, max_mbs=32)
    stream, cost, pcfg = _stream_and_plan(cfg, n, palette, global_tokens,
                                          max_len, seed)
    plan, batches = _first_plan(stream, cost, pcfg)
    params = MD.init_params(jax.random.PRNGKey(seed), cfg)
    print(f"model: {cfg.name} at published widths, {cfg.n_layers} layers "
          f"as {n} stages, {sum(int(x.size) for x in jax.tree.leaves(params)):,}"
          f" params; plan: {[(m.mbs, m.seq) for m in plan.micro_batches]}",
          flush=True)
    mesh = MeshBackend(cfg, n, mesh=make_stage_mesh(n))
    with count_compiles() as compiles:
        t0 = time.perf_counter()
        r_mesh = mesh.execute_plan(plan, params=params, batches=batches)
        jax.block_until_ready(r_mesh.grads)
        mesh_s = time.perf_counter() - t0
    memory = [_memory(d) for d in devices[:n]]
    print(f"mesh: loss_sum {r_mesh.loss_sum} over {r_mesh.weight_sum} "
          f"tokens, {compiles['count']} compiles, {mesh_s:.1f} s, "
          f"groups {r_mesh.meta['groups']}")
    for d, m in zip(devices[:n], memory):
        print(f"  chip {d.id}: {m}")
    seq = ThreadsBackend(cfg, n, use_executor=False)
    r_seq = seq.execute_plan(plan, params=params, batches=batches)
    diff = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                        - b.astype(jnp.float32), r_mesh.grads, r_seq.grads)
    grad_rel = float(global_norm(diff) / global_norm(r_seq.grads))
    loss_rel = _rel(r_mesh.loss_sum, r_seq.loss_sum)
    print(f"sequential on chip 0: loss_sum {r_seq.loss_sum}; mesh vs "
          f"sequential: loss rel err {loss_rel:.3e}, grad rel err "
          f"{grad_rel:.3e} (global norm of the difference)")
    fails = []
    if not loss_rel <= MESH_LOSS_RTOL:
        fails.append(f"mesh loss rel err {loss_rel} > {MESH_LOSS_RTOL}")
    if not grad_rel <= MESH_GRAD_RTOL:
        fails.append(f"mesh grad rel err {grad_rel} > {MESH_GRAD_RTOL}")
    idle = [d.id for d, m in zip(devices[:n], memory)
            if (m["bytes_in_use"] or 0) < 2 ** 28]
    if idle:
        fails.append(f"chips {idle} hold under 256 MiB after the mesh step")
    return fails


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip MeshBackend comparison")
    args = ap.parse_args()
    if _IMPORT_ERROR is not None:
        print(f"chip_smoke.py: cannot import the repository from {SRC}: "
              f"{_IMPORT_ERROR}", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind}, {len(jax.devices())} visible; "
          f"compile cache: {enable_compile_cache()}", flush=True)
    fails = four_chips() if args.four_chips else one_chip()
    if fails:
        for f in fails:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
