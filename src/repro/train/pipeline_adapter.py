"""Model <-> executor adapter: stage-sliced params and real JAX fwd/bwd.

Splits a model's scan-over-periods parameter stack into ``n_stages``
contiguous period groups; stage 0 additionally owns the embedding (+
modality adapters), the last stage owns the final norm and LM head.
Every stage but the last runs a forward program and, later, a backward
program that recomputes the stage forward via ``jax.vjp`` from the stashed
stage input (stage-granular activation checkpointing), so the only
per-micro-batch stash is the stage input — the quantity the planner's
memory model charges. The last stage trains each micro-batch in one
``value_and_grad`` program when its forward runs: the loss needs the
primal output anyway, so a separate forward program would only run the
stage twice. It stashes the input gradient, the size of its input, until
the schedule's backward sends it, and leaves the micro-batch's summed loss
on the device (``LossSum``): no stage thread waits for the device, and the
runner reads the step's losses once, when the step is done. Within a stage
program, periods are checkpointed only where a stage holds more than one
(``_period_remat``).

Tied embeddings are duplicated on stages 0 and c-1; their gradients are
summed at ``collect_grads`` time (the pipeline analogue of Megatron's
embedding all-reduce).

Stage fwd/bwd callables are compiled through a ``CompiledStepCache`` keyed by
``(kind, stage, mbs, seq)`` — 2D micro-batches key by ``(mbs, enc, dec)`` —
so one model reused across iterations (``set_params`` swaps the weights,
which are traced arguments) never recompiles a palette shape it has already
seen; the plan-ahead runner (train/runner.py) shares one cache across the
whole run.

``EncDecPipelinedModel`` is the encoder-decoder stage layout (the paper's
T5 workload): encoder periods occupy the early stages, decoder periods (with
their period-major cross-attention blocks) the later ones, and the final
encoder output rides the pipe unchanged to every decoder stage — the
inter-stage payload on the decoder side is the pair ``(he, hd)``, and
``jax.vjp`` over that pair routes cross-attention gradients back through the
encoder stages without any extra communication primitives.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import spans
from repro.core.executor import StageCallbacks
from repro.core.instructions import ExecutionPlan
from repro.models import layers as L
from repro.models import model as MD
from repro.models import transformer as T
from repro.train.step_cache import CompiledStepCache


def model_cache_namespace(cfg: ArchConfig) -> str:
    """Discriminator prefix for CompiledStepCache keys: a cache may be
    shared across runners/models, so shape keys alone are not identity —
    two configs with equal shapes must not hit each other's compiled
    steps. ``repr`` of the config dataclass covers every field."""
    return repr(cfg)


def build_grad_step(cfg: ArchConfig, impl: Optional[str] = None):
    """The sequential-path training step: jitted value_and_grad of the
    summed xent over one micro-batch. Shared by the runner and
    benchmarks/bench_e2e.py so benches measure exactly the system's math.

    ``impl`` pins the kernel path (pallas/interpret/ref) for forward AND
    backward — the attention kernels carry custom VJPs, so grad steps stay
    on the selected kernels instead of falling back to the jnp oracle.
    ``None`` defers to ``repro.kernels.default_impl()`` (which honours the
    ``REPRO_KERNEL_IMPL`` env override)."""

    @jax.jit
    def grad_step(p, batch):
        def f(p_):
            h, _, _ = MD.forward(p_, batch, cfg, mode="train", impl=impl)
            return _xent_sum(p_.get("head", p_.get("embed")), h,
                             batch["labels"], batch["loss_weights"], cfg)
        (loss_sum, w_sum), g = jax.value_and_grad(f, has_aux=True)(p)
        return loss_sum, w_sum, g
    return grad_step


def build_encdec_grad_step(cfg: ArchConfig, impl: Optional[str] = None):
    """Sequential enc-dec training step: value_and_grad of the dec-side
    summed xent through the ``encdec_fwd`` oracle (tied embedding head).
    The enc-dec analogue of :func:`build_grad_step`."""

    @jax.jit
    def grad_step(p, batch):
        def f(p_):
            hd = T.encdec_fwd(
                p_, batch["enc_tokens"], batch["dec_tokens"], cfg,
                enc_segments=batch["enc_segment_ids"],
                dec_segments=batch["dec_segment_ids"],
                enc_positions=batch["enc_positions"],
                dec_positions=batch["dec_positions"], impl=impl)
            return _xent_sum(p_["embed"], hd, batch["labels"],
                             batch["loss_weights"], cfg)
        (loss_sum, w_sum), g = jax.value_and_grad(f, has_aux=True)(p)
        return loss_sum, w_sum, g
    return grad_step


def _period_remat(k: int) -> bool:
    """Whether a stage of ``k`` periods checkpoints each period inside its
    program. With one period the stage input, stashed or rematerialized at
    stage granularity, already is the checkpoint: an inner one would only
    run the forward once more. With several it bounds a program's memory
    to one period's activations."""
    return k > 1


def _stage_apply(cfg: ArchConfig, k: int, n_stages: int, impl, j: int,
                 sparams, x_or_batch, batch_aux):
    """Stage forward as a module-level pure function of static config —
    jitted closures capture only these scalars, never a model instance.
    Returns h_out, or (loss_sum, w_sum) on the last stage."""
    positions = batch_aux["positions"]
    segment_ids = batch_aux["segment_ids"]
    if j == 0:
        h = MD.embed_inputs(sparams, x_or_batch, cfg)
    else:
        h = x_or_batch
    sub_cfg = dataclasses.replace(cfg, n_layers=k * len(cfg.layer_pattern))
    h, _, _ = T.stack_fwd(sparams["stack"], h, sub_cfg,
                          positions=positions, segment_ids=segment_ids,
                          impl=impl, remat=_period_remat(k))
    if j == n_stages - 1:
        h = L.rms_norm(h, sparams["final_norm"], cfg.norm_eps)
        head = sparams.get("head", sparams.get("embed"))
        loss_sum, w_sum = _xent_sum(head, h, batch_aux["labels"],
                                    batch_aux["loss_weights"], cfg)
        return loss_sum, w_sum
    return h


def _encdec_stage_apply(cfg: ArchConfig, k: int, n_stages: int,
                        n_enc_stages: int, impl, j: int,
                        sparams, x_or_batch, batch_aux):
    """Encoder-decoder stage forward (module-level pure function, like
    ``_stage_apply``). Stage kinds by position:

      j < n_enc_stages          encoder slice: in batch|he, out he
      j == n_enc_stages         first decoder slice: in he (the final
                                encoder output), embeds dec tokens itself,
                                out (he, hd)
      j > n_enc_stages          decoder slice: in (he, hd), out (he, hd) —
                                he passes through so every decoder stage
                                cross-attends the same encoder output
      j == n_stages - 1         + dec norm and dec-side loss -> (loss, w)
    """
    sub_cfg = dataclasses.replace(cfg, n_layers=k * len(cfg.layer_pattern))
    enc_seg = batch_aux["enc_segment_ids"]
    if j < n_enc_stages:
        if j == 0:
            h = jnp.take(sparams["embed"], x_or_batch["enc_tokens"], axis=0)
        else:
            h = x_or_batch
        h = T.enc_stage_fwd(sparams["stack"], h, sub_cfg,
                            positions=batch_aux["enc_positions"],
                            segment_ids=enc_seg, impl=impl,
                            remat=_period_remat(k),
                            rel_bias=sparams.get("rel_bias"))
        if j == n_enc_stages - 1:
            h = L.rms_norm(h, sparams["enc_norm"], cfg.norm_eps)
        return h
    if j == n_enc_stages:
        he = x_or_batch
        hd = jnp.take(sparams["embed"], batch_aux["dec_tokens"], axis=0)
    else:
        he, hd = x_or_batch
    hd = T.dec_stage_fwd({"stack": sparams["stack"],
                          "cross": sparams["cross"]},
                         hd, he, sub_cfg,
                         positions=batch_aux["dec_positions"],
                         segment_ids=batch_aux["dec_segment_ids"],
                         enc_segment_ids=enc_seg, impl=impl,
                         remat=_period_remat(k),
                         rel_bias=sparams.get("rel_bias"))
    if j == n_stages - 1:
        hd = T.dec_head_input(hd, sparams["dec_norm"], cfg)
        return _xent_sum(sparams["embed"], hd, batch_aux["labels"],
                         batch_aux["loss_weights"], cfg)
    return (he, hd)


class PipelinedModel:
    _aux_keys = ("positions", "segment_ids", "labels", "loss_weights")

    def __init__(self, cfg: ArchConfig, params, n_stages: int,
                 impl: Optional[str] = None,
                 step_cache: Optional[CompiledStepCache] = None):
        self.cfg = cfg
        self.n_stages = n_stages
        self.impl = impl
        self.full_params = params
        self.step_cache = step_cache if step_cache is not None \
            else CompiledStepCache()
        self._init_layout()

    def _init_layout(self):
        """Validate the stage split and bind the stage-apply hook; the
        enc-dec subclass overrides this (and only this) part of init."""
        cfg, n_stages = self.cfg, self.n_stages
        assert cfg.n_periods % n_stages == 0, (
            f"{cfg.name}: n_periods {cfg.n_periods} not divisible by "
            f"{n_stages} stages")
        self.k = cfg.n_periods // n_stages
        # cache keys carry full model identity: a shared cache must never
        # hand one model's compiled stage fn to a different config (or
        # kernel impl) with equal shapes — repr(cfg) covers every field
        self._cache_ns = (repr(cfg), n_stages, self.impl)
        # stage apply = module-level fn + static scalars: jitted closures
        # capture only these, never the model instance (see make_callbacks)
        self._apply_fn = _stage_apply
        self._apply_static = (cfg, self.k, n_stages, self.impl)

    @staticmethod
    def _batch_shape(b) -> tuple:
        tok = b["tokens"]
        return int(tok.shape[0]), int(tok.shape[1])

    def set_params(self, params):
        """Swap in updated weights; compiled stage fns are shape-keyed and
        take params as traced arguments, so no recompilation happens."""
        self.full_params = params

    # ------------------------- param slicing ---------------------------
    def stage_params(self, j: int):
        k = self.k
        stack = jax.tree.map(lambda x: x[j * k : (j + 1) * k],
                             self.full_params["stack"])
        p: dict[str, Any] = {"stack": stack}
        if j == 0:
            for key in ("embed", "frame_adapter", "mask_emb", "patch_adapter"):
                if key in self.full_params:
                    p[key] = self.full_params[key]
        if j == self.n_stages - 1:
            p["final_norm"] = self.full_params["final_norm"]
            if "head" in self.full_params:
                p["head"] = self.full_params["head"]
            elif self.cfg.tie_embeddings:
                p["embed"] = self.full_params["embed"]
        return p

    def merge_stage_grads(self, stage_grads: list):
        """Sum per-stage grad trees back into a full-params tree. Built
        from the stage grads alone: no full-size zero tree is allocated,
        which at published widths would cost a second copy of the grads."""
        out = {"stack": jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0),
            *[g["stack"] for g in stage_grads])}
        for g in stage_grads:
            for key, val in g.items():
                if key != "stack":
                    out[key] = out[key] + val if key in out else val
        return out

    # ------------------------- stage compute ---------------------------
    def _key(self, kind: str, j: int, shape: tuple) -> tuple:
        return (kind, self._cache_ns, j) + shape

    def compile_plan(self, plan: ExecutionPlan, batches: dict) -> None:
        """Compile every stage program the plan needs and the cache lacks.

        Runs before any stage thread starts, so a compile never counts
        against the executor's channel timeout (a full-width stage program
        takes seconds to compile). Programs are compiled ahead of time from
        shapes, and the cache holds the executables: the stage threads only
        ever run compiled code. Stages before the last get a forward and a
        backward program; the last stage gets one forward-and-backward
        program. Programs that produce parameter gradients take the stage's
        grad accumulator as a donated argument and return it updated in
        place. Programs are named ``stage{j}_{kind}``, so a device trace's
        ``XLA Modules`` line reads ``jit_stage{j}_fwd``, ``_bwd`` or
        ``_fwd_bwd``; each compile runs under a ``dynapipe.compile`` span.
        """
        c = self.n_stages
        firsts: dict[tuple, int] = {}
        for m in plan.micro_batches:
            firsts.setdefault(self._batch_shape(batches[m.mb_id]), m.mb_id)
        # cached programs close over only static config — never ``self`` —
        # so a shared step cache that outlives this model does not pin the
        # retired instance (and its full_params) in memory
        apply_fn, static = self._apply_fn, self._apply_static
        remat = _period_remat(self.k)

        def ensure(kind, j, shape, fn, args, **jit_kw):
            self.step_cache.get(self._key(kind, j, shape), lambda: _compile(
                fn, j, kind, shape, remat, args, **jit_kw))

        for shape, mb in firsts.items():
            if all(self._key(kind, j, shape) in self.step_cache.keys()
                   for j in range(c) for kind in (
                       ("fwd_bwd",) if j == c - 1 else ("fwd", "bwd"))):
                continue
            b = {k: _struct(jnp.asarray(v)) for k, v in batches[mb].items()}
            aux = {k: b[k] for k in self._aux_keys if k in b}
            x = b
            for j in range(c):
                sp = jax.eval_shape(lambda j=j: self.stage_params(j))
                if j == c - 1:
                    def fwd_bwd(sp_, x_, aux_, acc, j=j):
                        def loss(p, x2):
                            return apply_fn(*static, j, p, x2, aux_)
                        (loss_sum, _), (gp, gx) = jax.value_and_grad(
                            loss, argnums=(0, 1), has_aux=True)(sp_, x_)
                        return loss_sum, jax.tree.map(jnp.add, acc, gp), gx
                    ensure("fwd_bwd", j, shape, fwd_bwd, (sp, x, aux, sp),
                           donate_argnums=3)
                    continue

                def fwd(sp_, x_, aux_, j=j):
                    return apply_fn(*static, j, sp_, x_, aux_)

                def bwd(sp_, x_, g_out, aux_, acc, j=j):
                    _, vjp = jax.vjp(
                        lambda p, x2: apply_fn(*static, j, p, x2, aux_),
                        sp_, x_)
                    gp, gx = vjp(g_out)
                    return jax.tree.map(jnp.add, acc, gp), gx
                x_next = jax.eval_shape(fwd, sp, x, aux)
                ensure("fwd", j, shape, fwd, (sp, x, aux))
                ensure("bwd", j, shape, bwd, (sp, x, x_next, aux, sp),
                       donate_argnums=4)
                x = x_next

    # ------------------------- callbacks -------------------------------
    def make_callbacks(self, plan: ExecutionPlan, batches: dict,
                       on_step=None) -> tuple[list[StageCallbacks], dict]:
        """batches: mb_id -> batch dict (numpy/JAX arrays).

        Returns (callbacks, result) where result collects
        {"stage_grads", "loss_sum", "weight_sum"} after run(): the loss as a
        :class:`LossSum` still on the device, the weight sum from the
        host's batches (:func:`weight_sum`). Every stage program is
        compiled here (:meth:`compile_plan`), before the callbacks exist.
        The last stage's forward runs its forward-and-backward program,
        stashes the input gradient and returns the loss it leaves on the
        device; its backward hands that gradient on. Stage 0's backward
        returns its gradient accumulator, which nothing sends: no callback
        waits for the device, and a timer can wait on what each returns.
        """
        self.compile_plan(plan, batches)
        c = self.n_stages
        result = {
            "stage_grads": [None] * c,
            "loss_sum": LossSum(),
            "weight_sum": weight_sum(batches),
        }
        sparams = [self.stage_params(j) for j in range(c)]
        stashes: list[dict] = [dict() for _ in range(c)]

        aux_keys = self._aux_keys

        def aux_of(mb):
            b = batches[mb]
            return {k: b[k] for k in aux_keys if k in b}

        def program(kind, j, mb):
            key = self._key(kind, j, self._batch_shape(batches[mb]))
            return self.step_cache.get(key, _not_compiled(key))

        def acc_of(j):
            acc = result["stage_grads"][j]
            return jax.tree.map(jnp.zeros_like, sparams[j]) \
                if acc is None else acc

        def make_forward(j):
            def forward(mb, h_in=None):
                if j == 0:
                    with spans.span(spans.DEVICE_PUT):
                        x = {k: jnp.asarray(v)
                             for k, v in batches[mb].items()}
                else:
                    x = h_in
                if j < c - 1:
                    stashes[j][mb] = x
                    return program("fwd", j, mb)(sparams[j], x, aux_of(mb))
                loss_sum, acc, gx = program("fwd_bwd", j, mb)(
                    sparams[j], x, aux_of(mb), acc_of(j))
                result["stage_grads"][j] = acc
                stashes[j][mb] = gx
                result["loss_sum"].parts.append(loss_sum)
                return loss_sum
            return forward

        def make_backward(j):
            def backward(mb, g_out):
                if j == c - 1:
                    return stashes[j].pop(mb)
                acc, gx = program("bwd", j, mb)(
                    sparams[j], stashes[j].pop(mb), g_out, aux_of(mb),
                    acc_of(j))
                result["stage_grads"][j] = acc
                return gx if j > 0 else acc
            return backward

        def make_step(j):
            def step():
                if on_step is not None and j == 0:
                    on_step(result)
            return step

        cbs = [StageCallbacks(make_forward(j), make_backward(j), make_step(j))
               for j in range(c)]
        return cbs, result


def _struct(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def _compile(fn, j: int, kind: str, shape: tuple, remat: bool, args,
             **jit_kw):
    """Ahead-of-time compile of stage ``j``'s ``kind`` program, named
    ``stage{j}_{kind}`` (the name is all that differs from a plain
    ``jax.jit(fn)``: the compiled code is the same). ``remat`` says whether
    the program checkpoints each period; the span records it."""
    fn.__name__ = fn.__qualname__ = f"stage{j}_{kind}"
    with spans.span(spans.COMPILE, stage=j, kind=kind,
                    shape="x".join(map(str, shape)), remat=int(remat)):
        return jax.jit(fn, **jit_kw).lower(*args).compile()


def _not_compiled(key):
    def build():
        raise RuntimeError(f"stage program {key} was not compiled before "
                           "the stage threads started")
    return build


class EncDecPipelinedModel(PipelinedModel):
    """Encoder-decoder stage layout over the same executor plumbing.

    The model's ``2 · n_periods`` periods (encoder then decoder) split into
    ``n_stages`` contiguous groups of ``k`` periods each; the enc/dec
    boundary must land on a stage boundary (``n_periods % k == 0``), so
    encoder periods occupy stages ``0..E-1`` and decoder periods (each with
    its period-major cross-attention block) stages ``E..c-1``. Stage 0 owns
    the embedding table; the first decoder stage owns a copy (decoder-side
    lookup) and the last stage a third (tied LM head) — their gradients sum
    in ``merge_stage_grads``. So do T5's relative bias tables: every
    encoder stage holds ``enc_rel_bias`` and every decoder stage
    ``dec_rel_bias``, as the stage's ``rel_bias``. The final encoder output ``he`` is forwarded
    along the pipe to every decoder stage as part of the ``(he, hd)``
    payload; ``jax.vjp`` over the pair carries cross-attention gradients
    back to the encoder stages through the ordinary grad channels.
    """

    _aux_keys = ("enc_positions", "enc_segment_ids", "dec_tokens",
                 "dec_positions", "dec_segment_ids", "labels", "loss_weights")

    def _init_layout(self):
        cfg, n_stages = self.cfg, self.n_stages
        self.k, self.n_enc_stages = self.layout(cfg, n_stages)
        self._cache_ns = ("encdec", repr(cfg), n_stages, self.impl)
        self._apply_fn = _encdec_stage_apply
        self._apply_static = (cfg, self.k, n_stages, self.n_enc_stages,
                              self.impl)

    @staticmethod
    def layout(cfg: ArchConfig, n_stages: int) -> tuple[int, int]:
        """(periods per stage, number of encoder stages) — raises when the
        2·n_periods total does not split evenly or a stage would straddle
        the encoder/decoder boundary."""
        total = 2 * cfg.n_periods
        if n_stages < 2 or total % n_stages:
            raise ValueError(
                f"{cfg.name}: {total} enc+dec periods do not split over "
                f"{n_stages} stages")
        k = total // n_stages
        if cfg.n_periods % k:
            raise ValueError(
                f"{cfg.name}: stage of {k} periods straddles the enc/dec "
                f"boundary at period {cfg.n_periods}")
        return k, cfg.n_periods // k

    @staticmethod
    def _batch_shape(b) -> tuple:
        enc, dec = b["enc_tokens"], b["dec_tokens"]
        return int(enc.shape[0]), int(enc.shape[1]), int(dec.shape[1])

    # ------------------------- param slicing ---------------------------
    def stage_params(self, j: int):
        k, e = self.k, self.n_enc_stages
        p: dict[str, Any] = {}
        if j < e:
            p["stack"] = jax.tree.map(lambda x: x[j * k : (j + 1) * k],
                                      self.full_params["enc"])
            if j == e - 1:
                p["enc_norm"] = self.full_params["enc_norm"]
        else:
            dj = j - e
            p["stack"] = jax.tree.map(lambda x: x[dj * k : (dj + 1) * k],
                                      self.full_params["dec"])
            p["cross"] = jax.tree.map(lambda x: x[dj * k : (dj + 1) * k],
                                      self.full_params["cross"])
            if j == self.n_stages - 1:
                p["dec_norm"] = self.full_params["dec_norm"]
        table = self._table(j)
        if table in self.full_params:
            p["rel_bias"] = self.full_params[table]
        if j == 0 or j == e or j == self.n_stages - 1:
            p["embed"] = self.full_params["embed"]
        return p

    def merge_stage_grads(self, stage_grads: list):
        e = self.n_enc_stages
        out = jax.tree.map(jnp.zeros_like, self.full_params)
        out = dict(
            out,
            enc=jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                             *[g["stack"] for g in stage_grads[:e]]),
            dec=jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                             *[g["stack"] for g in stage_grads[e:]]),
            cross=jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                               *[g["cross"] for g in stage_grads[e:]]),
        )
        for j, g in enumerate(stage_grads):
            for key in ("embed", "enc_norm", "dec_norm"):
                if key in g:
                    out[key] = out[key] + g[key]
            if "rel_bias" in g:
                out[self._table(j)] = out[self._table(j)] + g["rel_bias"]
        return out

    def _table(self, j: int) -> str:
        """The full-params key of stage ``j``'s T5 relative bias table."""
        return "enc_rel_bias" if j < self.n_enc_stages else "dec_rel_bias"


class LossSum:
    """A plan's summed loss, left on the device as one float32 scalar per
    micro-batch, in the order the last stage produced them. ``float()``
    reads them in one transfer and adds them on the host in that order:
    the float a ``float()`` of each scalar as it came, added up, would
    give."""

    def __init__(self):
        self.parts: list = []

    def __len__(self) -> int:
        return len(self.parts)

    def __float__(self) -> float:
        total = 0.0
        for x in jax.device_get(self.parts):
            total += float(x)
        return total


def weight_sum(batches: dict) -> float:
    """The micro-batches' summed loss weights, from the host's arrays. It
    equals the float32 sum ``_xent_sum`` computes on the device: the
    weights are 0 or 1 and a micro-batch holds fewer than 2**24 tokens, so
    both sums are exact."""
    return sum(float(np.sum(b["loss_weights"], dtype=np.float32))
               for b in batches.values())


def _xent_sum(head_w, h, labels, weights, cfg: ArchConfig):
    """Sum (not mean) xent + weight sum — summed across micro-batches, the
    iteration mean is taken once at optimizer time."""
    logits = jnp.einsum("btd,vd->btv", h, head_w).astype(jnp.float32)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    vocab_ok = jnp.arange(cfg.vocab_padded) < cfg.vocab
    logits = jnp.where(vocab_ok[None, None, :], logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    w = weights.astype(jnp.float32)
    return jnp.sum((lse - ll) * w), jnp.sum(w)
