"""How ``correct`` is decided: the program's first three steps against the
plain reference's.

The window's own runner trains its first three iterations on pool batches
0, 1, 2; the reference trains the same three batches from the same seed in
float32 with the same AdamW. Three numbers are compared, each with its own
limit from the configuration file's ``limits``:

- ``loss_gap``: the relative gap of the first step's mean loss. All three
  steps' losses are printed beside it and must be finite, but the later
  two are not compared: on some seeds the loss spikes at step 2 in the
  program and the reference alike (gpt-paper-2L on a TPU v5e: 16.44
  against 16.50 where the first step read 11.34), and a spiked loss
  magnifies bf16's rounding twentyfold, so a limit on it would measure
  the spike;
- ``grad_gap``: from the optimizer's first moment after step 1 (which is
  (1 - b1) times the clipped mean gradient), the worst leaf's gap between
  the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and the median leaf's;
- ``delta_gap``: the same for the master weights' change over the three
  steps, leaving out leaves whose reference gradient norm is under a
  thousandth of the median leaf's (Adam moves those by round-off alone).

Layers stacked on a leading axis count as one leaf per layer.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3
QUIET_LEAF = 1e-3       # of the median leaf's first-gradient norm


def _path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _sumsq_tree(tree, stacked: tuple):
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        if path[0].key in stacked:
            out[_path_name(path)] = jnp.sum(x * x, axis=tuple(
                range(1, x.ndim)))
        else:
            out[_path_name(path)] = jnp.sum(x * x)
    return out


_sumsq = jax.jit(_sumsq_tree, static_argnums=(1,))


@functools.partial(jax.jit, static_argnums=(2,))
def _sumsq_diff(a, b, stacked: tuple):
    return _sumsq_tree(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b),
        stacked)


@functools.partial(jax.jit, static_argnums=(1,))
def _leaf_sumsq(x, stacked: bool):
    return jnp.sum(x * x, axis=tuple(range(1, x.ndim))) if stacked \
        else jnp.sum(x * x)


@functools.partial(jax.jit, static_argnums=(2,))
def _leaf_diff_sumsq(x, y, stacked: bool):
    d = x.astype(jnp.float32) - y.astype(jnp.float32)
    return jnp.sum(d * d, axis=tuple(range(1, d.ndim))) if stacked \
        else jnp.sum(d * d)


def _named(sums: dict) -> dict:
    out = {}
    for name, v in jax.device_get(sums).items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            for i, x in enumerate(v):
                out[f"{name}[{i}]"] = math.sqrt(float(x))
        else:
            out[name] = math.sqrt(float(v))
    return out


def leaf_norms(tree, stacked) -> dict:
    return _named(_sumsq(tree, tuple(stacked)))


def leaf_diff_norms(a, b, stacked) -> dict:
    return _named(_sumsq_diff(a, b, tuple(stacked)))


def leaf_diff_norms_host(a, b_host, stacked) -> dict:
    """``leaf_diff_norms`` against a tree held on the host, which goes to
    the device one leaf at a time: the device holds one extra leaf, not a
    second tree."""
    sums = {}
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b_host)):
        sums[_path_name(path)] = jax.block_until_ready(_leaf_diff_sumsq(
            x, jax.device_put(y), path[0].key in stacked))
    return _named(sums)


# ---------------------------------------------------------------- reference
def _grad_fn(ref, m: dict, prec: str):
    def acc_step(acc, params, blk):
        (ls, ws), g = jax.value_and_grad(
            lambda p: ref.loss(p, blk, m, prec), has_aux=True)(params)
        return jax.tree.map(jnp.add, acc, g), ls, ws
    return jax.jit(acc_step, donate_argnums=(0,))


def _adam_fn(opt: dict):
    b1, b2, lr, eps, wd = (opt["b1"], opt["b2"], opt["lr"], opt["eps"],
                           opt["weight_decay"])

    def upd(p, g, mom, vel, scale, step):
        g = g * scale
        mom = b1 * mom + (1 - b1) * g
        vel = b2 * vel + (1 - b2) * g * g
        mh = mom / (1 - b1 ** step)
        vh = vel / (1 - b2 ** step)
        return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p), mom, vel
    return jax.jit(upd, donate_argnums=(0,))


def reference_readings(ref, m: dict, opt: dict, seed: int, batches,
                       prec: str = "f32", block_tokens: int = 2048) -> dict:
    """Three AdamW steps of the reference on ``batches[:3]``: the losses,
    the first moment's leaf norms after step 1 and the weights' change
    after step 3, and the seconds it took. Moments live on the host between
    steps, so the device holds only the weights, one gradient tree and one
    block's work."""
    t0 = time.perf_counter()
    init = jax.jit(lambda: ref.init(seed, m))
    params = init()
    grad_fn, adam = _grad_fn(ref, m, prec), _adam_fn(opt)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    names = [_path_name(path) for path, _ in leaves]
    stacked = [path[0].key in ref.STACKED for path, _ in leaves]
    moms = vels = None
    out: dict = {"loss": []}
    for step in range(1, STEPS + 1):
        acc = jax.tree.map(jnp.zeros_like, params)
        ls = ws = 0.0
        for blk in ref.blocks(batches[step - 1], m, block_tokens):
            acc, l_, w_ = grad_fn(acc, params, blk)
            ls, ws = ls + float(l_), ws + float(w_)
        out["loss"].append(ls / max(ws, 1.0))
        gnorm = math.sqrt(sum(float(jnp.sum(jnp.square(g)))
                              for g in jax.tree.leaves(acc))) / max(ws, 1.0)
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-12)) / max(ws, 1.0)
        flat_p, flat_g = jax.tree.leaves(params), jax.tree.leaves(acc)
        del acc, params
        new_p, new_m, new_v, m1 = [], [], [], {}
        for i in range(len(flat_p)):
            p, g = flat_p[i], flat_g[i]
            flat_p[i] = flat_g[i] = None
            mom = jnp.zeros_like(p) if moms is None else jnp.asarray(moms[i])
            vel = jnp.zeros_like(p) if vels is None else jnp.asarray(vels[i])
            p, mom, vel = adam(p, g, mom, vel, jnp.float32(scale),
                               jnp.float32(step))
            del g
            new_p.append(p)
            if step == 1:
                m1[names[i]] = _leaf_sumsq(mom, stacked[i])
            if step < STEPS:        # moments wait on the host
                new_m.append(np.asarray(mom))
                new_v.append(np.asarray(vel))
            del mom, vel
        params = jax.tree.unflatten(treedef, new_p)
        moms, vels = new_m, new_v
        if step == 1:
            out["m1"] = _named(m1)
    p0 = init()
    out["delta"] = leaf_diff_norms(params, p0, ref.STACKED)
    out["seconds"] = time.perf_counter() - t0
    return out


# --------------------------------------------------------------- comparison
def _leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    names = [k for k in ref if keep is None or keep(k)]
    med = float(np.median([ref[k] for k in names]))
    worst, where = 0.0, ""
    for k in names:
        gap = abs(prog.get(k, 0.0) - ref[k]) / max(ref[k], med, 1e-30)
        if not gap <= worst:        # NaN counts as the worst
            worst, where = gap, k
    return worst, where


def gaps(prog: dict, ref: dict) -> dict:
    """The three compared numbers (and where the worst leaf is)."""
    losses = prog["loss"]
    loss = abs(losses[0] - ref["loss"][0]) / abs(ref["loss"][0]) \
        if len(losses) == STEPS and all(map(math.isfinite, losses)) \
        else math.inf
    med_g = float(np.median(list(ref["m1"].values())))
    grad, grad_at = _leaf_gap(prog.get("m1", {}), ref["m1"])
    delta, delta_at = _leaf_gap(
        prog.get("delta", {}), ref["delta"],
        keep=lambda k: ref["m1"][k] >= QUIET_LEAF * med_g)
    return {"loss_gap": loss, "grad_gap": grad, "delta_gap": delta,
            "grad_gap_leaf": grad_at, "delta_gap_leaf": delta_at}


def decide(g: dict, limits: dict) -> tuple[bool, dict]:
    """correct, and each compared number beside its limit."""
    checks = {k: {"value": g[k], "limit": limits[k]} for k in
              ("loss_gap", "grad_gap", "delta_gap")}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks

