"""The one traffic generator: a traffic file's parameters -> global batches.

The sampling is a copy of ``MultiTaskStream`` (``src/repro/data/streams.py``):
per-task lognormal lengths with log-means uniform over a range, a Pareto
tail mixed in per sample, power-law task weights, token-budgeted batches and
task-conditional affine-bigram token ids. Two things differ, both so that
runs with different seeds do the same work:

- The *lengths* of every batch come from the traffic file's own
  ``mix_seed``, never from the run's seed: every seed trains on the same
  set of batch shapes.
- The run's seed only reorders those batches (within blocks of
  ``order_block`` consecutive batches, the first block being the
  warm-up) and picks each sample's first token id.

A length spec is either ``{"fixed": n}`` or a lognormal mixture
``{"mean_range": [lo, hi], "sigma_range": [a, b], "clip": [min, max],
"tail_fraction": f, "tail_alpha": a}`` (tail optional). Decoder lengths
(``"dec"``) are drawn only for an encoder-decoder configuration.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

_TASK_SALT = 0x5EED
_BATCH_SALT = 7919
_ORDER_SALT = 0x0DE5
_TOKEN_SALT = 0x70C5


@dataclass(frozen=True)
class Task:
    mean_log_enc: float
    sigma_enc: float
    mean_log_dec: float
    sigma_dec: float
    weight: float
    bigram_a: int
    bigram_b: int


@dataclass
class Batch:
    """Duck-types ``repro.data.streams.GlobalBatch``: the runner reads
    ``lengths``, ``tokens`` and ``total_tokens``."""
    iteration: int
    lengths: np.ndarray          # (n, 2) int64: (enc or whole, dec)
    task_ids: np.ndarray
    tokens: list

    @property
    def n_samples(self) -> int:
        return len(self.lengths)

    @property
    def total_tokens(self) -> int:
        return int(self.lengths.sum())

    @property
    def has_decoder(self) -> bool:
        return bool(np.any(self.lengths[:, 1]))

    def enc_tokens(self, i: int) -> np.ndarray:
        return self.tokens[i][: int(self.lengths[i, 0])]

    def dec_tokens(self, i: int) -> np.ndarray:
        e = int(self.lengths[i, 0])
        return self.tokens[i][e: e + int(self.lengths[i, 1])]


def _log_range(spec: dict, key: str) -> tuple[float, float]:
    lo, hi = spec[key]
    return float(np.log(lo)), float(np.log(hi))


def make_tasks(tr: dict) -> list[Task]:
    """The task mixture, from the traffic file alone (``mix_seed``)."""
    rng = np.random.default_rng([tr["mix_seed"], _TASK_SALT])
    enc, dec = tr["enc"], tr.get("dec", {"fixed": 0})
    tasks = []
    for t in range(tr["n_tasks"]):
        # draw order as in make_stream_tasks: enc mean, enc sigma, dec
        # mean, dec sigma; fixed specs draw nothing
        me = rng.uniform(*_log_range(enc, "mean_range")) \
            if "fixed" not in enc else 0.0
        se = rng.uniform(*enc["sigma_range"]) if "fixed" not in enc else 0.0
        md = rng.uniform(*_log_range(dec, "mean_range")) \
            if "fixed" not in dec else 0.0
        sd = rng.uniform(*dec["sigma_range"]) if "fixed" not in dec else 0.0
        tasks.append(Task(me, se, md, sd,
                          weight=float((t + 1) ** -tr["task_weight_exponent"]),
                          bigram_a=31 + 2 * (t % 13), bigram_b=7 + (t % 97)))
    return tasks


def _draw(rng, spec: dict, mean_log: float, sigma: float) -> int:
    if "fixed" in spec:
        return int(spec["fixed"])
    n = rng.lognormal(mean_log, sigma)
    if spec.get("tail_fraction", 0.0) and rng.random() < spec["tail_fraction"]:
        n *= 1.0 + rng.pareto(spec["tail_alpha"])
    lo, hi = spec["clip"]
    return int(np.clip(n, lo, hi))


def batch_lengths(tr: dict, tasks: list[Task], index: int,
                  encdec: bool) -> tuple[np.ndarray, np.ndarray]:
    """Lengths and task ids of pool batch ``index``: a pure function of the
    traffic file and ``index``."""
    rng = np.random.default_rng([tr["mix_seed"], _BATCH_SALT, int(index)])
    w = np.array([t.weight for t in tasks])
    w = w / w.sum()
    lengths, tids, total = [], [], 0
    while total < tr["tokens_per_iteration"] or len(lengths) < 2:
        tid = int(rng.choice(len(tasks), p=w))
        task = tasks[tid]
        enc = _draw(rng, tr["enc"], task.mean_log_enc, task.sigma_enc)
        dec = 0
        if encdec:
            dec = _draw(rng, tr["dec"], task.mean_log_dec, task.sigma_dec)
            enc = min(enc, tr["max_len"] - dec)
        lengths.append((enc, dec))
        tids.append(tid)
        total += enc + dec
    return np.asarray(lengths, np.int64), np.asarray(tids, np.int64)


def run_order(n: int, warm: int, block: int, seed: int) -> list[int]:
    """Pool indices in the order a run with ``seed`` trains on them: the
    first ``warm`` permuted among themselves, then each block of ``block``
    permuted within itself. A window that ends mid-pool therefore covers
    the same set of batches for every seed, up to its last block."""
    rng = np.random.default_rng([int(seed), _ORDER_SALT])
    order = list(rng.permutation(min(warm, n)))
    for lo in range(warm, n, block):
        hi = min(n, lo + block)
        order += [lo + int(i) for i in rng.permutation(hi - lo)]
    return order


def _bigram(s0: int, a: int, b: int, v: int, n: int) -> np.ndarray:
    """next = (prev*a + b) % v from s0, by the doubling closed form of
    ``MultiTaskStream._sample_tokens``."""
    p = np.array([1], dtype=np.int64)
    t = np.array([0], dtype=np.int64)
    while len(p) < n:
        pm = (p[-1] * a) % v
        tm = (t[-1] + p[-1]) % v
        p = np.concatenate([p, (pm * p) % v])
        t = np.concatenate([t, (tm + pm * t) % v])
    return ((p[:n] * s0 + b * t[:n]) % v).astype(np.int32)


def build(tr: dict, vocab: int, encdec: bool, seed: int, n: int,
          warm: int) -> list[Batch]:
    """``n`` global batches in the run's order; batch k is iteration k."""
    tasks = make_tasks(tr)
    out = []
    for k, idx in enumerate(run_order(n, warm, tr["order_block"], seed)):
        lengths, tids = batch_lengths(tr, tasks, idx, encdec)
        rng = np.random.default_rng([int(seed), _TOKEN_SALT, int(idx)])
        tokens = [_bigram(int(rng.integers(0, vocab)), tasks[t].bigram_a,
                          tasks[t].bigram_b, vocab, int(e + d))
                  for t, (e, d) in zip(tids, lengths)]
        out.append(Batch(iteration=k, lengths=lengths, task_ids=tids,
                         tokens=tokens))
    return out
