"""The last pipeline stage leaves each micro-batch's loss on the device, and
the runner reads the step's losses once, after ``step_sync``.

A tiny two-stage decoder (gpt-paper) and encoder-decoder (t5-11b) runner,
three steps each: losses, grad norms and final weights are bit-identical to
``data/pipeline_losses.json``, recorded from the program when its last stage
still read each micro-batch's loss with ``float()`` on its thread
(``python tests/test_loss_on_device.py`` records it anew); no stage thread
reads a device value; the host's weight sum is the device's; and the two
waits the pipeline may still make, for the calibrator's timings and for a
monitor's replica times, wait on the work they time.
"""
import dataclasses
import hashlib
import json
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from repro.configs.base import get_arch, reduced
from repro.core import spans
from repro.core.cost_model import AnalyticCostModel
from repro.core.planner import PlannerConfig, plan_iteration
from repro.core.shapes import ShapePalette
from repro.data.dataset import materialize_micro_batch
from repro.data.streams import MultiTaskStream, StreamConfig
from repro.dist import backend as B
from repro.dist.fault import StragglerMonitor
from repro.models import model as MD
from repro.models import transformer as T
from repro.train import pipeline_adapter as PA
from repro.train.runner import PlanAheadRunner, RunnerConfig

FIXTURE = Path(__file__).parent / "data" / "pipeline_losses.json"
# max_mbs 2 makes 4-7 micro-batches a step, so the order of the sum counts
PAL = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32, max_mbs=2)
CASES = {
    "gpt": (dataclasses.replace(reduced(get_arch("gpt-paper")), n_layers=2),
            0.0),
    "t5": (dataclasses.replace(reduced(get_arch("t5-11b")), n_layers=1),
           1.0),
}
N_ITERS = 3


def _runner(family, n_iters=N_ITERS, dp_size=1, monitor=None):
    cfg, encdec_fraction = CASES[family]
    stream = StreamConfig(n_tasks=8, global_tokens=768, max_len=128,
                          vocab=cfg.vocab, seed=3,
                          encdec_fraction=encdec_fraction)
    pcfg = PlannerConfig(n_stages=2, dp_size=dp_size, d_model=cfg.d_model,
                         palette=PAL)
    rcfg = RunnerConfig(n_iters=n_iters, use_executor=True, log_every=0)
    return PlanAheadRunner(cfg, AnalyticCostModel(cfg, n_stages=2), pcfg,
                           rcfg, MultiTaskStream(stream), monitor=monitor)


def _digest(params) -> dict:
    return {jax.tree_util.keystr(path): hashlib.sha256(
                np.asarray(leaf).tobytes()).hexdigest()
            for path, leaf in jax.tree_util.tree_leaves_with_path(params)}


def _record(params, history) -> dict:
    return {"loss": [h["loss"].hex() for h in history],
            "grad_norm": [h["grad_norm"].hex() for h in history],
            "params_sha256": _digest(params)}


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each span's
    opening and closing, in order, with its thread and args."""

    def __init__(self, log, name, **args):
        self.log, self.name, self.args = log, name, dict(args)

    def __enter__(self):
        self.log.append(("open", self.name, threading.get_ident(),
                         self.args))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name, threading.get_ident(),
                         self.args))
        return False

    def set_metadata(self, **args):
        self.args.update(args)


@pytest.fixture(scope="module", params=list(CASES))
def run(request):
    """Three steps of one family, with every span and every device read
    (``ArrayImpl._value``, behind ``float()``, ``np.asarray`` and
    ``jax.device_get``) logged by thread."""
    log, reads = [], []
    value = ArrayImpl._value

    def logged_value(self):
        reads.append(threading.get_ident())
        return value.fget(self)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "_annotation",
                   lambda name, **args: _Spans(log, name, **args))
        mp.setattr(ArrayImpl, "_value", property(logged_value))
        params, history, _ = _runner(request.param).run()
    return dict(family=request.param, params=params, history=history,
                log=log, reads=reads, main=threading.get_ident())


def test_losses_bit_identical_to_reading_each_micro_batch(run):
    want = json.loads(FIXTURE.read_text())[run["family"]]
    assert _record(run["params"], run["history"]) == want


def test_no_stage_thread_reads_the_device(run):
    """Every device read is the runner thread's: the stage threads only
    dispatch. The pipeline ran on other threads."""
    names = {spans.stage(j, kind) for j in (0, 1) for kind in ("fwd", "bwd")}
    stage_threads = {tid for _, name, tid, _ in run["log"] if name in names}
    assert stage_threads and run["main"] not in stage_threads
    assert set(run["reads"]) == {run["main"]}


def test_loss_read_once_a_step_after_step_sync(run):
    log, main = run["log"], run["main"]
    assert not [e for e in log if e[1] == spans.LOSS_SYNC and e[2] != main]
    its = [i for i, e in enumerate(log)
           if e[:2] == ("open", spans.ITERATION)]
    assert len(its) == N_ITERS
    for k, (lo, h) in enumerate(zip(its, run["history"])):
        hi = its[k + 1] if k + 1 < N_ITERS else len(log)
        opened = [e[1] for e in log[lo:hi] if e[0] == "open" and e[2] == main]
        assert opened[-2:] == [spans.STEP_SYNC, spans.LOSS_SYNC]
        assert opened.count(spans.LOSS_SYNC) == 1
        sync = next(e for e in log[lo:hi] if e[1] == spans.LOSS_SYNC)
        assert sync[3]["n_reads"] == h["n_micro"]
        assert log[lo][3]["pipeline_syncs"] == 0


def _planned(family):
    cfg, encdec_fraction = CASES[family]
    stream = StreamConfig(n_tasks=8, global_tokens=768, max_len=128,
                          vocab=cfg.vocab, seed=3,
                          encdec_fraction=encdec_fraction)
    gb = MultiTaskStream(stream).batch(0)
    plan = plan_iteration(gb.lengths, AnalyticCostModel(cfg, n_stages=2),
                          PlannerConfig(n_stages=2, d_model=cfg.d_model,
                                        palette=PAL)).replica_plans[0]
    batches = {m.mb_id: materialize_micro_batch(m, gb.tokens,
                                                lengths=gb.lengths)
               for m in plan.micro_batches}
    init = T.init_encdec if cfg.family == "encdec" else MD.init_params
    return cfg, plan, batches, init(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("family", list(CASES))
def test_host_weight_sum_equals_the_devices(family):
    """AdamW's scale comes from the host's sum of the loss weights; it is
    the float32 sum the device's loss computes, for each micro-batch and
    for the step."""
    cfg, plan, batches, params = _planned(family)
    device_sum = jax.jit(lambda w: jnp.sum(w.astype(jnp.float32)))
    dev = 0.0
    for m in plan.micro_batches:
        w = batches[m.mb_id]["loss_weights"]
        one = float(device_sum(jnp.asarray(w)))
        assert PA.weight_sum({0: batches[m.mb_id]}) == one
        dev += one
    assert PA.weight_sum(batches) == dev
    res = B.ThreadsBackend(cfg, 2).execute_plan(plan, params=params,
                                                batches=batches)
    assert res.weight_sum == dev


@pytest.mark.parametrize("family", list(CASES))
def test_timed_callbacks_wait_on_every_program(family, monkeypatch):
    """With timings collected, each timed callback waits for what it
    dispatched: the last stage's forward for its loss, stage 0's backward
    for its gradient accumulator. One wait per record, so the calibrator's
    times cover compute."""
    cfg, plan, batches, params = _planned(family)
    waits = []
    block = jax.block_until_ready

    def wait(x):
        waits.append((threading.get_ident(), x))
        return block(x)
    monkeypatch.setattr(jax, "block_until_ready", wait)
    res = B.ThreadsBackend(cfg, 2).execute_plan(
        plan, params=params, batches=batches, collect_timings=True)
    n = len(plan.micro_batches)
    assert sorted(r[0] for r in res.timings) == sorted(
        ["f"] * n + ["total"] * n + ["b"] * n)
    main = threading.get_ident()
    waited = [x for tid, x in waits if tid != main]
    assert len(waited) == len(res.timings) == res.meta["pipeline_syncs"]
    # the last stage's losses and stage 0's accumulators among them
    assert sum(isinstance(x, jax.Array) and x.ndim == 0 for x in waited) == n
    assert sum(isinstance(x, dict) for x in waited) == n
    assert len(res.loss_sum) == n


@pytest.mark.parametrize("family", list(CASES))
def test_merge_waits_for_the_last_stages_last_program(family, monkeypatch):
    """The stage gradients are merged only once the last stage's last
    program is done: the merge's output is allocated when it is
    dispatched, and should not sit beside that program's temporaries. It
    is the plan's one wait on the device, on the calling thread."""
    cfg, plan, batches, params = _planned(family)
    backend = B.ThreadsBackend(cfg, 2)
    log = []
    block, merge = jax.block_until_ready, backend.pm.merge_stage_grads

    def wait(x):
        log.append(("wait", threading.get_ident(), x))
        return block(x)

    def merged(stage_grads):
        log.append(("merge", threading.get_ident(), None))
        return merge(stage_grads)
    monkeypatch.setattr(jax, "block_until_ready", wait)
    monkeypatch.setattr(backend.pm, "merge_stage_grads", merged)
    res = backend.execute_plan(plan, params=params, batches=batches)
    main = threading.get_ident()
    assert [e[:2] for e in log] == [("wait", main), ("merge", main)]
    assert log[0][2] is res.loss_sum.parts[-1]
    assert res.meta["pipeline_syncs"] == 0


def test_monitor_times_each_replica_to_the_end_of_its_compute(monkeypatch):
    """A runner with a monitor waits once at the end of each replica's
    plan, inside the time it reports for that replica, and counts it in
    ``pipeline_syncs``."""
    delay = 0.3
    block = jax.block_until_ready
    waited = []

    def slow_wait(x):
        if isinstance(x, dict):     # the monitor's wait, on the grads
            waited.append(threading.get_ident())
            time.sleep(delay)       # stands in for compute still queued
        return block(x)
    monkeypatch.setattr(jax, "block_until_ready", slow_wait)
    beats = []
    mon = StragglerMonitor(2, heartbeat_timeout=1e9)
    heartbeat = mon.heartbeat

    def record(rep, iter_time=None):
        beats.append(iter_time)
        return heartbeat(rep, iter_time=iter_time)
    monkeypatch.setattr(mon, "heartbeat", record)
    log = []
    monkeypatch.setattr(spans, "_annotation",
                        lambda name, **args: _Spans(log, name, **args))
    runner = _runner("gpt", n_iters=2, dp_size=2, monitor=mon)
    runner.run()
    its = [e[3] for e in log if e[:2] == ("open", spans.ITERATION)]
    assert [a["pipeline_syncs"] for a in its] == [2, 2]
    assert len(waited) == 4 and set(waited) == {threading.get_ident()}
    assert len(beats) == 4 and min(beats) >= delay


if __name__ == "__main__":
    FIXTURE.parent.mkdir(exist_ok=True)
    out = {}
    for fam in CASES:
        p, h, _ = _runner(fam).run()
        out[fam] = _record(p, h)
    FIXTURE.write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {FIXTURE}")
