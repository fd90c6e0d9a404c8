"""The process fault domain (ISSUE 10): real corpses, real recovery.

PR 6's invariant — a faulted run's last-occurrence loss trajectory equals
the fault-free one — was proven inside one process, where replica death
was simulated heartbeat silence. These tests prove it transfers across
actual process corpses: one OS process per DP replica
(``repro.dist.cluster``), socket heartbeats, ``kill -9`` as the fault
injector, coordinator election, and checkpoint-restore + deterministic
stream replay as the recovery path. Also covers the satellite fixes that
make the shared checkpoint directory safe under real crashes: the
pid-aware ``_sweep_tmp`` (only dead writers' tmp dirs are swept) and
torn-write recovery after a SIGKILL mid-``save()``.
"""
import dataclasses
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.core.cost_model import AnalyticCostModel
from repro.core.planner import PlannerConfig
from repro.core.shapes import ShapePalette
from repro.data.streams import MultiTaskStream, StreamConfig
from repro.dist.chaos import (FaultEvent, FaultKind, FaultSchedule,
                              deliver_kill)
from repro.dist.cluster import ClusterConfig, _Conn, run_process_cluster
from repro.train import checkpoint as CKPT
from repro.train.runner import PlanAheadRunner, RunnerConfig
from tests.conftest import SRC, run_subprocess_devices

CFG = dataclasses.replace(reduced(get_arch("gpt-paper")), n_layers=2)
PAL = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32, max_mbs=8)
STREAM_CFG = StreamConfig(n_tasks=8, global_tokens=512, max_len=128,
                          vocab=CFG.vocab, seed=5)


def _last_losses(history) -> dict:
    """iter -> loss of its LAST occurrence (recovery replays re-log)."""
    return {h["iter"]: h["loss"] for h in history}


def _dead_pid() -> int:
    """A pid that provably belonged to a real (now dead, reaped) process."""
    p = subprocess.Popen([sys.executable, "-c", "pass"])
    p.wait(timeout=30)
    return p.pid


# ----------------------------------------------------------- chaos layer --
def test_take_process_kills_claims_each_event_once():
    sched = FaultSchedule([
        FaultEvent(2, FaultKind.KILL_PROCESS, replica=1),
        FaultEvent(5, FaultKind.KILL_PROCESS, target="coordinator"),
    ])
    assert sched.take_process_kills(1) == []
    first = sched.take_process_kills(3)
    assert [e.replica for e in first] == [1]
    assert sched.take_process_kills(3) == []      # claimed exactly once
    late = sched.take_process_kills(9)            # past-due events still fire
    assert [e.target for e in late] == ["coordinator"]
    assert sched.pending() == []


def test_kill_event_describe_names_target():
    ev = FaultEvent(4, FaultKind.KILL_PROCESS, target="coordinator")
    assert "target=coordinator" in ev.describe()
    ev = FaultEvent(4, FaultKind.KILL_PROCESS, replica=2)
    assert "target=replica" in ev.describe() and "replica=2" in ev.describe()


def test_deliver_kill_leaves_a_verified_corpse():
    p = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        assert deliver_kill(p.pid, wait_s=30.0)
        with pytest.raises(ProcessLookupError):
            os.kill(p.pid, 0)                     # really dead, really reaped
    finally:
        p.poll()


# ------------------------------------------------------------ wire frames --
def test_conn_frames_roundtrip_json_and_blob():
    a, b = socket.socketpair()
    ca, cb = _Conn(a), _Conn(b)
    try:
        ca.send({"type": "plan", "epoch": 3, "iter": 7}, b"\x00\x01binary")
        msg, blob = cb.recv()
        assert msg == {"type": "plan", "epoch": 3, "iter": 7}
        assert blob == b"\x00\x01binary"
        cb.send({"type": "heartbeat"})            # empty blob path
        msg, blob = ca.recv()
        assert msg["type"] == "heartbeat" and blob == b""
    finally:
        ca.close()
        cb.close()


# --------------------------------------------------------- runner routing --
def test_runner_config_routes_process_fault_domain(monkeypatch):
    """fault_domain='process' must bypass the in-process loop entirely and
    hand the exact run configuration to the cluster driver."""
    import repro.dist.cluster as cluster

    seen = {}

    def fake(cfg, cost, pcfg, rcfg, stream, opt_cfg=None, chaos=None,
             ccfg=None):
        seen.update(rcfg=rcfg, pcfg=pcfg, chaos=chaos)
        return "params", ["history"], "stats"

    monkeypatch.setattr(cluster, "run_process_cluster", fake)
    cm = AnalyticCostModel(CFG, n_stages=1)
    pcfg = PlannerConfig(n_stages=1, dp_size=2, d_model=CFG.d_model,
                         palette=PAL)
    rcfg = RunnerConfig(n_iters=3, fault_domain="process", log_every=0)
    out = PlanAheadRunner(CFG, cm, pcfg, rcfg,
                          MultiTaskStream(STREAM_CFG)).run()
    assert out == ("params", ["history"], "stats")
    assert seen["rcfg"].fault_domain == "process"
    assert seen["pcfg"].dp_size == 2


def test_process_cluster_refuses_a_tpu_platform(monkeypatch, tmp_path):
    """On a TPU host every replica process would need the one chip: the
    cluster must refuse before it spawns a worker or makes a run dir."""
    import multiprocessing

    import jax

    def no_spawn(*a, **k):
        raise AssertionError("spawned a process on a TPU platform")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(multiprocessing, "get_context", no_spawn)
    rundir = tmp_path / "run"
    cm = AnalyticCostModel(CFG, n_stages=1)
    pcfg = PlannerConfig(n_stages=1, dp_size=2, d_model=CFG.d_model,
                         palette=PAL)
    with pytest.raises(RuntimeError, match="CPU test harness"):
        run_process_cluster(CFG, cm, pcfg, RunnerConfig(n_iters=1),
                            MultiTaskStream(STREAM_CFG),
                            ccfg=ClusterConfig(n_replicas=2,
                                               rundir=str(rundir)))
    assert not rundir.exists()


def test_make_backend_process_points_at_cluster():
    from repro.dist.backend import make_backend

    with pytest.raises(ValueError, match="fault_domain='process'"):
        make_backend("process", CFG, 1)


# ------------------------------------------- checkpoint sweep (satellite) --
def _tree(seed=0, n=2):
    rng = np.random.default_rng(seed)
    return {f"w{i}": rng.normal(size=(4, 4)).astype(np.float32)
            for i in range(n)}


def test_sweep_tmp_spares_live_writers_tmp_dir(tmp_path):
    """Only dead writers' .tmp dirs are swept: a concurrent live writer's
    in-flight tmp (pid alive) must survive, as must unparseable names."""
    dead = tmp_path / f".tmp-3-{_dead_pid()}-aaaaaaaa"
    dead.mkdir()
    (dead / "junk.npy").write_bytes(b"torn")
    live = tmp_path / f".tmp-4-{os.getpid()}-bbbbbbbb"
    live.mkdir()
    (live / "inflight.npy").write_bytes(b"half")
    weird = tmp_path / ".tmp-weird"
    weird.mkdir()

    CKPT.save(tmp_path, 1, _tree())

    assert not dead.exists(), "dead writer's tmp must be swept"
    assert live.exists(), "live writer's in-flight tmp must be left alone"
    assert weird.exists(), "unparseable tmp names are never deleted"
    assert CKPT.latest_step(tmp_path) == 1


# ------------------------------------------- conftest timeout (satellite) --
def test_subprocess_timeout_reports_partial_output():
    code = ("import sys, time\n"
            "print('PARTIAL-MARKER', flush=True)\n"
            "time.sleep(600)\n")
    t0 = time.monotonic()
    with pytest.raises(AssertionError) as ei:
        run_subprocess_devices(code, n_devices=1, timeout=3)
    assert time.monotonic() - t0 < 60, "child must be killed, not waited out"
    assert "timed out after 3s" in str(ei.value)
    assert "PARTIAL-MARKER" in str(ei.value)


# ------------------------------------- torn-write recovery under SIGKILL --
@pytest.mark.slow
def test_sigkill_mid_save_leaves_recoverable_dir(tmp_path):
    """SIGKILL a child mid-``save()``: the torn attempt must never become
    a visible checkpoint (``load_latest_valid`` restores the previous
    step), and the next ``save()`` sweeps only the dead writer's tmp."""
    ckpt = tmp_path / "ckpt"
    marker = tmp_path / "MARKER"
    code = f"""
import os, sys, time
import numpy as np
from repro.train import checkpoint as CKPT

ckpt = {str(ckpt)!r}
tree = {{"w0": np.arange(16, dtype=np.float32).reshape(4, 4),
         "w1": np.ones((4, 4), dtype=np.float32)}}
CKPT.save(ckpt, 1, tree)
orig = np.save
def slow_save(path, arr):
    orig(path, arr)
    open({str(marker)!r}, "w").write("mid-save")
    time.sleep(600)
CKPT.np.save = slow_save
CKPT.save(ckpt, 2, tree)
"""
    env = dict(os.environ, PYTHONPATH=str(SRC))
    p = subprocess.Popen([sys.executable, "-c", code], env=env)
    try:
        deadline = time.monotonic() + 120
        while not marker.exists():
            assert time.monotonic() < deadline, "child never reached save(2)"
            assert p.poll() is None, "child died before the mid-save kill"
            time.sleep(0.02)
        os.kill(p.pid, signal.SIGKILL)
    finally:
        p.wait(timeout=30)

    torn = list(ckpt.glob(".tmp-2-*"))
    assert len(torn) == 1, "mid-save SIGKILL must leave the torn tmp behind"
    assert int(torn[0].name.split("-")[2]) == p.pid

    # the torn attempt never surfaced: newest *valid* checkpoint is step 1
    like = {"w0": np.zeros((4, 4), np.float32),
            "w1": np.zeros((4, 4), np.float32)}
    state, manifest = CKPT.load_latest_valid(ckpt, like)
    assert manifest["step"] == 1
    np.testing.assert_array_equal(
        np.asarray(state["w0"]),
        np.arange(16, dtype=np.float32).reshape(4, 4))

    # next save sweeps ONLY the dead writer's tmp dir
    live = ckpt / f".tmp-9-{os.getpid()}-cafecafe"
    live.mkdir()
    CKPT.save(ckpt, 3, {k: np.asarray(v) for k, v in state.items()})
    assert not torn[0].exists(), "dead writer's torn tmp must be swept"
    assert live.exists(), "live writer's tmp must survive the sweep"
    assert CKPT.latest_step(ckpt) == 3


# ------------------------------------------------- the cluster, end to end --
def _cluster(n_iters, dp_size, chaos=None, ckpt_every=2):
    cm = AnalyticCostModel(CFG, n_stages=1)
    pcfg = PlannerConfig(n_stages=1, dp_size=dp_size, d_model=CFG.d_model,
                         palette=PAL)
    rcfg = RunnerConfig(n_iters=n_iters, use_executor=False, log_every=0,
                        ckpt_every=ckpt_every, fault_domain="process")
    runner = PlanAheadRunner(CFG, cm, pcfg, rcfg, MultiTaskStream(STREAM_CFG),
                             chaos=chaos)
    params, history, stats = runner.run()
    return params, history, stats


def _inprocess_losses(n_iters, dp_size):
    cm = AnalyticCostModel(CFG, n_stages=1)
    pcfg = PlannerConfig(n_stages=1, dp_size=dp_size, d_model=CFG.d_model,
                         palette=PAL)
    rcfg = RunnerConfig(n_iters=n_iters, use_executor=False, log_every=0)
    _, history, _ = PlanAheadRunner(CFG, cm, pcfg, rcfg,
                                    MultiTaskStream(STREAM_CFG)).run()
    return _last_losses(history)


@pytest.mark.slow
def test_process_cluster_matches_inprocess_trajectory():
    """The same run through real worker processes produces the same loss
    trajectory as the in-process runner: batches are rebuilt from pure
    ``batch(k)``, grads merge in the same order, AdamW is deterministic."""
    n = 3
    params, history, stats = _cluster(n, dp_size=2)
    shutil.rmtree(stats.cluster["rundir"], ignore_errors=True)
    got = _last_losses(history)
    assert sorted(got) == list(range(n))
    assert stats.cluster["completed"] and not stats.cluster["orphans"]
    assert params is not None
    want = _inprocess_losses(n, dp_size=2)
    a = np.array([got[i] for i in range(n)])
    b = np.array([want[i] for i in range(n)])
    np.testing.assert_allclose(a, b, rtol=1e-3)


@pytest.mark.slow
def test_coordinator_sigkill_elects_successor_and_recovers():
    """kill -9 the coordinator's process mid-run: the surviving rank must
    elect itself, restore from the shared checkpoint dir (or replay from
    scratch), and finish every iteration on the fault-free trajectory."""
    n = 4
    chaos = FaultSchedule(
        [FaultEvent(1, FaultKind.KILL_PROCESS, target="coordinator")])
    params, history, stats = _cluster(n, dp_size=2, chaos=chaos)
    cl = stats.cluster
    shutil.rmtree(cl["rundir"], ignore_errors=True)

    assert chaos.pending() == []
    assert len(cl["kills"]) == 1
    assert cl["kills"][0]["target"] == "coordinator"
    assert cl["kills"][0]["verified_dead"], \
        "the kill must leave a verified dead pid, not simulated silence"
    assert cl["elections"] >= 1, "coordinator death must trigger an election"
    assert cl["completed"] and cl["final_alive"] == [1]
    assert not cl["orphans"] and not cl["tmp_dirs_left"]

    got = _last_losses(history)
    assert sorted(got) == list(range(n))
    want = _inprocess_losses(n, dp_size=2)
    a = np.array([got[i] for i in range(n)])
    b = np.array([want[i] for i in range(n)])
    np.testing.assert_allclose(a, b, rtol=1e-2)
