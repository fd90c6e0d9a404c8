"""Elastic-training benchmark: throughput under faults vs fault-free.

ISSUE 7's bottom line: the plan-ahead runtime survives the full fault
trace — straggler, lost planner future, state-losing stage crash, replica
death — by replanning over the survivors and restoring from the newest
valid checkpoint, and the *last-occurrence* loss trajectory still matches
the fault-free run (deterministic streams make the replay bit-equal).

Three records over the same deterministic ``MultiTaskStream``:

- **fault_free** — dp_size=2 plan-ahead run, no chaos; the baseline wall
  time and loss trajectory.
- **faulted** — identical run with a composite ``FaultSchedule`` (one
  fault of each class across four consecutive iterations) plus a
  ``StragglerMonitor`` and periodic checkpoints. Reports recovery wall
  seconds, recovery-event kinds, the faulted/fault-free throughput ratio
  (machine-normalized — both runs share the box, so the ratio is
  gateable where absolute tokens/sec are not), and the max relative
  trajectory error vs fault_free.
- **calibration** — a deliberately mis-scaled cost model self-calibrates
  online during a short run; reports err_first/err_last (mean
  |log(pred/measured)|) and the learned scales.

Hard failures at generation time (mirrored by the CI gate in
``benchmarks/check_regression.py`` against the committed baseline):
the faulted run must complete every iteration, the recovered trajectory
must match fault-free to 1%, and calibration must reduce the error.

Records go to ``BENCH_elastic.json`` (``--smoke``: a smaller grid to
``BENCH_elastic_smoke.json``, used by CI).

``--processes`` (ISSUE 10) runs the *process fault domain* instead: one OS
process per DP replica over ``repro.dist.cluster``, with chaos delivered
as real ``os.kill(pid, SIGKILL)`` — a replica worker mid-run, then the
coordinator itself (forcing an election + checkpoint restore). Hard gates
at generation time (mirrored by ``check_regression.py::check_elastic_procs``):
every injected kill fires against a verifiably dead pid, both targets
(replica and coordinator) are covered, at least one election happens, the
recovered trajectory matches the process-domain fault-free run to 1%, and
teardown leaves no orphaned processes or checkpoint tmp dirs behind.
Records go to ``BENCH_elastic_procs[_smoke].json``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import tempfile
from pathlib import Path

import numpy as np

from repro.configs.base import get_arch, reduced
from repro.core.cost_model import AnalyticCostModel
from repro.core.planner import PlannerConfig
from repro.core.shapes import ShapePalette
from repro.data.streams import MultiTaskStream, StreamConfig
from repro.dist.chaos import (FaultEvent, FaultKind, FaultSchedule,
                              LogicalClock)
from repro.dist.fault import StragglerMonitor
from repro.train.runner import PlanAheadRunner, RunnerConfig

REPO_ROOT = Path(__file__).resolve().parents[1]

CFG = dataclasses.replace(reduced(get_arch("gpt-paper")), n_layers=2)
PAL = ShapePalette.build(min_seq=32, max_seq=128, seq_align=32, max_mbs=8)


def bench_json_path(smoke: bool) -> Path:
    return REPO_ROOT / f"BENCH_elastic{'_smoke' if smoke else ''}.json"


def procs_json_path(smoke: bool) -> Path:
    return REPO_ROOT / f"BENCH_elastic_procs{'_smoke' if smoke else ''}.json"


def make_stream(global_tokens: int, seed: int = 5) -> MultiTaskStream:
    return MultiTaskStream(StreamConfig(
        n_tasks=8, global_tokens=global_tokens, max_len=128,
        vocab=CFG.vocab, seed=seed))


def make_runner(n_iters: int, global_tokens: int, dp_size: int = 2,
                chaos=None, monitor=None, ckpt_dir: str = "",
                ckpt_every: int = 0, calibrate: bool = False,
                cost=None) -> PlanAheadRunner:
    cm = cost if cost is not None else AnalyticCostModel(CFG, n_stages=1)
    pcfg = PlannerConfig(n_stages=1, dp_size=dp_size, d_model=CFG.d_model,
                        palette=PAL)
    rcfg = RunnerConfig(n_iters=n_iters, use_executor=False, log_every=0,
                        ckpt_dir=str(ckpt_dir), ckpt_every=ckpt_every,
                        max_retries=3, plan_timeout=0.5,
                        retry_backoff_s=0.01, calibrate=calibrate,
                        exec_timeout=60.0)
    return PlanAheadRunner(CFG, cm, pcfg, rcfg,
                           make_stream(global_tokens),
                           monitor=monitor, chaos=chaos)


def fault_trace() -> FaultSchedule:
    """One fault of each class across four consecutive iterations — the
    acceptance trace of ISSUE 7."""
    return FaultSchedule([
        FaultEvent(1, FaultKind.STRAGGLER, stage=0, replica=1, delay_s=0.05),
        FaultEvent(2, FaultKind.PLANNER_LOST),
        FaultEvent(3, FaultKind.STAGE_CRASH, stage=0, state_lost=True),
        FaultEvent(4, FaultKind.REPLICA_DEAD, replica=1),
    ])


def _last_losses(history) -> dict:
    """iter -> loss of its LAST occurrence (recovery replays re-log)."""
    return {h["iter"]: h["loss"] for h in history}


def _throughput(history, stats) -> dict:
    wall = sum(h["time_s"] for h in history)
    # recovery-replayed iterations re-log: count each iteration's tokens once
    tokens = {h["iter"]: h["tokens"] for h in history}
    real = sum(tokens.values())
    return {
        "wall_s": round(wall, 4),
        "real_tokens": real,
        "tokens_per_s": round(real / max(wall, 1e-9), 1),
    }


def run_fault_free(n_iters: int, global_tokens: int, ckpt_dir: str) -> dict:
    _, history, stats = make_runner(
        n_iters, global_tokens, ckpt_dir=ckpt_dir, ckpt_every=2).run()
    rec = {"mode": "fault_free", "iters": n_iters, **_throughput(history, stats)}
    rec["losses"] = [round(v, 6) for _, v in sorted(_last_losses(history).items())]
    print(json.dumps(rec), flush=True)
    return rec


def run_faulted(n_iters: int, global_tokens: int, ckpt_dir: str,
                free_losses: list[float]) -> dict:
    clk = LogicalClock()
    mon = StragglerMonitor(2, heartbeat_timeout=2.0, window=4, clock=clk)
    chaos = fault_trace()
    runner = make_runner(n_iters, global_tokens, chaos=chaos, monitor=mon,
                         ckpt_dir=ckpt_dir, ckpt_every=2)
    _, history, stats = runner.run()

    losses = _last_losses(history)
    if sorted(losses) != list(range(n_iters)):
        raise SystemExit(f"faulted run did not complete every iteration: "
                         f"{sorted(losses)}")
    faulted = np.array([losses[i] for i in range(n_iters)])
    free = np.array(free_losses)
    traj_err = float(np.max(np.abs(faulted - free) / np.abs(free)))

    rec = {
        "mode": "faulted",
        "iters": n_iters,
        **_throughput(history, stats),
        "faults": stats.faults,
        "n_recoveries": len(stats.recoveries),
        "recovery_s": round(stats.recovery_s, 4),
        "recovery_kinds": sorted({r["kind"] for r in stats.recoveries}),
        "final_dp_size": runner.pcfg.dp_size,
        "faults_pending": len(chaos.pending()),
        "trajectory_max_rel_err": round(traj_err, 6),
    }
    print(json.dumps(rec), flush=True)
    if chaos.pending():
        raise SystemExit(f"declared faults never fired: {chaos.describe()}")
    if traj_err > 1e-2:
        raise SystemExit(
            f"recovered trajectory diverged from fault-free: "
            f"max rel err {traj_err:.4f} > 1e-2")
    return rec


def run_calibration(n_iters: int, global_tokens: int) -> dict:
    cm = AnalyticCostModel(CFG, n_stages=1)   # TPU roofline, wrong for CPU
    _, _, stats = make_runner(n_iters, global_tokens, dp_size=1,
                              calibrate=True, cost=cm).run()
    cal = stats.calibration
    rec = {
        "mode": "calibration",
        "iters": n_iters,
        "fwd_scale": round(cal["fwd_scale"], 4),
        "bwd_scale": round(cal["bwd_scale"], 4),
        "n_observed": cal["n_observed"],
        "err_first": round(cal["err_first"], 4),
        "err_last": round(cal["err_last"], 4),
    }
    print(json.dumps(rec), flush=True)
    if not rec["err_last"] < rec["err_first"]:
        raise SystemExit(
            f"online calibration did not reduce prediction error: "
            f"{rec['err_first']:.4f} -> {rec['err_last']:.4f}")
    return rec


# ------------------------- process fault domain -------------------------

def run_process_domain(n_iters: int, global_tokens: int, dp_size: int = 3,
                       chaos=None):
    """One full run in the process fault domain; returns
    ``(last-occurrence losses by iter, raw history, stats)``."""
    from repro.dist.cluster import ClusterConfig, run_process_cluster

    cm = AnalyticCostModel(CFG, n_stages=1)
    pcfg = PlannerConfig(n_stages=1, dp_size=dp_size, d_model=CFG.d_model,
                         palette=PAL)
    rcfg = RunnerConfig(n_iters=n_iters, use_executor=False, log_every=0,
                        ckpt_every=2, exec_timeout=60.0)
    _, history, stats = run_process_cluster(
        CFG, cm, pcfg, rcfg, make_stream(global_tokens), chaos=chaos,
        ccfg=ClusterConfig(n_replicas=dp_size, run_timeout_s=420.0))
    losses = _last_losses(history)
    if sorted(losses) != list(range(n_iters)):
        raise SystemExit(f"process run did not complete every iteration: "
                         f"{sorted(losses)}")
    return losses, history, stats


def procs_kill_trace() -> FaultSchedule:
    """The ISSUE 10 acceptance trace: SIGKILL a replica worker
    mid-iteration, then SIGKILL the coordinator (forcing an election)."""
    return FaultSchedule([
        FaultEvent(2, FaultKind.KILL_PROCESS, replica=2),
        FaultEvent(5, FaultKind.KILL_PROCESS, target="coordinator"),
    ])


def main_processes(smoke: bool = False):
    n_iters = 8 if smoke else 12
    global_tokens = 512 if smoke else 1024
    records = []

    free_losses, free_hist, free_stats = run_process_domain(
        n_iters, global_tokens)
    shutil.rmtree(free_stats.cluster["rundir"], ignore_errors=True)
    rec = {"mode": "procs_fault_free", "iters": n_iters,
           **_throughput(free_hist, free_stats)}
    rec["losses"] = [round(free_losses[i], 6) for i in range(n_iters)]
    print(json.dumps(rec), flush=True)
    records.append(rec)

    chaos = procs_kill_trace()
    losses, history, stats = run_process_domain(
        n_iters, global_tokens, chaos=chaos)
    cl = stats.cluster
    shutil.rmtree(cl["rundir"], ignore_errors=True)
    faulted = np.array([losses[i] for i in range(n_iters)])
    free = np.array([free_losses[i] for i in range(n_iters)])
    traj_err = float(np.max(np.abs(faulted - free) / np.abs(free)))
    rec = {
        "mode": "procs_faulted",
        "iters": n_iters,
        **_throughput(history, stats),
        "kills": cl["kills"],
        "elections": cl["elections"],
        "final_alive": cl["final_alive"],
        "orphans": len(cl["orphans"]),
        "tmp_dirs_left": len(cl["tmp_dirs_left"]),
        "trajectory_max_rel_err": round(traj_err, 6),
    }
    print(json.dumps(rec), flush=True)
    records.append(rec)

    # hard gates — the ISSUE 10 acceptance criteria, enforced at
    # generation time and re-checked against the committed baseline by
    # check_regression.py::check_elastic_procs
    if chaos.pending():
        raise SystemExit(f"declared kills never fired: {chaos.describe()}")
    if not all(k["verified_dead"] for k in cl["kills"]):
        raise SystemExit(f"a kill was not verified dead: {cl['kills']}")
    if {k["target"] for k in cl["kills"]} != {"replica", "coordinator"}:
        raise SystemExit(f"kills must cover both targets: {cl['kills']}")
    if cl["elections"] < 1:
        raise SystemExit("coordinator death did not trigger an election")
    if cl["orphans"] or cl["tmp_dirs_left"]:
        raise SystemExit(f"teardown left debris: orphans={cl['orphans']} "
                         f"tmp={cl['tmp_dirs_left']}")
    if traj_err > 1e-2:
        raise SystemExit(
            f"process-domain recovered trajectory diverged from fault-free: "
            f"max rel err {traj_err:.4f} > 1e-2")

    summary = {
        "mode": "_summary",
        "iters": n_iters,
        "n_kills": len(cl["kills"]),
        "kills_verified_dead": True,
        "targets": sorted({k["target"] for k in cl["kills"]}),
        "elections": cl["elections"],
        "orphans": 0,
        "tmp_dirs_left": 0,
        "trajectory_max_rel_err": rec["trajectory_max_rel_err"],
        "faulted_over_fault_free": round(
            rec["tokens_per_s"] / max(records[0]["tokens_per_s"], 1e-9), 3),
        "smoke": smoke,
    }
    print(json.dumps(summary), flush=True)
    records.append(summary)

    out = procs_json_path(smoke)
    out.write_text(json.dumps(records, indent=2) + "\n")
    print(f"wrote {out}", flush=True)


def main(smoke: bool = False):
    n_iters = 8 if smoke else 16
    global_tokens = 512 if smoke else 1024

    records = []
    with tempfile.TemporaryDirectory(prefix="bench-elastic-") as td:
        free = run_fault_free(n_iters, global_tokens, f"{td}/free")
        records.append(free)
        records.append(run_faulted(n_iters, global_tokens, f"{td}/faulted",
                                   free["losses"]))
    records.append(run_calibration(min(n_iters, 6), global_tokens))

    by = {r["mode"]: r for r in records}
    ratio = by["faulted"]["tokens_per_s"] / max(
        by["fault_free"]["tokens_per_s"], 1e-9)
    summary = {
        "mode": "_summary",
        "iters": n_iters,
        "faulted_over_fault_free": round(ratio, 3),
        "recovery_s": by["faulted"]["recovery_s"],
        "n_recoveries": by["faulted"]["n_recoveries"],
        "trajectory_max_rel_err": by["faulted"]["trajectory_max_rel_err"],
        "calibration_err_ratio": round(
            by["calibration"]["err_last"]
            / max(by["calibration"]["err_first"], 1e-9), 4),
        "smoke": smoke,
    }
    print(json.dumps(summary), flush=True)
    records.append(summary)

    out = bench_json_path(smoke)
    out.write_text(json.dumps(records, indent=2) + "\n")
    print(f"wrote {out}", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI variant (writes BENCH_elastic_smoke.json)")
    ap.add_argument("--processes", action="store_true",
                    help="process fault domain: one OS process per replica, "
                         "real SIGKILL chaos + coordinator election "
                         "(writes BENCH_elastic_procs[_smoke].json)")
    args = ap.parse_args()
    if args.processes:
        main_processes(smoke=args.smoke)
    else:
        main(smoke=args.smoke)
