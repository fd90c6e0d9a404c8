"""The idle-phase and program reduction (``chip_bench.phases``): on a
hand-built trace with a runner thread, a stage thread and a planner
thread; on the recorded TPU probe's programs; and on a traced run of the
plan-ahead runner recorded on a TPU v5e (``record_runner_probe.py``)."""
from pathlib import Path

import pytest

from chip_bench import phases as P
from chip_bench import trace as T

DATA = Path(__file__).parent / "data"
U = 1_000_000                  # 1 ms in trace nanoseconds
RUNNER, STAGE, PLANNER = 0, 1, 2


def _hand_built():
    """Device busy 10-20, 40-50 and 70-80 ms of a 0-100 ms window, one
    program each; the runner's iteration 0-90 ms in its phases."""
    ops = [(10 * U, 15 * U, "%fusion.1 = bf16[8] fusion(x)"),
           (15 * U, 20 * U, "%bitcast_add_fusion = bf16[8] fusion(y)"),
           (40 * U, 50 * U, "%fusion.2 = bf16[8] fusion(z)"),
           (70 * U, 78 * U, "%fusion.3 = f32[8] fusion(m)"),
           (78 * U, 80 * U, "%fusion.4 = f32[8] fusion(v)")]
    modules = [(10 * U, 20 * U, "jit_stage0_fwd"),
               (40 * U, 50 * U, "jit_stage1_bwd"),
               (70 * U, 80 * U, "jit_adamw_step")]
    spans = [
        (-10 * U, -1 * U, "dynapipe.iteration", RUNNER, {
            "predicted_compute_ms": 99.0}),
        (0, 90 * U, "dynapipe.iteration", RUNNER, {
            "predicted_compute_ms": 25.0}),
        (0, 5 * U, "dynapipe.submit", RUNNER, {}),
        (5 * U, 15 * U, "dynapipe.plan_wait", RUNNER, {}),
        (15 * U, 25 * U, "dynapipe.materialize", RUNNER, {}),
        (25 * U, 30 * U, "dynapipe.stage_setup", RUNNER, {}),
        (26 * U, 29 * U, "dynapipe.compile", RUNNER, {
            "stage": 0, "kind": "fwd", "shape": "2x64"}),
        (30 * U, 60 * U, "dynapipe.pipeline", RUNNER, {}),
        (60 * U, 62 * U, "dynapipe.grad_merge", RUNNER, {}),
        (62 * U, 65 * U, "dynapipe.optimizer", RUNNER, {}),
        (65 * U, 85 * U, "dynapipe.step_sync", RUNNER, {}),
        (92 * U, 150 * U, "dynapipe.iteration", RUNNER, {
            "predicted_compute_ms": 99.0}),
        # the stage thread is busy in a forward, then waits, all inside the
        # runner's pipeline span
        (30 * U, 45 * U, "dynapipe.stage0.fwd", STAGE, {"mb": 0}),
        (45 * U, 60 * U, "dynapipe.recv_wait", STAGE, {}),
        # a planner thread's spans, and phase names off the runner's
        # thread, count for nothing
        (0, 100 * U, "dynapipe.plan", PLANNER, {}),
        (30 * U, 60 * U, "dynapipe.submit", PLANNER, {}),
        (82 * U, 98 * U, "dynapipe.pipeline", PLANNER, {}),
    ]
    tr = T.Trace(ops=[sorted(ops)])
    return tr, P.Program(spans=sorted(spans, key=lambda s: s[:4]),
                         modules=[modules])


def test_idle_goes_to_the_runner_threads_phase():
    tr, prog = _hand_built()
    assert P.runner_line(prog.spans, 0, 100 * U) == RUNNER
    ph = P.idle_phases(tr.ops[0], prog.spans, 0, 100 * U)
    # idle 0-10, 20-40, 50-70 and 80-100 ms
    assert ph["prep"] == pytest.approx(20e-3)       # 0-10, 20-30
    assert ph["pipeline"] == pytest.approx(20e-3)   # 30-40, 50-60
    assert ph["step_end"] == pytest.approx(30e-3)   # 60-70, 80-100
    idle = 1e-9 * sum(e - s for s, e in T.gaps(tr.ops[0], 0, 100 * U))
    assert sum(ph.values()) == pytest.approx(idle)
    assert idle == pytest.approx(
        (100 * U - T.busy_ns(tr.ops[0], 0, 100 * U)) * 1e-9)


def test_programs_and_their_ops():
    tr, prog = _hand_built()
    secs = P.program_seconds(prog.modules[0], 0, 100 * U)
    assert secs == pytest.approx({"jit_stage0_fwd": 10e-3,
                                  "jit_stage1_bwd": 10e-3,
                                  "jit_adamw_step": 10e-3})
    # clipped to the window
    assert P.program_seconds(prog.modules[0], 0, 15 * U) == pytest.approx(
        {"jit_stage0_fwd": 5e-3})
    top = P.program_top_ops(tr.ops[0], prog.modules[0], 0, 100 * U, n=1)
    assert top == {"jit_stage0_fwd": [["fusion.1", pytest.approx(5e-3)]],
                   "jit_stage1_bwd": [["fusion.2", pytest.approx(10e-3)]],
                   "jit_adamw_step": [["fusion.3", pytest.approx(8e-3)]]}


def test_reduce_and_metrics():
    tr, prog = _hand_built()
    red = P.reduce(tr, prog, 0, 100 * U)
    assert red["iterations"] == 1           # the one ending in the window
    assert red["predicted_compute_s"] == pytest.approx(25e-3)
    assert red["compiles"] == [[0, "fwd", "2x64", pytest.approx(3e-3)]]
    m = P.metrics(red)
    assert m == pytest.approx({
        "idle_prep_ms": 20.0, "idle_pipeline_ms": 20.0,
        "idle_step_end_ms": 30.0, "optimizer_device_ms": 10.0,
        # measured stage programs 20 ms against 25 ms predicted
        "cost_model_error": 25.0})
    assert P.metrics(dict(red, iterations=0)) == {}


def test_tpu_probe_programs():
    tr = T.load(str(DATA / "probe.xplane.pb"))
    prog = P.load(str(DATA / "probe.xplane.pb"))
    lo, hi = tr.ops[0][0][0], tr.ops[0][-1][1]
    secs = P.program_seconds(prog.modules[0], lo, hi)
    assert set(secs) == {"jit_mm", "jit_attn", "jit__lambda"}
    # three runs of each program; modules cover their ops
    assert sum(secs.values()) >= T.busy_ns(tr.ops[0], lo, hi) * 1e-9
    top = P.program_top_ops(tr.ops[0], prog.modules[0], lo, hi)
    assert set(top) == set(secs)
    # the two matmuls of ``mm``; the Pallas kernel of ``attn``
    assert {op for op, _ in top["jit_mm"][:2]} == {
        "fusion", "convolution_tanh_fusion"}
    assert top["jit_attn"][0][0] == "attn.1"


@pytest.fixture(scope="module")
def runner_probe():
    path = str(DATA / "runner.xplane.pb")
    tr, prog = T.load(path), P.load(path)
    lo, hi = T.window_bounds(tr, "bench.window_open", "bench.window_close")
    return tr, prog, lo, hi


def test_runner_probe_spans(runner_probe):
    _, prog, lo, hi = runner_probe
    names = {s[2] for s in prog.spans}
    # every program span but ``compile`` (all compiled before the trace)
    assert names == {
        "dynapipe." + n for n in (
            "iteration", "submit", "plan_wait", "materialize",
            "stage_setup", "pipeline", "grad_merge", "optimizer",
            "step_sync", "stage0.fwd", "stage0.bwd", "stage1.fwd",
            "stage1.bwd", "device_put", "loss_sync", "recv_wait", "plan")}
    main = P.runner_line(prog.spans, lo, hi)
    off_main = {s[2] for s in prog.spans if s[3] != main}
    assert off_main == {"dynapipe." + n for n in (
        "stage0.fwd", "stage0.bwd", "stage1.fwd", "stage1.bwd",
        "device_put", "loss_sync", "recv_wait", "plan")}


def test_runner_probe_reduction(runner_probe):
    tr, prog, lo, hi = runner_probe
    red = P.reduce(tr, prog, lo, hi)
    assert red["iterations"] == 2 and red["compiles"] == []
    # the phases partition the idle time device_idle_share counts
    busy = T.reduce(tr, lo, hi)["busy_s"]
    assert sum(red["idle_phase_s"].values()) == pytest.approx(
        red["idle_s"], abs=1e-9)
    assert red["idle_s"] == pytest.approx((hi - lo) * 1e-9 - busy, abs=1e-9)
    assert {f"jit_stage{j}_{k}" for j in (0, 1) for k in ("fwd", "bwd")} \
        | {"jit_adamw_step"} <= set(red["program_s"])
    m = P.metrics(red)
    assert set(m) == {"idle_prep_ms", "idle_pipeline_ms", "idle_step_end_ms",
                      "optimizer_device_ms", "cost_model_error"}
    assert all(v > 0 for v in m.values())
