"""One run of one cell: set-up, the measured window, and the check.

What the window drives is the program's training entry,
``PlanAheadRunner.run()``: plan-ahead planning on the planner pool, the
threaded pipeline executor, the Pallas attention kernels, the stage-grad
merge and the jitted donated AdamW. The benchmark hands it three things it
owns and observes the run through them:

- the stream (``Feed``): the runner calls ``batch(k + lookahead)`` at the
  top of iteration k, after iteration k-1's ``float(grad_norm)`` has waited
  for its optimizer step, so the calls are the iteration boundaries. The
  feed opens the window at the boundary of iteration ``WARM`` and closes it
  at the first boundary ``seconds`` later, by raising ``WindowClosed`` out
  of ``run()``;
- the runner's ``_obtain`` and ``_execute_replica`` and its backend's
  ``optimizer_step``, each wrapped (``Probe``) to record the plan wait the
  runner measured, the plans' padded shapes, each iteration's loss sums,
  the weights before step 1 (copied to the host) and the optimizer state
  after steps 1 and 3, under host spans the trace reduction names idle
  gaps by;
- the compiled-step cache, filled in set-up with every stage program the
  window's plans need (the pool is planned ahead with the program's own
  planner and compiled with its own ``compile_plan``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import math
import shutil
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from chip_bench import check, flops, traffic

WARM = 4            # iterations before the window: steps 1-3 are checked
MIN_STEP_S = 0.1    # the pool holds enough batches for steps this short
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# model-section keys that must equal the program configuration's fields
_MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_head",
               "d_ff", "vocab", "act", "norm_eps", "rope_theta", "mlp_gated",
               "tie_embeddings")


class WindowClosed(Exception):
    """Raised by the feed at the boundary that ends the window."""


def seed31(seed: int) -> int:
    """The run's seed as a non-negative int32 (PRNGKey and the runner)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def keep_every_program():
    """Write every program to the persistent compilation cache, however
    quick its compile, so that a warm run loads all and compiles none."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def program_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's ``model`` section."""
    spec = config["program"]
    cfg = dataclasses.replace(
        importlib.import_module(spec["config"]).CONFIG, **spec["replace"])
    model = config["model"]
    wrong = {k: (getattr(cfg, k), model[k]) for k in _MODEL_KEYS
             if getattr(cfg, k) != model[k]}
    if (cfg.family == "encdec") != (model["family"] == "encdec"):
        wrong["family"] = (cfg.family, model["family"])
    if wrong:
        raise ValueError(f"program config differs from the file: {wrong}")
    return cfg


@dataclass
class Window:
    """What the window did, for the metric readers."""
    model: dict
    peak: dict
    chips: int
    seconds: float
    iterations: list                      # dicts, one per window iteration
    compiles: int
    trace: dict | None = None


@dataclass
class Outcome:
    setup_s: float
    window: Window
    memory_peak_bytes: int
    faults: int
    readings: dict = field(default_factory=dict)   # program's check numbers
    phases: dict = field(default_factory=dict)     # set-up phase ends, s


class Tracer:
    """The profiler over the window, when asked for. It starts one warm
    iteration before the window opens, so that the profiler's own start-up
    (a stall of seconds in the first traced execution) falls in set-up."""

    def __init__(self, enabled: bool):
        self.enabled, self.running = enabled, False
        self.dir = tempfile.mkdtemp(prefix="chip_bench_trace_") \
            if enabled else None

    def start(self):
        import jax
        if self.enabled:
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.running = True

    def mark(self, name: str):
        import jax
        if self.enabled:
            with jax.profiler.TraceAnnotation(name):
                pass

    def stop(self):
        import jax
        if self.running:
            self.mark("bench.window_close")
            jax.profiler.stop_trace()
            self.running = False

    def reduce(self) -> dict:
        from chip_bench import trace as T
        tr = T.load(T.find_xplane(self.dir))
        lo, hi = T.window_bounds(tr, "bench.window_open", "bench.window_close")
        return T.reduce(tr, lo, hi)

    def cleanup(self):
        self.stop()
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


class Feed:
    """The stream handed to the runner; its calls mark iteration starts."""

    def __init__(self, batches, lookahead: int, seconds: float, probe,
                 tracer: Tracer):
        self.batches, self.lookahead, self.seconds = batches, lookahead, seconds
        self.probe, self.tracer = probe, tracer
        self.bounds: dict[int, float] = {}
        self.t_open = self.t_close = None
        self.closed_at: int | None = None

    def batch(self, k: int):
        import jax
        with jax.profiler.TraceAnnotation("bench.stream"):
            it = k - self.lookahead       # the iteration starting now
            if it >= 0:
                self._boundary(it, k)
            return self.batches[k]

    def _boundary(self, it: int, k: int):
        import jax
        now = time.perf_counter()
        if it == WARM - 1:
            self.tracer.start()
        elif it == WARM:
            self.tracer.mark("bench.window_open")
            now = self.t_open = time.perf_counter()
        elif self.t_open is not None and (now - self.t_open >= self.seconds
                                          or k >= len(self.batches)):
            jax.block_until_ready(self.probe.last_params)
            now = self.t_close = time.perf_counter()
            self.closed_at = it
            self.tracer.stop()
            self.bounds[it] = now
            raise WindowClosed
        self.bounds[it] = now


class Probe:
    """Records what the runner does, through the objects it calls."""

    def __init__(self, stacked):
        self.stacked = tuple(stacked)
        self.loss: dict[int, list] = {}
        self.plans: dict[int, tuple] = {}
        self.steps = 0
        self.last_params = None
        self.stats = None
        self.w0 = self.m1 = self.delta = None
        self._backend = None

    def attach(self, runner):
        import jax
        obtain, execute = runner._obtain, runner._execute_replica

        def _obtain(it, stats=None):
            with jax.profiler.TraceAnnotation("bench.plan_wait"):
                got = obtain(it, stats)
            self.stats = stats
            _, _, it_plan, wait, _ = got
            padded = sum(m.mbs * (sum(m.seq) if isinstance(m.seq, (tuple, list))
                                  else m.seq)
                         for rp in it_plan.replica_plans
                         for m in rp.micro_batches)
            self.plans[it] = (wait, int(padded))
            return got

        def _execute(it, rep, plan, gb, params):
            if runner.backend is not self._backend:
                self._wrap_optimizer(runner.backend)
            with jax.profiler.TraceAnnotation("bench.execute"):
                g, ls, ws = execute(it, rep, plan, gb, params)
            acc = self.loss.setdefault(it, [0.0, 0.0])
            acc[0] += float(ls)
            acc[1] += float(ws)
            return g, ls, ws

        runner._obtain, runner._execute_replica = _obtain, _execute

    def _wrap_optimizer(self, backend):
        import jax
        self._backend = backend
        step_fn = backend.optimizer_step

        def optimizer_step(params, grads, opt_state, opt_cfg, grad_scale=1.0):
            if self.steps == 0:     # the step donates the initial weights
                self.w0 = jax.device_get(params)
            with jax.profiler.TraceAnnotation("bench.optimizer"):
                out = step_fn(params, grads, opt_state, opt_cfg,
                              grad_scale=grad_scale)
            self.steps += 1
            self.last_params = out[0]
            if self.steps == 1:
                self.m1 = check.leaf_norms(out[1]["m"], self.stacked)
            if self.steps == check.STEPS:
                self.delta = check.leaf_diff_norms_host(
                    out[1]["master"], self.w0, self.stacked)
                self.w0 = None
            return out

        backend.optimizer_step = optimizer_step

    def readings(self) -> dict:
        loss = [self.loss[i][0] / max(self.loss[i][1], 1.0)
                for i in range(check.STEPS) if i in self.loss]
        return {"loss": loss, "m1": self.m1 or {}, "delta": self.delta or {}}


@contextlib.contextmanager
def compile_log():
    """perf_counter times of every XLA compile or persistent-cache load."""
    import jax
    times: list[float] = []

    def on_event(event, seconds, **_):
        if event == COMPILE_EVENT:
            times.append(time.perf_counter())
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield times
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _shape_key(m) -> tuple:
    return (m.mbs,) + (tuple(m.seq) if isinstance(m.seq, (tuple, list))
                       else (m.seq,))


def precompile(cfg, n_stages: int, rcfg, cache, pcfg, cost, batches,
               init_fn) -> int:
    """Plan every pool batch with the program's planner, compile into
    ``cache`` every stage program those plans use, and run each plan that
    brings a new shape once on zero weights. Returns the count of distinct
    (mbs, seq) shapes."""
    import jax
    import jax.numpy as jnp
    from repro.core.planner import plan_iteration
    from repro.data.dataset import materialize_micro_batch
    from repro.dist.backend import make_backend
    from repro.train.runner import PlanAheadRunner

    backend = make_backend(rcfg.backend, cfg, n_stages, impl=rcfg.impl,
                           step_cache=cache, use_executor=rcfg.use_executor,
                           exec_timeout=rcfg.exec_timeout,
                           strict=rcfg.strict_verify)
    pm = backend.pm
    if pm is None:
        raise RuntimeError(f"{cfg.name}: the threads backend would not run "
                           f"the pipelined executor over {n_stages} stages")
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          jax.eval_shape(init_fn))
    pm.set_params(params)
    seen: set = set()
    for gb in batches:
        plan = plan_iteration(PlanAheadRunner._plan_lengths(gb), cost,
                              pcfg).replica_plans[0]
        new = {_shape_key(m) for m in plan.micro_batches} - seen
        if not new:
            continue
        mbs = {m.mb_id: materialize_micro_batch(m, gb.tokens,
                                                lengths=gb.lengths)
               for m in plan.micro_batches}
        pm.compile_plan(plan, mbs)
        # and run each new shape once, so no first execution is timed
        backend.execute_plan(plan, params=params, batches=mbs)
        seen |= new
    pm.set_params(None)
    return len(seen)


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             peak: dict, step_cache=None) -> Outcome:
    """Set-up, then the window, then the program's check readings.
    ``step_cache`` lets several runs in one process share compiled stage
    programs (the calibration); a benchmark run makes its own."""
    import jax
    from repro.core.cost_model import AnalyticCostModel
    from repro.core.planner import PlannerConfig
    from repro.core.shapes import ShapePalette
    from repro.models import model as MD
    from repro.models import transformer as T
    from repro.train.optimizer import AdamWConfig
    from repro.train.runner import PlanAheadRunner, RunnerConfig
    from repro.train.step_cache import CompiledStepCache

    config, tr = cell.config, cell.traffic
    cfg = program_config(config)
    n_stages = config["n_stages"]
    s31 = seed31(seed)
    encdec = cfg.family == "encdec"
    n_pool = WARM + math.ceil(seconds / MIN_STEP_S) + tr["order_block"]
    phases = {"start": time.perf_counter() - t_start}
    batches = traffic.build(tr, cfg.vocab, encdec, seed, n_pool, WARM)
    phases["traffic"] = time.perf_counter() - t_start

    def init_fn():
        key = jax.random.PRNGKey(s31)
        return T.init_encdec(key, cfg) if encdec else MD.init_params(key, cfg)

    palette = ShapePalette.build(max_seq=tr["max_len"])
    pcfg = PlannerConfig(n_stages=n_stages, device_mem=config["device_mem"],
                         d_model=cfg.d_model, palette=palette)
    cost = AnalyticCostModel(cfg, n_stages=n_stages)
    rcfg = RunnerConfig(n_iters=len(batches) + 1, seed=s31, log_every=0)
    cache = step_cache if step_cache is not None else CompiledStepCache()
    tracer = Tracer(trace)
    probe = Probe(config["stacked"])
    feed = Feed(batches, rcfg.lookahead, seconds, probe, tracer)
    try:
        with compile_log() as compiles:
            phases["shapes"] = precompile(cfg, n_stages, rcfg, cache, pcfg,
                                          cost, batches, init_fn)
            phases["compiled"] = time.perf_counter() - t_start
            runner = PlanAheadRunner(cfg, cost, pcfg, rcfg, feed,
                                     opt_cfg=AdamWConfig(**config["optimizer"]),
                                     step_cache=cache)
            probe.attach(runner)
            try:
                runner.run()
            except WindowClosed:
                pass
        if feed.closed_at is None:
            raise RuntimeError("the run ended before the window closed")
        mem = jax.devices()[0].memory_stats() or {}
        its = []
        for i in range(WARM, feed.closed_at):
            wait, padded = probe.plans[i]
            its.append({"iteration": i,
                        "tokens": batches[i].total_tokens, "padded": padded,
                        "plan_wait_s": wait, "lengths": batches[i].lengths,
                        "step_s": feed.bounds[i + 1] - feed.bounds[i]})
        window = Window(
            model=config["model"], peak=peak, chips=cell.chips,
            seconds=feed.t_close - feed.t_open, iterations=its,
            compiles=sum(feed.t_open <= t <= feed.t_close for t in compiles),
            trace=tracer.reduce() if trace else None)
        out = Outcome(setup_s=feed.t_open - t_start, window=window,
                      memory_peak_bytes=int(mem.get("peak_bytes_in_use", 0)),
                      faults=int(probe.stats.faults) if probe.stats else 0,
                      readings=probe.readings(),
                      phases=dict(phases, first_iteration=feed.bounds[0]
                                  - t_start))
    finally:
        tracer.cleanup()
    del runner, probe, feed, cache
    gc.collect()
    return out


def check_readings(cell, seed: int, prec: str = "f32") -> dict:
    """The reference's readings for this run's first three batches."""
    from chip_bench import spec
    config = cell.config
    cfg = program_config(config)
    batches = traffic.build(cell.traffic, cfg.vocab, cfg.family == "encdec",
                            seed, WARM, WARM)[:check.STEPS]
    ref = spec.reference(config["reference"])
    return check.reference_readings(ref, config["model"],
                                     config["optimizer"], seed31(seed),
                                     batches, prec=prec)


def end_to_end(out: Outcome) -> dict:
    """The end-to-end numbers of a run, by metric name."""
    w = out.window
    steps_ms = [it["step_s"] * 1e3 for it in w.iterations]
    return {
        "real_tokens_per_s": sum(it["tokens"] for it in w.iterations)
        / w.seconds,
        "step_ms_p90": float(np.percentile(steps_ms, 90)),
        "setup_s": out.setup_s,
    }


def model_flops(w: Window) -> float:
    return sum(flops.model_flops(w.model, it["lengths"])
               for it in w.iterations)
