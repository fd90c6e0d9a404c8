"""Process-per-replica fault domain (ISSUE 10): real corpses, real recovery.

PR 6 proved the drain -> replan -> restore loop inside ONE process, where
"replica death" was simulated heartbeat silence. This module makes the
fault domain real: one OS process per DP replica, heartbeats over localhost
TCP sockets, ``kill -9`` as the fault injector, and the same invariant —
the recovered loss trajectory equals the fault-free one — now across
actual dead pids.

Topology
--------
``run_process_cluster`` (the *driver*, typically the test/bench process or
``PlanAheadRunner`` with ``RunnerConfig.fault_domain="process"``) spawns
``n_replicas`` worker processes (spawn context — the same discipline as
``core/planner.PlannerPool``: importing repro loads jax, and forking a
multithreaded jax parent risks deadlock). Every process is the same
archetype, ``_Worker``; the *coordinator role* attaches to the lowest live
rank (rank 0 initially) as extra threads inside that worker's process, so
killing the coordinator also kills a replica — the harshest failover case.

The coordinator:

- accepts worker connections and feeds their socket heartbeats into the
  existing :class:`~repro.dist.fault.StragglerMonitor` (real clock:
  ``heartbeat_timeout_s`` wall seconds); socket EOF is the fast death
  signal (SIGKILL closes the peer's fds), the monitor catches hung-alive
  processes and supplies per-replica speed factors;
- plans each iteration over the survivors (``plan_iteration`` with
  ``dp_size=len(alive)``) and distributes per-replica
  :class:`~repro.core.instructions.ExecutionPlan`'s as JSON (the verified
  round-trip fixed point from PR 9) through one :class:`ProcessBackend`
  per rank — the PR 8 ``ExecutionBackend`` protocol, with gradients and
  losses collected back over the wire;
- runs *epoch-numbered membership*: every membership change (a worker's
  socket dies, its heartbeats stop, or a coordinator is elected) bumps a
  monotonic epoch, re-published in ``coordinator.json``. Every message
  carries the epoch; stale workers' results and deposed coordinators'
  commands are fenced by key, and a half-collected iteration is simply
  re-planned over the survivors under the new epoch — safe because the
  optimizer step (the only irreversible action) is broadcast only after
  ALL survivors' gradients merged.

What is *not* transferred, and why that is safe: batches are never sent —
``stream.batch(k)`` is a pure function of ``(StreamConfig, k)``
(data/streams.py), so every worker rebuilds its micro-batches from the
integer ``k`` alone. Params are never sent either — all replicas hold the
same replicated params, apply the same broadcast merged gradient with the
same deterministic AdamW update, and therefore stay bit-identical.

Coordinator election: when a worker's connection dies and
``coordinator.json``'s pid is a verified corpse, the lowest-rank survivor
(by signal-0 probe of the ``worker-{rank}.json`` registry) claims the next
epoch via an ``O_EXCL`` lock file, starts the coordinator role in-process,
and re-publishes ``coordinator.json``. The new coordinator restores the
whole cluster from the shared CRC-verified checkpoint directory
(``train/checkpoint.load_latest_valid``) — or fresh seed-deterministic
init when none exists — and resumes planning from that step with
deterministic stream replay, which is what makes the post-failover
trajectory equal the fault-free run.

Fault injection: the driver polls ``history.jsonl`` for progress and
delivers :class:`~repro.dist.chaos.FaultKind.KILL_PROCESS` events as real
``os.kill(pid, SIGKILL)`` (:func:`repro.dist.chaos.deliver_kill`),
verifying each target is an actual dead pid before recording the kill.

Wire protocol: length-prefixed frames over localhost TCP — an 8-byte
header (u32 json length, u32 blob length, big-endian), a UTF-8 JSON
control message, and an optional binary blob (pickled numpy pytrees; the
sockets only ever connect spawned children of one trusted local driver).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import multiprocessing
import os
import pickle
import signal
import socket
import struct
import sys
import tempfile
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np

from repro.dist.chaos import FaultSchedule, deliver_kill
from repro.dist.fault import StragglerMonitor

COORD_FILE = "coordinator.json"
HISTORY_FILE = "history.jsonl"
EVENTS_FILE = "events.jsonl"
RESULT_FILE = "result.json"


@dataclass(frozen=True)
class ClusterConfig:
    """Knobs of the process fault domain (everything else rides in the
    same ``ArchConfig``/``PlannerConfig``/``RunnerConfig`` the in-process
    runner uses)."""

    n_replicas: int = 2
    host: str = "127.0.0.1"
    heartbeat_interval_s: float = 0.1
    heartbeat_timeout_s: float = 2.0     # wall seconds of silence = dead
    connect_timeout_s: float = 60.0      # worker boot / reconnect budget
    result_timeout_s: float = 120.0      # per-iteration gradient collect
    election_poll_s: float = 0.05
    election_timeout_s: float = 60.0
    run_timeout_s: float = 600.0         # driver's hard wall clock
    rundir: str = ""                     # "" = private tempdir


class WorkerDied(RuntimeError):
    """A replica's socket died or its heartbeats stopped mid-collect."""

    def __init__(self, rank: int, why: str):
        super().__init__(f"worker {rank} died: {why}")
        self.rank = rank


# ---------------------------------------------------------------------------
# small file/pid helpers (shared by driver, coordinator, workers)
# ---------------------------------------------------------------------------

def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


def _atomic_json(path: Path, obj: dict) -> None:
    tmp = path.with_name(f".{path.name}.{os.getpid()}.tmp")
    tmp.write_text(json.dumps(obj))
    os.replace(tmp, path)


def _read_json(path: Path) -> Optional[dict]:
    try:
        return json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return None


def _append_jsonl(path: Path, obj: dict) -> None:
    # O_APPEND single-write lines: atomic enough for the one-live-writer-
    # at-a-time (plus short post-SIGKILL overlap) discipline used here
    with open(path, "a") as f:
        f.write(json.dumps(obj) + "\n")


def _read_jsonl(path: Path) -> list[dict]:
    out = []
    try:
        text = path.read_text()
    except OSError:
        return out
    for line in text.splitlines():
        with contextlib.suppress(json.JSONDecodeError):
            out.append(json.loads(line))
    return out


def _tree_to_bytes(tree) -> bytes:
    """Pytree -> pickled numpy tree (device_get'd, dtype-preserving)."""
    import jax

    host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
    return pickle.dumps(host, protocol=pickle.HIGHEST_PROTOCOL)


def _tree_from_bytes(blob: bytes):
    return pickle.loads(blob)


# ---------------------------------------------------------------------------
# framed-message connection
# ---------------------------------------------------------------------------

class _Conn:
    """One framed-message TCP connection. ``send`` is thread-safe (the
    heartbeat thread and the serving loop share it); ``recv`` has a single
    reader by construction."""

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self._slock = threading.Lock()

    def send(self, msg: dict, blob: bytes = b"") -> None:
        data = json.dumps(msg).encode()
        frame = struct.pack(">II", len(data), len(blob)) + data + blob
        with self._slock:
            self.sock.sendall(frame)

    def _recv_exact(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("peer closed")
            buf += chunk
        return bytes(buf)

    def recv(self) -> tuple[dict, bytes]:
        lj, lb = struct.unpack(">II", self._recv_exact(8))
        msg = json.loads(self._recv_exact(lj).decode())
        blob = self._recv_exact(lb) if lb else b""
        return msg, blob

    def close(self) -> None:
        with contextlib.suppress(OSError):
            self.sock.shutdown(socket.SHUT_RDWR)
        with contextlib.suppress(OSError):
            self.sock.close()


# ---------------------------------------------------------------------------
# ProcessBackend: the ExecutionBackend protocol over the wire
# ---------------------------------------------------------------------------

class ProcessBackend:
    """PR 8 ``ExecutionBackend`` over a socket to one replica process.

    ``execute_plan`` ships the plan's JSON (iteration + epoch ride in
    ``plan.meta``) and blocks until that worker's gradients return as a
    :class:`~repro.dist.backend.BackendResult`. ``params``/``batches`` are
    deliberately NOT shipped: the worker owns its replicated params, and
    rebuilds the batch from the deterministic stream. ``optimizer_step``
    broadcasts the merged gradient to every live replica (each applies the
    identical AdamW update locally) — the coordinator's whole data plane
    goes through this class, which is what routes
    ``RunnerConfig.fault_domain="process"`` through the backend API.
    """

    name = "process"

    def __init__(self, coord: "_Coordinator", rank: int):
        self.coord = coord
        self.rank = rank

    def execute_plan(self, plan, *, params=None, batches=None, callbacks=None,
                     hook=None, collect_timings: bool = False,
                     timeout: Optional[float] = None):
        from repro.dist.backend import BackendResult

        if callbacks is not None:
            raise ValueError("the process backend ships plans to worker "
                             "processes; callback-driven execution is the "
                             "threads backend's host plane")
        if hook is not None:
            raise ValueError("the process fault domain injects real process "
                             "faults (chaos KILL_PROCESS via the driver); "
                             "executor hooks do not cross process boundaries")
        it = int(plan.meta["iteration"])
        ep = int(plan.meta["epoch"])
        self.coord.send_to(self.rank, {
            "type": "plan", "epoch": ep, "iter": it,
            "collect_timings": bool(collect_timings),
            "plan": plan.to_json()})
        msg, blob = self.coord.await_msg(
            "result", ep, it, self.rank,
            timeout if timeout is not None
            else self.coord.ccfg.result_timeout_s)
        grads = _tree_from_bytes(blob) if blob else None
        return BackendResult(grads, float(msg["loss_sum"]),
                             float(msg["weight_sum"]),
                             [tuple(t) for t in msg.get("timings") or []])

    def place_opt_state(self, opt_state):
        return opt_state    # workers own (and place) their own opt state

    def optimizer_step(self, params, grads, opt_state, opt_cfg):
        """Broadcast the merged (unscaled) grads + scale; every surviving
        worker applies the same deterministic AdamW update locally."""
        gnorm = self.coord.broadcast_step(grads)
        return params, opt_state, {"grad_norm": gnorm}


# ---------------------------------------------------------------------------
# coordinator role
# ---------------------------------------------------------------------------

def _plan_lengths(gb):
    L = gb.lengths
    return L[:, 0] if not np.any(L[:, 1]) else L


class _Coordinator:
    """The planning/membership brain; lives as threads inside the lowest
    live rank's worker process."""

    def __init__(self, rundir: Path, epoch: int, payload: dict, rank: int):
        self.rundir = rundir
        self.payload = payload
        self.cfg = payload["cfg"]
        self.cost = payload["cost"]
        self.pcfg = payload["pcfg"]
        self.rcfg = payload["rcfg"]
        self.stream = payload["stream"]
        self.ccfg: ClusterConfig = payload["ccfg"]
        self.n = self.ccfg.n_replicas
        self.epoch = epoch
        self.rank = rank
        self.elected = epoch > 0

        self.lock = threading.Lock()
        self.cv = threading.Condition(self.lock)
        self.conns: dict[int, _Conn] = {}
        self.sock_dead: set[int] = set()
        self.inbox: dict[tuple, tuple] = {}
        self.monitor = StragglerMonitor(
            self.n, heartbeat_timeout=self.ccfg.heartbeat_timeout_s)
        self.scale_pending: Optional[dict] = None

        self.srv = socket.create_server((self.ccfg.host, 0), backlog=self.n + 2)
        self.port = self.srv.getsockname()[1]
        self._publish()
        self._event({"kind": "coordinator_start", "rank": rank,
                     "pid": os.getpid(), "elected": self.elected})
        threading.Thread(target=self._accept_loop, daemon=True,
                         name="coord-accept").start()

    # --------------------------- bookkeeping ---------------------------
    def _publish(self) -> None:
        _atomic_json(self.rundir / COORD_FILE, {
            "epoch": self.epoch, "rank": self.rank, "pid": os.getpid(),
            "port": self.port})

    def _event(self, obj: dict) -> None:
        _append_jsonl(self.rundir / EVENTS_FILE,
                      dict(obj, epoch=self.epoch, t=time.time()))

    # ----------------------------- sockets -----------------------------
    def _accept_loop(self) -> None:
        while True:
            try:
                sock, _ = self.srv.accept()
            except OSError:
                return       # server closed at shutdown
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._reader, args=(_Conn(sock),),
                             daemon=True, name="coord-reader").start()

    def _reader(self, conn: _Conn) -> None:
        rank = None
        try:
            msg, _ = conn.recv()
            if msg.get("type") != "hello":
                conn.close()
                return
            rank = int(msg["rank"])
            with self.cv:
                self.conns[rank] = conn
                self.sock_dead.discard(rank)
                self.monitor.heartbeat(rank)
                self.cv.notify_all()
            while True:
                msg, blob = conn.recv()
                t = msg["type"]
                if t == "heartbeat":
                    self.monitor.heartbeat(rank)
                    continue
                key = (t, int(msg["epoch"]), int(msg["iter"]), rank)
                if t == "result" and msg.get("iter_time") is not None:
                    self.monitor.heartbeat(rank, iter_time=msg["iter_time"])
                with self.cv:
                    self.inbox[key] = (msg, blob)
                    self.cv.notify_all()
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()
            if rank is not None:
                with self.cv:
                    if self.conns.get(rank) is conn:
                        del self.conns[rank]
                        self.sock_dead.add(rank)
                    self.cv.notify_all()

    def send_to(self, rank: int, msg: dict, blob: bytes = b"") -> None:
        with self.lock:
            conn = self.conns.get(rank)
        if conn is None:
            raise WorkerDied(rank, "no live connection")
        try:
            conn.send(msg, blob)
        except (ConnectionError, OSError) as e:
            with self.cv:
                if self.conns.get(rank) is conn:
                    del self.conns[rank]
                    self.sock_dead.add(rank)
                self.cv.notify_all()
            raise WorkerDied(rank, f"send failed: {e!r}") from e

    def await_msg(self, type_: str, epoch: int, it: int, rank: int,
                  timeout: float) -> tuple[dict, bytes]:
        key = (type_, epoch, it, rank)
        deadline = time.monotonic() + timeout
        with self.cv:
            while True:
                if key in self.inbox:
                    return self.inbox.pop(key)
                if rank in self.sock_dead:
                    raise WorkerDied(rank, "socket closed")
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self.cv.wait(min(left, 0.25))
        # timed out: a hung-but-connected worker is declared dead by the
        # heartbeat monitor, a slow-but-alive one is a hard cluster error
        if rank not in self.monitor.alive():
            with self.cv:
                self.sock_dead.add(rank)
                self.cv.notify_all()
            raise WorkerDied(rank, "heartbeat timeout")
        raise TimeoutError(
            f"worker {rank} still heartbeats but produced no {type_} for "
            f"iteration {it} within {timeout}s")

    # --------------------------- membership ----------------------------
    def _registry_live(self) -> set[int]:
        live = set()
        for r in range(self.n):
            info = _read_json(self.rundir / f"worker-{r}.json")
            if info is None:
                # bootstrap: every rank was just spawned, a missing file
                # means still booting — wait for it. Post-election the
                # registry is complete, so missing == never existed.
                if not self.elected:
                    live.add(r)
            elif _pid_alive(int(info["pid"])):
                live.add(r)
        return live

    def _wait_members(self) -> list[int]:
        deadline = time.monotonic() + self.ccfg.connect_timeout_s
        while time.monotonic() < deadline:
            expected = self._registry_live()
            with self.lock:
                have = set(self.conns)
            if expected and expected <= have:
                break
            time.sleep(self.ccfg.election_poll_s)
        with self.lock:
            return sorted(self.conns)

    def _alive_now(self) -> list[int]:
        hb = set(self.monitor.alive())
        with self.lock:
            return sorted((set(self.conns) - self.sock_dead) & hb)

    # --------------------------- data plane ----------------------------
    def broadcast_step(self, grads) -> float:
        """Send merged grads + scale + checkpoint duty to every survivor;
        collect acks. Once this starts the iteration is committed: a rank
        that fails to ack is declared dead and leaves the membership, but
        the survivors all applied the identical update."""
        st = self.scale_pending
        assert st is not None, "broadcast_step outside an iteration"
        blob = _tree_to_bytes(grads)
        alive = list(st["alive"])
        saver = min(alive)
        for rank in alive:
            with contextlib.suppress(WorkerDied):
                self.send_to(rank, {
                    "type": "step", "epoch": st["epoch"], "iter": st["iter"],
                    "scale": st["scale"],
                    "save": bool(st["save"]) and rank == saver}, blob)
        gnorm = float("nan")
        for rank in alive:
            with contextlib.suppress(WorkerDied):
                msg, _ = self.await_msg("step_ok", st["epoch"], st["iter"],
                                        rank, self.ccfg.result_timeout_s)
                if rank == saver:
                    gnorm = float(msg["grad_norm"])
        return gnorm

    def _restore_round(self, alive: list[int]) -> int:
        """Reset every survivor to the newest CRC-valid shared checkpoint
        (or fresh deterministic init) so the cluster resumes from one
        consistent step. Mandatory after election: a coordinator death
        between partial step broadcasts may have left replicas divergent."""
        ep = self.epoch
        for r in alive:
            self.send_to(r, {"type": "restore", "epoch": ep, "iter": -1})
        resumes = []
        for r in alive:
            msg, _ = self.await_msg("restore_ok", ep, -1, r,
                                    self.ccfg.result_timeout_s)
            resumes.append(int(msg["resume"]))
        resume = min(resumes) if resumes else 0
        self._event({"kind": "restore", "resume": resume,
                     "resumes": resumes, "alive": alive})
        return resume

    # ---------------------------- main loop ----------------------------
    def run(self) -> None:
        try:
            self._run()
        except BaseException as e:    # noqa: BLE001 — reporting path
            self._event({"kind": "coordinator_error", "err": repr(e),
                         "tb": traceback.format_exc()})
            raise
        finally:
            with contextlib.suppress(OSError):
                self.srv.close()

    def _run(self) -> None:
        rcfg, pcfg = self.rcfg, self.pcfg
        from repro.core.planner import plan_iteration

        alive = self._wait_members()
        if not alive:
            raise RuntimeError("no workers connected")
        prev_alive = list(alive)
        self._event({"kind": "membership", "alive": alive, "iter": -1})
        it = self._restore_round(alive)
        end = rcfg.n_iters
        backends = {r: ProcessBackend(self, r) for r in range(self.n)}
        pool = ThreadPoolExecutor(max_workers=max(2, self.n),
                                  thread_name_prefix="coord-dispatch")
        try:
            while it < end:
                alive = self._alive_now()
                if alive != prev_alive:
                    self.epoch += 1
                    self._publish()
                    self._event({
                        "kind": "membership", "iter": it, "alive": alive,
                        "dead": sorted(set(prev_alive) - set(alive)),
                        "joined": sorted(set(alive) - set(prev_alive))})
                    prev_alive = list(alive)
                if not alive:
                    raise RuntimeError(
                        f"iteration {it}: all replicas dead")
                t0 = time.perf_counter()
                gb = self.stream.batch(it)
                p = dataclasses.replace(pcfg, dp_size=len(alive))
                if len(alive) > 1 and \
                        self.monitor.drift() > rcfg.drift_tolerance:
                    sf = self.monitor.speed_factors()
                    p = dataclasses.replace(
                        p, speed_factors=[sf[r] for r in alive])
                it_plan = plan_iteration(_plan_lengths(gb), self.cost, p)

                ep = self.epoch
                futs = {}
                for pos, rank in enumerate(alive):
                    rp = it_plan.replica_plans[pos]
                    rp.meta["iteration"] = it
                    rp.meta["epoch"] = ep
                    futs[rank] = pool.submit(backends[rank].execute_plan, rp)
                try:
                    results = {r: f.result() for r, f in futs.items()}
                except WorkerDied as e:
                    # membership changed mid-collect: the epoch bump at the
                    # top of the loop fences every partial result (inbox
                    # keys carry the old epoch) and the same iteration is
                    # re-planned over the survivors — no optimizer step
                    # ran, so replay is exact
                    self._event({"kind": "replica_lost", "iter": it,
                                 "rank": e.rank, "why": str(e)})
                    continue

                grads, loss_sum, w_sum = None, 0.0, 0.0
                for rank in alive:         # ascending: deterministic merge
                    res = results[rank]
                    loss_sum += res.loss_sum
                    w_sum += res.weight_sum
                    if res.grads is not None:
                        grads = res.grads if grads is None else \
                            _tree_add(grads, res.grads)
                scale = 1.0 / max(w_sum, 1.0)
                save = bool(
                    rcfg.ckpt_every
                    and (it + 1) % rcfg.ckpt_every == 0) or it == end - 1
                self.scale_pending = {"epoch": ep, "iter": it, "alive": alive,
                                      "scale": scale, "save": save}
                _, _, om = backends[min(alive)].optimizer_step(
                    None, grads, None, None)
                self.scale_pending = None

                dt = time.perf_counter() - t0
                padded = sum(
                    m.mbs * (sum(m.seq) if isinstance(m.seq, (tuple, list))
                             else m.seq)
                    for rp in it_plan.replica_plans
                    for m in rp.micro_batches)
                _append_jsonl(self.rundir / HISTORY_FILE, {
                    "epoch": ep, "iter": it,
                    "loss": loss_sum / max(w_sum, 1.0),
                    "time_s": dt,
                    "n_micro": sum(len(rp.micro_batches)
                                   for rp in it_plan.replica_plans),
                    "grad_norm": om["grad_norm"],
                    "dp_size": len(alive),
                    "tokens": gb.total_tokens,
                    "padded_tokens": int(padded),
                })
                it += 1

            _atomic_json(self.rundir / RESULT_FILE, {
                "completed": True, "iters": end, "epoch": self.epoch,
                "final_alive": prev_alive, "coordinator_rank": self.rank,
                "elected": self.elected})
            with self.lock:
                conns = dict(self.conns)
            for _rank, conn in sorted(conns.items()):
                with contextlib.suppress(ConnectionError, OSError):
                    conn.send({"type": "shutdown", "epoch": self.epoch,
                               "iter": end})
        finally:
            pool.shutdown(wait=False, cancel_futures=True)


def _tree_add(a, b):
    import jax

    return jax.tree.map(lambda x, y: x + y, a, b)


# ---------------------------------------------------------------------------
# worker process
# ---------------------------------------------------------------------------

class _Worker:
    """One DP replica: owns a full replicated copy of params + opt state,
    executes shipped plans over locally-rebuilt batches, applies broadcast
    merged gradients, and participates in coordinator election."""

    def __init__(self, rundir: Path, rank: int, payload: dict):
        self.rundir = rundir
        self.rank = rank
        self.payload = payload
        self.cfg = payload["cfg"]
        self.pcfg = payload["pcfg"]
        self.rcfg = payload["rcfg"]
        self.opt_cfg = payload["opt_cfg"]
        self.stream = payload["stream"]
        self.ccfg: ClusterConfig = payload["ccfg"]
        self.ckpt_dir = self.rcfg.ckpt_dir
        # -1 so the bootstrap claim (no coordinator.json yet) lands on
        # epoch 0; every real election claims a strictly positive epoch
        self.epoch_seen = -1
        self.done = False
        self.coordinator: Optional[_Coordinator] = None
        self._coord_dead_pids: set[int] = set()
        self._connect_fails: dict[tuple, int] = {}
        self._t0 = time.monotonic()

        from repro.dist.backend import ThreadsBackend

        self.backend = ThreadsBackend(
            self.cfg, self.pcfg.n_stages, impl=self.rcfg.impl,
            use_executor=self.rcfg.use_executor,
            exec_timeout=self.rcfg.exec_timeout)
        self.params, self.opt = self._fresh_state()
        _atomic_json(rundir / f"worker-{rank}.json",
                     {"rank": rank, "pid": os.getpid()})

    def _fresh_state(self):
        """Seed-deterministic init: identical in every process, so replicas
        start (and, under identical updates, stay) bit-identical."""
        import jax

        from repro.models import model as MD
        from repro.models import transformer as T
        from repro.train.optimizer import init_opt_state

        key = jax.random.PRNGKey(self.rcfg.seed)
        params = (T.init_encdec(key, self.cfg)
                  if self.cfg.family == "encdec"
                  else MD.init_params(key, self.cfg))
        return params, init_opt_state(params, self.opt_cfg)

    # ------------------------ election / discovery ---------------------
    def _live_ranks(self) -> list[int]:
        """Ranks presumed alive from the registry. A rank whose file
        exists but whose pid is dead is a corpse; a rank with NO file yet
        is *still booting* during the initial connect window (registry
        files are written before first connect, so a boot race must not
        let a higher rank win the bootstrap election from rank 0) and only
        counts as dead once that window has passed."""
        booting = (time.monotonic() - self._t0) < self.ccfg.connect_timeout_s
        live = []
        for r in range(self.ccfg.n_replicas):
            info = _read_json(self.rundir / f"worker-{r}.json")
            if info is None:
                if booting:
                    live.append(r)
            elif _pid_alive(int(info["pid"])):
                live.append(r)
        return live

    def _claim_epoch(self, epoch: int) -> bool:
        path = self.rundir / f".claim-{epoch}"
        try:
            fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            claimant = _read_json(path)
            if claimant and not _pid_alive(int(claimant.get("pid", -1))):
                # the claimant died between claim and publish: release
                with contextlib.suppress(OSError):
                    os.unlink(path)
            return False
        with os.fdopen(fd, "w") as f:
            f.write(json.dumps({"pid": os.getpid(), "rank": self.rank}))
        return True

    def _locate_coordinator(self) -> dict:
        """Find a live coordinator to serve, or become one: the lowest
        live registry rank claims ``epoch+1`` and starts the role
        in-process (the deterministic election rule)."""
        deadline = time.monotonic() + self.ccfg.election_timeout_s
        while time.monotonic() < deadline and not self.done:
            info = _read_json(self.rundir / COORD_FILE)
            if info and int(info["pid"]) not in self._coord_dead_pids \
                    and _pid_alive(int(info["pid"])):
                return info
            survivors = self._live_ranks()
            if survivors and survivors[0] == self.rank:
                epoch = max(self.epoch_seen,
                            int(info["epoch"]) if info else -1) + 1
                if self._claim_epoch(epoch):
                    coord = _Coordinator(self.rundir, epoch,
                                         self.payload, self.rank)
                    self.coordinator = coord
                    threading.Thread(target=coord.run, daemon=True,
                                     name="coordinator").start()
                    _append_jsonl(self.rundir / EVENTS_FILE, {
                        "kind": "election", "epoch": epoch,
                        "rank": self.rank, "pid": os.getpid(),
                        "t": time.time()})
                    return {"epoch": epoch, "rank": self.rank,
                            "pid": os.getpid(), "port": coord.port}
            time.sleep(self.ccfg.election_poll_s)
        if self.done:
            return {}
        raise TimeoutError(
            f"worker {self.rank}: no coordinator found/elected within "
            f"{self.ccfg.election_timeout_s}s")

    # ----------------------------- serving -----------------------------
    def run(self) -> None:
        while not self.done:
            info = self._locate_coordinator()
            if self.done:
                return
            try:
                self._serve(info)
            except (ConnectionError, OSError) as e:
                key = (int(info["pid"]), int(info["port"]))
                self._connect_fails[key] = self._connect_fails.get(key, 0) + 1
                if self._connect_fails[key] >= 3 \
                        or not _pid_alive(int(info["pid"])):
                    # verified (or thrice-presumed) corpse: stop retrying
                    # it and let the election path take over
                    self._coord_dead_pids.add(int(info["pid"]))
                print(f"worker {self.rank}: coordinator connection lost "
                      f"({e!r}); rediscovering", flush=True)
                time.sleep(self.ccfg.election_poll_s)

    def _serve(self, info: dict) -> None:
        sock = socket.create_connection(
            (self.ccfg.host, int(info["port"])),
            timeout=self.ccfg.connect_timeout_s)
        sock.settimeout(None)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn = _Conn(sock)
        conn.send({"type": "hello", "rank": self.rank, "pid": os.getpid()})
        self._connect_fails.pop((int(info["pid"]), int(info["port"])), None)
        stop_hb = threading.Event()

        def heartbeat():
            while not stop_hb.wait(self.ccfg.heartbeat_interval_s):
                try:
                    conn.send({"type": "heartbeat", "rank": self.rank})
                except (ConnectionError, OSError):
                    return

        threading.Thread(target=heartbeat, daemon=True,
                         name=f"hb-{self.rank}").start()
        try:
            while True:
                msg, blob = conn.recv()
                ep = int(msg.get("epoch", 0))
                if ep < self.epoch_seen:
                    continue     # fenced: a deposed coordinator's command
                self.epoch_seen = ep
                t = msg["type"]
                if t == "plan":
                    self._do_plan(conn, msg)
                elif t == "step":
                    self._do_step(conn, msg, blob)
                elif t == "restore":
                    self._do_restore(conn, msg)
                elif t == "shutdown":
                    self.done = True
                    return
        finally:
            stop_hb.set()
            conn.close()

    def _do_plan(self, conn: _Conn, msg: dict) -> None:
        from repro.core.instructions import ExecutionPlan
        from repro.data.dataset import materialize_micro_batch

        it = int(msg["iter"])
        plan = ExecutionPlan.from_json(msg["plan"])
        t0 = time.perf_counter()
        if plan.micro_batches:
            gb = self.stream.batch(it)     # zero state transfer: pure in k
            batches = {m.mb_id: materialize_micro_batch(
                           m, gb.tokens, lengths=gb.lengths)
                       for m in plan.micro_batches}
            res = self.backend.execute_plan(
                plan, params=self.params, batches=batches,
                collect_timings=bool(msg.get("collect_timings")))
            blob = (_tree_to_bytes(res.grads)
                    if res.grads is not None else b"")
            loss_sum, w_sum, timings = res.loss_sum, res.weight_sum, \
                res.timings
        else:
            blob, loss_sum, w_sum, timings = b"", 0.0, 0.0, []
        # the pipeline leaves its loss on the device (LossSum): read it
        # here, where it leaves the process
        conn.send({"type": "result", "rank": self.rank,
                   "epoch": msg["epoch"], "iter": it,
                   "loss_sum": float(loss_sum),
                   "weight_sum": float(w_sum),
                   "iter_time": time.perf_counter() - t0,
                   "timings": [list(t) for t in timings]}, blob)

    def _do_step(self, conn: _Conn, msg: dict, blob: bytes) -> None:
        import jax
        import jax.numpy as jnp

        from repro.train import checkpoint as CKPT
        from repro.train.optimizer import adamw_update

        scale = float(msg["scale"])
        grads = jax.tree.map(lambda g: jnp.asarray(g) * scale,
                             _tree_from_bytes(blob))
        self.params, self.opt, om = adamw_update(
            self.params, grads, self.opt, self.opt_cfg)
        if msg.get("save"):
            CKPT.save(self.ckpt_dir, int(msg["iter"]) + 1,
                      {"params": self.params, "opt": self.opt})
        conn.send({"type": "step_ok", "rank": self.rank,
                   "epoch": msg["epoch"], "iter": msg["iter"],
                   "grad_norm": float(om["grad_norm"])})

    def _do_restore(self, conn: _Conn, msg: dict) -> None:
        import jax

        from repro.train import checkpoint as CKPT

        resume = 0
        try:
            like = jax.eval_shape(
                lambda: {"params": self.params, "opt": self.opt})
            state, manifest = CKPT.load_latest_valid(self.ckpt_dir, like)
            self.params, self.opt = state["params"], state["opt"]
            resume = int(manifest["step"])
        except FileNotFoundError:
            # nothing restorable: everyone re-inits from the seed and the
            # deterministic stream replays from 0 — consistent by
            # construction
            self.params, self.opt = self._fresh_state()
        conn.send({"type": "restore_ok", "rank": self.rank,
                   "epoch": msg["epoch"], "iter": -1, "resume": resume})


def _worker_entry(rundir: str, rank: int, payload: dict) -> None:
    """Spawn target (top-level for pickling, like ``PlannerPool``'s
    ``_plan_job``). Worker stdout/stderr go to ``worker-{rank}.log`` so a
    hung or crashed replica is diagnosable from the driver."""
    log = open(Path(rundir) / f"worker-{rank}.log", "a", buffering=1)
    sys.stdout = sys.stderr = log
    print(f"worker {rank} booting pid={os.getpid()}", flush=True)
    try:
        _Worker(Path(rundir), rank, payload).run()
        print(f"worker {rank} clean exit", flush=True)
    except BaseException as e:    # noqa: BLE001 — last-resort diagnostics
        print(f"worker {rank} crashed: {e!r}\n{traceback.format_exc()}",
              flush=True)
        raise


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _progress_iteration(rundir: Path) -> int:
    hist = _read_jsonl(rundir / HISTORY_FILE)
    return (max(h["iter"] for h in hist) + 1) if hist else 0


def _target_pid(rundir: Path, ev) -> Optional[int]:
    if ev.target == "coordinator":
        info = _read_json(rundir / COORD_FILE)
        return int(info["pid"]) if info else None
    info = _read_json(rundir / f"worker-{ev.replica}.json")
    return int(info["pid"]) if info else None


def run_process_cluster(cfg, cost, pcfg, rcfg, stream, opt_cfg=None,
                        chaos: Optional[FaultSchedule] = None,
                        ccfg: Optional[ClusterConfig] = None):
    """Drive one full training run in the process fault domain.

    Returns ``(params, history, stats)`` shaped like
    ``PlanAheadRunner.run()`` — ``history`` keeps every logged occurrence
    (recovery replays re-log an iteration; last occurrence wins, exactly as
    the elastic bench consumes it), ``params`` are restored from the final
    shared checkpoint, and ``stats.cluster`` carries the process-domain
    evidence: delivered kills with verified-dead pids, election/membership
    events, and the orphan count after teardown.
    """
    import jax

    from repro.train.runner import RunnerStats

    if jax.default_backend() == "tpu":
        # each replica is a process doing its own JAX compute, and a TPU
        # belongs to one process: the second replica could not load it
        raise RuntimeError(
            "fault_domain='process' starts one JAX process per replica, and "
            "a TPU belongs to one process; this fault domain is a CPU test "
            "harness. Train on the chip with fault_domain='thread'.")
    if opt_cfg is None:
        from repro.train.optimizer import AdamWConfig
        opt_cfg = AdamWConfig(lr=3e-4)
    ccfg = ccfg if ccfg is not None else ClusterConfig(
        n_replicas=max(1, pcfg.dp_size))
    rundir = Path(ccfg.rundir) if ccfg.rundir else \
        Path(tempfile.mkdtemp(prefix="repro-cluster-"))
    rundir.mkdir(parents=True, exist_ok=True)
    # workers run the threads plane; never recurse into the process domain
    rcfg_w = dataclasses.replace(
        rcfg, fault_domain="thread",
        ckpt_dir=rcfg.ckpt_dir or str(rundir / "ckpt"))
    pcfg_w = dataclasses.replace(pcfg, dp_size=ccfg.n_replicas)
    payload = {"cfg": cfg, "cost": cost, "pcfg": pcfg_w, "rcfg": rcfg_w,
               "opt_cfg": opt_cfg, "stream": stream, "ccfg": ccfg}

    ctx = multiprocessing.get_context("spawn")
    procs = {r: ctx.Process(target=_worker_entry,
                            args=(str(rundir), r, payload),
                            name=f"repro-worker-{r}")
             for r in range(ccfg.n_replicas)}
    for p in procs.values():
        p.start()

    kills: list[dict] = []
    result = None
    deadline = time.monotonic() + ccfg.run_timeout_s
    try:
        while time.monotonic() < deadline:
            result = _read_json(rundir / RESULT_FILE)
            if result is not None:
                break
            if chaos is not None:
                cur = _progress_iteration(rundir)
                for ev in chaos.take_process_kills(cur):
                    pid = _target_pid(rundir, ev)
                    rec = {"fault": ev.describe(), "target": ev.target,
                           "pid": pid, "at_iteration": cur,
                           "verified_dead": False}
                    if pid is not None:
                        # reap promptly: an unreaped SIGKILL corpse is a
                        # zombie, and zombies still answer signal-0 — the
                        # survivors' election waits on the probe flipping.
                        # For our own mp children the reap MUST go through
                        # Process.join (a raw waitpid would steal the wait
                        # status and leave is_alive() True forever)
                        proc = next((p for p in procs.values()
                                     if p.pid == pid), None)
                        if proc is not None:
                            with contextlib.suppress(ProcessLookupError):
                                os.kill(pid, signal.SIGKILL)
                            proc.join(10)
                            rec["verified_dead"] = bool(
                                not proc.is_alive() and not _pid_alive(pid))
                        else:
                            rec["verified_dead"] = deliver_kill(pid)
                    kills.append(rec)
            if not any(p.is_alive() for p in procs.values()):
                result = _read_json(rundir / RESULT_FILE)
                if result is not None:
                    break
                raise RuntimeError(
                    "all cluster processes died without a result; logs:\n"
                    + _tail_logs(rundir, ccfg.n_replicas))
            time.sleep(0.05)
        else:
            raise TimeoutError(
                f"cluster run exceeded {ccfg.run_timeout_s}s; logs:\n"
                + _tail_logs(rundir, ccfg.n_replicas))
    finally:
        for p in procs.values():
            if p.is_alive():
                p.terminate()
        for p in procs.values():
            p.join(10)
            if p.is_alive():
                p.kill()
                p.join(10)

    orphans = [p.name for p in procs.values() if p.is_alive()]
    hist_by_iter: dict[int, dict] = {}
    history = []
    for h in _read_jsonl(rundir / HISTORY_FILE):
        history.append(h)
        hist_by_iter[h["iter"]] = h
    events = _read_jsonl(rundir / EVENTS_FILE)

    import jax

    from repro.train import checkpoint as CKPT
    from repro.models import model as MD
    from repro.models import transformer as T
    from repro.train.optimizer import init_opt_state

    def init():
        key = jax.random.PRNGKey(rcfg_w.seed)
        p0 = (T.init_encdec(key, cfg) if cfg.family == "encdec"
              else MD.init_params(key, cfg))
        return {"params": p0, "opt": init_opt_state(p0, opt_cfg)}

    params = None
    try:
        state, _ = CKPT.load_latest_valid(rcfg_w.ckpt_dir,
                                          jax.eval_shape(init))
        params = state["params"]
    except FileNotFoundError:
        pass    # run died before its first save; history still tells why

    stats = RunnerStats(mode="process")
    stats.iters = len(hist_by_iter)
    stats.exec_s = sum(h["time_s"] for h in hist_by_iter.values())
    stats.real_tokens = sum(h["tokens"] for h in hist_by_iter.values())
    stats.padded_tokens = sum(h["padded_tokens"]
                              for h in hist_by_iter.values())
    stats.faults = len(kills) + sum(
        1 for e in events if e.get("kind") == "replica_lost")
    stats.recoveries = [e for e in events
                        if e.get("kind") in ("membership", "replica_lost",
                                             "election", "restore")]
    stats.cluster = {
        "completed": bool(result and result.get("completed")),
        "n_replicas": ccfg.n_replicas,
        "final_epoch": int(result["epoch"]) if result else -1,
        "final_alive": list(result.get("final_alive", [])) if result else [],
        # epoch 0 is the bootstrap claim, not a failover
        "elections": sum(1 for e in events
                         if e.get("kind") == "election"
                         and e.get("epoch", 0) > 0),
        "kills": kills,
        "orphans": orphans,
        "tmp_dirs_left": sorted(
            p.name for p in Path(rcfg_w.ckpt_dir).glob(".tmp-*")),
        "rundir": str(rundir),
    }
    return params, history, stats


def _tail_logs(rundir: Path, n: int, lines: int = 15) -> str:
    out = []
    for r in range(n):
        p = rundir / f"worker-{r}.log"
        try:
            tail = p.read_text().splitlines()[-lines:]
        except OSError:
            tail = ["<no log>"]
        out.append(f"--- worker {r} ---\n" + "\n".join(tail))
    return "\n".join(out)
