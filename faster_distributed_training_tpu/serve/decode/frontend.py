"""The decode front door: replicas as PROCESSES behind a socket RPC.

The r16 serving tier runs its replicas as threads of one process — a
"replica death" there is a fault seam, not a process.  This module
promotes each decode replica to its own OS process behind a localhost
TCP socket with a length-framed JSON protocol:

  frame   := 4-byte big-endian length || utf-8 JSON object
  request := {"op": "generate", "id": int, "tokens": [int],
              "max_new": int}
           | {"op": "ping"} | {"op": "stop"}
  reply   := {"id": int, "tokens": [int], "ttft_ms": float}
           | {"ok": 1, ...} | {"error": str}

Liveness is the r14/r10 pair of idioms at process scope: every worker
process touches an ``HB_<name>`` marker file from a daemon thread (the
coordinator's marker heartbeat, verbatim), and the parent's
:class:`ProcReplica` folds marker staleness into the ``Replica.stale``
predicate the ReplicaSet watchdog already polls — so a SIGKILLed or
wedged process is DETACHED exactly like a wedged thread, its in-flight
generations re-dispatched to the survivors (deterministic per (seed,
request) sampling makes the re-run return the same tokens), and
re-admission RESPAWNS the process, whose warmup rides the executable
cache instead of a cold compile.

The parent-side control loop is :class:`GenScheduler` — the r16
``BatchScheduler`` with its assembly seam overridden to the identity
wire payload (batch size 1: the front door dispatches REQUESTS;
token-granular batching happens inside each worker's
DecodeScheduler).  Dispatch, parking, the bounded attempt budget, and
replica rescue are untouched inheritance.
"""

from __future__ import annotations

import dataclasses
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from faster_distributed_training_tpu.serve.queue import (GenRequest,
                                                         RequestQueue)
from faster_distributed_training_tpu.serve.replicas import (Replica,
                                                            ReplicaSet)
from faster_distributed_training_tpu.serve.scheduler import BatchScheduler

_HB_PERIOD_S = 0.3


# -- wire protocol ---------------------------------------------------------

def send_msg(sock: socket.socket, obj: dict) -> None:
    data = json.dumps(obj).encode("utf-8")
    sock.sendall(struct.pack(">I", len(data)) + data)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def recv_msg(sock: socket.socket) -> Optional[dict]:
    head = _recv_exact(sock, 4)
    if head is None:
        return None
    (length,) = struct.unpack(">I", head)
    body = _recv_exact(sock, length)
    if body is None:
        return None
    return json.loads(body.decode("utf-8"))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def load_cfg(path: str):
    """TrainConfig back from the JSON the parent wrote
    (dataclasses.asdict round-trip; JSON turned the tuple fields into
    lists, so coerce them back)."""
    from faster_distributed_training_tpu.config import TrainConfig
    with open(path) as f:
        d = json.load(f)
    names = {f.name for f in dataclasses.fields(TrainConfig)}
    kw = {}
    for k, v in d.items():
        if k in names:
            kw[k] = tuple(v) if isinstance(v, list) else v
    return TrainConfig(**kw)


# -- the worker process ----------------------------------------------------

def _touch_forever(path: str, stop: threading.Event) -> None:
    while not stop.is_set():
        try:
            with open(path, "w") as f:
                f.write(str(time.time()))
        except OSError:
            pass
        stop.wait(_HB_PERIOD_S)


def worker_main(argv: Optional[List[str]] = None) -> int:
    """Entry point of one decode worker process: restore the
    checkpoint, warm the decode program set (through the observatory +
    executable cache when armed — the restart-MTTR path), then serve
    generate/ping frames until "stop" or parent death."""
    import argparse
    p = argparse.ArgumentParser(prog="fdt-decode-worker")
    p.add_argument("--cfg", required=True)
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--name", default="worker0")
    p.add_argument("--hb_dir", default="")
    args = p.parse_args(argv)

    cfg = load_cfg(args.cfg)
    from faster_distributed_training_tpu.cli import setup_platform
    setup_platform(cfg)

    from faster_distributed_training_tpu.models.decode import SamplingCfg
    from faster_distributed_training_tpu.serve.decode.engine import (
        DecodeEngine)
    from faster_distributed_training_tpu.serve.decode.scheduler import (
        DecodeScheduler)
    from faster_distributed_training_tpu.serve.engine import (
        load_serving_state)
    from faster_distributed_training_tpu.telemetry import (
        TelemetryRecorder, programs, resolve_telemetry_dir,
        update_manifest)

    name = args.name
    log = lambda m: print(f"[{name}] {m}", flush=True)   # noqa: E731

    recorder = None
    obs = None
    prev_obs = None
    if cfg.telemetry:
        tdir = resolve_telemetry_dir(cfg)
        recorder = TelemetryRecorder(tdir, log=log)
        update_manifest(tdir, {"decode_worker": {
            "name": name, "port": args.port,
            "config": dataclasses.asdict(cfg)}})
        if programs.observatory_enabled():
            from faster_distributed_training_tpu.resilience \
                .executable_cache import build_executable_cache
            from faster_distributed_training_tpu.resilience.storage import (
                build_backend)
            from faster_distributed_training_tpu.telemetry import (
                ProgramObservatory)
            obs = ProgramObservatory(recorder=recorder, log=log)
            obs.executable_cache = build_executable_cache(
                cfg, backend=build_backend(
                    getattr(cfg, "storage_backend", "posix"),
                    cfg.checkpoint_dir, log=log),
                mesh=None, log=log)
            prev_obs = programs.set_observatory(obs)

    hb_stop = threading.Event()
    if args.hb_dir:
        os.makedirs(args.hb_dir, exist_ok=True)
        threading.Thread(
            target=_touch_forever,
            args=(os.path.join(args.hb_dir, f"HB_{name}"), hb_stop),
            daemon=True).start()

    model, sstate, _meta = load_serving_state(cfg, log=log)
    q = RequestQueue(cfg.seq_buckets, max_len=cfg.seq_len)
    engine = DecodeEngine(
        model, sstate, q.buckets,
        batch_size=cfg.decode_batch_size, page=cfg.decode_page,
        max_pages=cfg.decode_max_pages,
        sampling=SamplingCfg(method=cfg.decode_sample,
                             temperature=cfg.decode_temperature,
                             top_k=cfg.decode_top_k, seed=cfg.seed),
        name=name, log=log)
    warm_s = engine.warmup()
    log(f"decode program set warmed in {warm_s:.2f}s "
        f"({len(engine.buckets)} prefill + {engine.max_pages} decode "
        f"programs)")
    sched = DecodeScheduler(q, engine,
                            max_delay_ms=cfg.serve_max_delay_ms,
                            max_new_tokens=cfg.decode_max_new_tokens,
                            recorder=recorder, name=name, log=log)
    sched.start()

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", args.port))
    srv.listen(16)
    log(f"serving on 127.0.0.1:{args.port}")
    stopping = threading.Event()

    def handle(conn: socket.socket) -> None:
        try:
            while True:
                msg = recv_msg(conn)
                if msg is None:
                    return
                op = msg.get("op")
                if op == "ping":
                    send_msg(conn, {"ok": 1, "name": name})
                elif op == "stop":
                    send_msg(conn, {"ok": 1})
                    stopping.set()
                    return
                elif op == "generate":
                    try:
                        # the PARENT's request id rides the wire into
                        # the sampling fold_in key, so a generation
                        # retried on another worker (replica death)
                        # returns the same tokens
                        req = q.submit(
                            np.asarray(msg["tokens"], np.int32),
                            max_new_tokens=int(msg["max_new"]),
                            req_id=msg.get("id"))
                        out = req.wait(timeout=300.0)
                        send_msg(conn, {
                            "id": msg.get("id"),
                            "tokens": np.asarray(out).tolist(),
                            "ttft_ms": req.ttft_ms()})
                    except BaseException as e:
                        send_msg(conn, {"id": msg.get("id"),
                                        "error": repr(e)})
                else:
                    send_msg(conn, {"error": f"unknown op {op!r}"})
        except OSError:
            pass
        finally:
            conn.close()

    srv.settimeout(0.2)
    try:
        while not stopping.is_set():
            try:
                conn, _addr = srv.accept()
            except socket.timeout:
                continue
            threading.Thread(target=handle, args=(conn,),
                             daemon=True).start()
    finally:
        srv.close()
        q.close()
        sched.close(drain_s=5.0)
        hb_stop.set()
        if recorder is not None:
            if obs is not None:
                programs.set_observatory(prev_obs)
                update_manifest(recorder.directory,
                                {"decode_compile": obs.summary()})
            recorder.close()
        log("worker stopped")
    return 0


# -- the parent side -------------------------------------------------------

class WorkerClient:
    """Engine-shaped socket client: ``predict_batch(payload) ->
    np.int32 tokens``.  One persistent connection, reconnect with
    bounded retry on demand (a freshly respawned worker may still be
    warming; the retry window is the readiness budget).  Any socket
    error mid-call raises — the Replica worker converts that into
    detach + re-dispatch, which is the whole point."""

    def __init__(self, port: int, connect_timeout_s: float = 120.0,
                 call_timeout_s: float = 300.0):
        self.port = int(port)
        self.connect_timeout_s = float(connect_timeout_s)
        self.call_timeout_s = float(call_timeout_s)
        self._sock: Optional[socket.socket] = None
        self._lock = threading.Lock()

    def _connect(self) -> socket.socket:
        deadline = time.monotonic() + self.connect_timeout_s
        last: Optional[BaseException] = None
        while time.monotonic() < deadline:
            try:
                s = socket.create_connection(("127.0.0.1", self.port),
                                             timeout=2.0)
                s.settimeout(self.call_timeout_s)
                return s
            except OSError as e:
                last = e
                time.sleep(0.2)
        raise ConnectionError(
            f"worker on port {self.port} not reachable within "
            f"{self.connect_timeout_s}s") from last

    def _call(self, msg: dict) -> dict:
        with self._lock:
            if self._sock is None:
                self._sock = self._connect()
            try:
                send_msg(self._sock, msg)
                reply = recv_msg(self._sock)
            except OSError:
                self.drop()
                raise
            if reply is None:
                self.drop()
                raise ConnectionError(
                    f"worker on port {self.port} closed the connection")
            return reply

    def drop(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def ping(self) -> dict:
        return self._call({"op": "ping"})

    def stop(self) -> None:
        try:
            self._call({"op": "stop"})
        except (OSError, ConnectionError):
            pass

    def predict_batch(self, payload: dict) -> np.ndarray:
        reply = self._call({"op": "generate", **payload})
        if "error" in reply:
            raise RuntimeError(f"worker generate failed: "
                               f"{reply['error']}")
        return np.asarray(reply["tokens"], np.int32)


class ProcReplica(Replica):
    """A Replica whose engine lives in another PROCESS.  ``start``
    (first admission and every re-admission) ensures the process is
    running and READY (ping) before the worker thread spins up — a
    respawn after process death warms from the executable cache, which
    is what keeps re-admission near ``restart_cached_mttr_s`` instead
    of a cold compile.  ``stale`` adds the r14 marker check: a process
    whose HB_<name> file stops moving is presumed dead/wedged even if
    the parent-side worker thread is idle and beating."""

    def __init__(self, name: str, spawn: Callable[[], subprocess.Popen],
                 client: WorkerClient, hb_path: str,
                 marker_timeout_s: float = 5.0,
                 log: Callable[[str], None] = print):
        super().__init__(name, client, log=log)
        self._spawn = spawn
        self.client = client
        self.hb_path = hb_path
        self.marker_timeout_s = float(marker_timeout_s)
        self.proc: Optional[subprocess.Popen] = None
        self.respawns = 0

    def proc_alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def ensure_proc(self) -> None:
        if not self.proc_alive():
            if self.proc is not None:
                self.respawns += 1
            self.proc = self._spawn()

    def start(self) -> None:
        """Called under the ReplicaSet lock (first admission and every
        re-admission).  A readiness failure must NOT raise — the caller
        is the watchdog loop — so a worker that never answers its ping
        stays detached with a fresh ``detached_at`` and the auto-
        readmit timer simply tries again."""
        try:
            self.ensure_proc()
            self.client.drop()
            self.client.ping()      # blocks (bounded) until ready
        except (OSError, ConnectionError, RuntimeError) as e:
            self._log(f"[serve] replica {self.name} respawn not ready: "
                      f"{e!r}; will retry")
            self.alive = False
            self.detached_at = time.monotonic()
            return
        super().start()

    def stale(self, now: float, timeout_s: float) -> bool:
        if super().stale(now, timeout_s):
            return True
        if not self.alive:
            return False
        if not self.proc_alive():
            return True
        try:
            age = time.time() - os.path.getmtime(self.hb_path)
        except OSError:
            return False            # not written yet (still starting)
        return age > self.marker_timeout_s

    def kill(self) -> None:
        """Fault seam for smokes/tests: SIGKILL the worker process —
        the process-scope analog of the in-process ``hang_s``."""
        if self.proc is not None:
            self.proc.kill()


class GenScheduler(BatchScheduler):
    """BatchScheduler at slot granularity: cells of ONE request, the
    wire payload as the work's batch, the generated token array as its
    result.  Everything between — least-loaded dispatch, parking when
    no replica is live, the bounded attempt budget, rescue from a
    detached replica — is the inherited r16 machinery."""

    def __init__(self, queue: RequestQueue, replicas: ReplicaSet,
                 max_delay_ms: float = 20.0, recorder=None,
                 request_deadline_s: Optional[float] = None,
                 log: Callable[[str], None] = print):
        super().__init__(queue, replicas, batch_size=1,
                         max_delay_ms=max_delay_ms, recorder=recorder,
                         request_deadline_s=request_deadline_s,
                         log=log)

    def summary(self) -> dict:
        """The front door's robustness counters under their README
        names: a generation re-dispatched because its worker PROCESS
        died/errored mid-request is a decode_request_retry; one that
        blew its per-request deadline is a decode_request_timeout."""
        out = super().summary()
        out["decode_request_retries"] = out.pop("request_retries", 0)
        out["decode_request_timeouts"] = out.pop("request_timeouts", 0)
        return out

    def _assemble(self, bucket: int, requests):
        req = requests[0]
        if not isinstance(req, GenRequest):
            raise TypeError("the decode front door serves GenRequests "
                            "(queue.submit(tokens, max_new_tokens=...))")
        return {"id": req.id, "tokens": np.asarray(req.tokens).tolist(),
                "max_new": req.max_new}, 1

    def _on_done(self, work, tokens: np.ndarray, replica) -> None:
        now = time.monotonic()
        req = work.requests[0]
        req.fulfill(np.asarray(tokens, np.int32), replica.name, now)
        with self._lock:
            self.completed_batches += 1
            self.completed_requests += 1
            self.latencies_ms.append(req.latency_ms())
            t0 = req.t_submit
            self._t_first = t0 if self._t_first is None \
                else min(self._t_first, t0)
            self._t_last = now if self._t_last is None \
                else max(self._t_last, now)
        if self.recorder is not None and self.request_events:
            self.recorder.record_event(
                "serve_request", bucket=req.bucket, len=req.raw_len,
                queue_ms=round((work.t_created - req.t_submit) * 1e3, 3),
                total_ms=round(req.latency_ms(), 3),
                replica=replica.name)


class FrontDoor:
    """Parent-side assembly: N worker processes + queue + GenScheduler.

    ``cfg`` is the serving TrainConfig (checkpoint_dir names the
    artifact to serve); each worker gets its own telemetry directory
    (``telemetry_dir=<run_dir>/telemetry_<name>``) so the r12 one-file-
    per-process contract holds across the process boundary."""

    def __init__(self, cfg, n_workers: int = 2, run_dir: str = "",
                 heartbeat_timeout_s: float = 60.0,
                 marker_timeout_s: float = 5.0,
                 readmit_after_s: float = 1.0,
                 recorder=None, log: Callable[[str], None] = print):
        self.cfg = cfg
        self.run_dir = run_dir or os.path.join(cfg.checkpoint_dir,
                                               "frontdoor")
        os.makedirs(self.run_dir, exist_ok=True)
        self._log = log
        self.queue = RequestQueue(cfg.seq_buckets, max_len=cfg.seq_len)
        self.replicas: List[ProcReplica] = []
        hb_dir = os.path.join(self.run_dir, "hb")
        for i in range(int(n_workers)):
            name = f"decode{i}"
            port = free_port()
            cfg_path = os.path.join(self.run_dir, f"cfg_{name}.json")
            worker_cfg = cfg.replace(telemetry_dir=os.path.join(
                self.run_dir, f"telemetry_{name}"))
            with open(cfg_path, "w") as f:
                json.dump(dataclasses.asdict(worker_cfg), f)
            cmd = [sys.executable, "-m",
                   "faster_distributed_training_tpu.serve.decode"
                   ".worker",
                   "--cfg", cfg_path, "--port", str(port),
                   "--name", name, "--hb_dir", hb_dir]

            log_path = os.path.join(self.run_dir, f"{name}.log")
            # the package root on the child's PYTHONPATH: `-m` resolves
            # from sys.path, and the parent may be running from any cwd
            pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))))
            env = dict(os.environ)
            env["PYTHONPATH"] = pkg_root + (
                os.pathsep + env["PYTHONPATH"]
                if env.get("PYTHONPATH") else "")

            def spawn(_cmd=tuple(cmd), _log=log_path,
                      _env=env) -> subprocess.Popen:
                # own log file, not the parent's stdout: worker output
                # survives the parent and a child can never hold a
                # parent-side pipe open
                logf = open(_log, "ab")
                try:
                    return subprocess.Popen(list(_cmd), stdout=logf,
                                            stderr=subprocess.STDOUT,
                                            env=_env)
                finally:
                    logf.close()

            self.replicas.append(ProcReplica(
                name, spawn, WorkerClient(port),
                hb_path=os.path.join(hb_dir, f"HB_{name}"),
                marker_timeout_s=marker_timeout_s, log=log))
        self.rset = ReplicaSet(self.replicas,
                               heartbeat_timeout_s=heartbeat_timeout_s,
                               readmit_after_s=readmit_after_s, log=log)
        self.sched = GenScheduler(
            self.queue, self.rset,
            max_delay_ms=cfg.serve_max_delay_ms, recorder=recorder,
            request_deadline_s=float(
                getattr(cfg, "decode_deadline_s", 0.0) or 0.0) or None,
            log=log)

    def start(self) -> None:
        # spawn every process first so their warmups overlap, then let
        # each start() block on its own readiness ping
        for r in self.replicas:
            r.ensure_proc()
        self.sched.start()

    def submit(self, tokens, max_new: int) -> GenRequest:
        req = self.queue.submit(tokens, max_new_tokens=max_new)
        assert isinstance(req, GenRequest)
        return req

    def close(self) -> None:
        self.sched.close()
        for r in self.replicas:
            if r.proc_alive():
                r.client.stop()
        deadline = time.monotonic() + 5.0
        for r in self.replicas:
            if r.proc is None:
                continue
            while r.proc.poll() is None and time.monotonic() < deadline:
                time.sleep(0.05)
            if r.proc.poll() is None:
                r.proc.kill()


if __name__ == "__main__":
    sys.exit(worker_main(sys.argv[1:]))
