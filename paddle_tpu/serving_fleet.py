"""Fleet serving fabric: N replica processes behind one router.

Everything through the self-healing serving plane is one process —
one ``GenerationServer``, one engine, one KV pool. This module is the
millions-of-users topology (ROADMAP item 1): replica processes each
running a supervised server, and a front-end :class:`FleetRouter`
that places continuous-batching traffic across them and survives any
of them dying mid-decode.

Wire protocol — deliberately stdlib-only: a 4-byte big-endian length
prefix followed by a UTF-8 JSON object, over a local TCP socket. Ops:
``submit`` / ``poll`` (stream delta) / ``cancel`` / ``health`` /
``stats`` / ``prepare_swap`` / ``retain_params`` / ``swap_weights`` /
``generate`` / ``shutdown``. :class:`ReplicaServer` serves a
``GenerationServer`` (real or a test fake — the framing is identical)
and :func:`replica_main` is the child-process entrypoint that boots
one from a model + warm bundle and prints a single JSON boot line
(port, pid, executable-cache counters) for the parent to read.

Router robustness contract (the PR 15 invariant, now across a process
boundary):

* **Placement** is KV-pressure-aware: each heartbeat ships the gauges
  the replica already exports (``blocks_free``, backlog, adaptive-
  admission pressure level) and ``policy="pressure"`` routes around
  starved replicas — measurably better than round-robin under skew
  (test-pinned). When EVERY live replica reports pressure level 3 the
  fleet sheds with a ``retry_after`` hint instead of queueing onto a
  brownout.
* **Failover**: a heartbeat stall or data-plane connection death
  FENCES the replica (its router-side epoch bumps; poll results from
  the zombie epoch are discarded), and its in-flight requests are
  re-dispatched to healthy replicas seeded with their already-
  streamed committed tokens — greedy streams stay bit-equal to the
  uninterrupted oracle because decoding is causal in the whole
  (prompt + committed) sequence. A request active at
  ``quarantine_after`` consecutive replica deaths is quarantined as
  poison fleet-wide rather than allowed to crash-loop the fleet.
* **Resurrection**: the dead replica is relaunched via its ``spawn``
  callable (the same executable cache + warm bundle ⇒ 0 fresh XLA
  compiles) under a bounded full-jittered exponential
  backoff; ``max_restarts`` failures degrade the fleet to the
  survivors — the router itself never crashes.

``rollout()`` (canary probe, divergence rollback) runs unmodified
over :class:`ReplicaClient` handles: ``prepare_swap`` serializes the
state dict over the wire, the replica scans it for non-finite values
server-side and retains prepared trees under opaque tokens, so the
supervisor's ``_count_nonfinite`` sees a :class:`RemotePrepared` with
the count already attached. ``inference.serve(fleet=N)`` wires the
whole fabric behind the existing HTTP front end.

Chaos hooks: every data connection threads through
``fault_injection.FlakyTransport`` (site ``fleet.rpc``) and the
poller calls ``fault_injection.kill_pid("fleet.apply.r<idx>", pid)``
after each token application — tests SIGKILL a real replica at an
exact stream position instead of sleeping and hoping.
"""
from __future__ import annotations

import base64
import io
import itertools
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .core.device import env_wants_cpu, one_chip_env
from .core.flags import flag_value
from .observability import flight as _flight
from .observability import metrics as _om
from .utils import backoff as _backoff
from .utils import fault_injection as _fi

__all__ = ["FleetRouter", "ReplicaServer", "ReplicaClient",
           "ReplicaHandle", "RemotePrepared", "FleetSaturated",
           "health_snapshot", "replica_main", "launch_replica",
           "spawn_fleet"]

_F = _om.scope("fleet")
_M_dispatched = _F.counter("dispatched_total",
                           "Requests placed on a replica by the router")
_M_redispatched = _F.counter(
    "redispatched_total",
    "Failovers: in-flight requests re-dispatched after a replica death")
_M_quarantined = _F.counter(
    "quarantined_total",
    "Poison requests failed fleet-wide after repeated replica deaths")
_M_shed = _F.counter("shed_total",
                     "Submissions shed because every live replica was "
                     "at pressure level 3")
_M_stale = _F.counter("stale_drops_total",
                      "Zombie-epoch replica responses discarded by the "
                      "router's fence")
_M_deaths = _F.counter("replica_deaths_total",
                       "Replica fencings (heartbeat stall or connection "
                       "death)")
_M_resurrected = _F.counter("resurrections_total",
                            "Dead replicas successfully relaunched")
_M_degraded = _F.counter("degraded_total",
                         "Replicas abandoned after max_restarts failed "
                         "relaunches")
_M_healthy = _F.gauge("replicas_healthy",
                      "Live replicas the router will place traffic on")

_FLEET_SEQ = itertools.count(1)
_TOKEN_SEQ = itertools.count(1)


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------

class FrameConn:
    """One length-prefixed-JSON connection: ``send(obj)``/``recv()``
    move whole frames; framing errors surface as ConnectionError so
    every caller handles a half-dead socket the same way."""

    def __init__(self, sock: socket.socket):
        self._sock = sock
        self._rlock = threading.Lock()
        self._wlock = threading.Lock()

    def send(self, obj) -> None:
        blob = json.dumps(obj, default=str).encode()
        with self._wlock:
            self._sock.sendall(struct.pack(">I", len(blob)) + blob)

    def _read_exact(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self._sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("peer closed mid-frame")
            buf += chunk
        return buf

    def recv(self):
        with self._rlock:
            n = struct.unpack(">I", self._read_exact(4))[0]
            if n > (1 << 30):
                raise ConnectionError(f"oversized frame ({n} bytes)")
            return json.loads(self._read_exact(n).decode())

    def settimeout(self, t) -> None:
        self._sock.settimeout(t)

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


def _connect(host: str, port: int, timeout: float = 5.0,
             site: Optional[str] = None):
    s = socket.create_connection((host, port), timeout=timeout)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    conn = FrameConn(s)
    # every fleet connection threads through the chaos wrapper: one
    # dict lookup per frame when unarmed, deterministic drop/delay/
    # duplicate when a test arms the site
    return _fi.FlakyTransport(conn, site or "fleet.rpc")


# ---------------------------------------------------------------------------
# readiness — ONE source of truth for the /healthz endpoint, the
# heartbeat RPC, and an operator's load-balancer probe
# ---------------------------------------------------------------------------

def health_snapshot(server) -> dict:
    """Readiness + placement evidence for one ``GenerationServer``
    (duck-typed; the jax-free test fakes qualify). ``ok`` means "will
    productively take traffic": decode loop alive, supervisor not
    given up, not draining, admission below hard shed."""
    thread = getattr(server, "_thread", None)
    loop_alive = bool(thread is not None and thread.is_alive()
                      and not getattr(server, "_crashed", False))
    sup = getattr(server, "_supervisor", None)
    gave_up = bool(getattr(sup, "gave_up", False))
    level = int(getattr(server.policy, "level", 0))
    kv = server.engine._kv
    blocks_free, blocks_total = int(kv.available_blocks()), \
        int(kv.num_blocks)
    backlog = int(server._q.qsize() + len(server._waiting))
    draining = bool(server._stopping.is_set())
    ok = loop_alive and not gave_up and not draining and level < 3
    return {"ok": ok, "loop_alive": loop_alive, "gave_up": gave_up,
            "level": level, "blocks_free": blocks_free,
            "blocks_total": blocks_total, "backlog": backlog,
            "in_flight": len(server._slots),
            "draining": draining, "pid": os.getpid()}


# ---------------------------------------------------------------------------
# replica side
# ---------------------------------------------------------------------------

def _encode_array(a) -> dict:
    buf = io.BytesIO()
    np.save(buf, np.asarray(a), allow_pickle=False)
    return {"npy": base64.b64encode(buf.getvalue()).decode("ascii")}


def _decode_array(d):
    return np.load(io.BytesIO(base64.b64decode(d["npy"])),
                   allow_pickle=False)


def _err_payload(e: BaseException) -> dict:
    return {"type": type(e).__name__, "msg": str(e)}


def _rebuild_error(d: Optional[dict]) -> Optional[BaseException]:
    if not d:
        return None
    kind = {"TimeoutError": TimeoutError,
            "ValueError": ValueError}.get(d.get("type"), RuntimeError)
    return kind(f"[replica {d.get('type')}] {d.get('msg')}")


class ReplicaServer:
    """Serve one ``GenerationServer`` over the fleet RPC. Used by
    :func:`replica_main` inside real child processes AND in-thread
    over jax-free fakes in tier-1 tests — the framing, request table
    and op handlers are byte-identical in both.

    ``kill()`` abruptly closes the listener and every live connection
    without draining anything — the in-process simulation of a
    SIGKILL, leaving the wrapped server running as a zombie the
    router must fence."""

    def __init__(self, server, host: str = "127.0.0.1", port: int = 0):
        self.server = server
        self._reqs: Dict[str, dict] = {}   # rid -> live request dict
        self._prepared: Dict[str, object] = {}  # token -> device tree
        self._reqs_order: List[str] = []   # FIFO bound on the table
        self._lock = threading.Lock()
        self._conns: List[socket.socket] = []
        self._stop = threading.Event()
        self._listener = socket.socket(socket.AF_INET,
                                       socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET,
                                  socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(64)
        self.host, self.port = self._listener.getsockname()[:2]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name=f"fleet-replica-{self.port}")
        self._accept_thread.start()

    # -- socket plumbing ----------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return  # listener closed
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._lock:
                self._conns.append(sock)
            threading.Thread(target=self._serve_conn,
                             args=(FrameConn(sock),), daemon=True,
                             name=f"fleet-conn-{self.port}").start()

    def _serve_conn(self, conn: FrameConn) -> None:
        while not self._stop.is_set():
            try:
                msg = conn.recv()
            except (ConnectionError, OSError, ValueError):
                return
            try:
                reply = self._handle(msg)
            except Exception as e:  # noqa: BLE001 — surfaced per op
                reply = {"ok": False, "error": _err_payload(e)}
            try:
                conn.send(reply)
            except (ConnectionError, OSError):
                return
            if msg.get("op") == "shutdown":
                return

    def kill(self) -> None:
        """Simulated process death: every socket dies NOW, nothing
        drains, the wrapped server becomes an unreachable zombie."""
        self._stop.set()
        try:
            self._listener.close()
        except OSError:
            pass
        with self._lock:
            conns, self._conns = self._conns, []
        for s in conns:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    def close(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Graceful stop: drain the wrapped server, then drop sockets."""
        try:
            self.server.shutdown(drain=drain, timeout=timeout)
        finally:
            self.kill()

    # -- ops ----------------------------------------------------------------
    def _remember(self, req: dict) -> None:
        with self._lock:
            rid = req["trace_id"]
            self._reqs[rid] = req
            self._reqs_order.append(rid)
            # bound the table: evict oldest FINISHED entries only (a
            # live stream must stay pollable); duplicates of recent
            # polls still resolve
            while len(self._reqs_order) > 4096:
                old = self._reqs_order[0]
                got = self._reqs.get(old)
                if got is not None and not got["done"].is_set():
                    break
                self._reqs_order.pop(0)
                self._reqs.pop(old, None)

    def _handle(self, msg: dict) -> dict:
        op = msg.get("op")
        srv = self.server
        if op == "submit":
            try:
                req = srv.submit(
                    np.asarray(msg["prompt"], np.int32),
                    int(msg["max_new"]),
                    deadline=msg.get("deadline"))
            except RuntimeError as e:
                reason = "shed" if "admission" in str(e) else \
                    "shutting_down"
                return {"ok": False, "reason": reason,
                        "error": _err_payload(e)}
            self._remember(req)
            return {"ok": True, "rid": req["trace_id"]}
        if op == "poll":
            with self._lock:
                req = self._reqs.get(msg["rid"])
            if req is None:
                return {"ok": False, "reason": "unknown_rid"}
            since = int(msg.get("since", 0))
            err = req["error"] if req["done"].is_set() else None
            return {"ok": True,
                    "tokens": [int(t) for t in req["out"][since:]],
                    "done": req["done"].is_set(),
                    "error": _err_payload(err) if err else None}
        if op == "cancel":
            with self._lock:
                req = self._reqs.get(msg["rid"])
            if req is None:
                return {"ok": False, "reason": "unknown_rid"}
            if req["done"].is_set():
                return {"ok": True, "already_done": True}
            # best-effort: a queued request dies here (admission drops
            # done-set requests); an ACTIVE one finishes its stream —
            # a decode step cannot be abandoned without corrupting the
            # slot tables
            active = any(r is req for r in srv._slots.values()) \
                or any(r is req for r in srv._prefilling.values())
            if active:
                return {"ok": False, "reason": "active"}
            srv._fail(req, RuntimeError("cancelled by the fleet router"))
            return {"ok": True}
        if op == "health":
            return {"ok": True, "health": health_snapshot(srv)}
        if op == "stats":
            return {"ok": True, "stats": srv.stats()}
        if op == "cache_stats":
            # the 0-fresh-compile evidence: after a warm boot has
            # served traffic, misses must still be 0
            from .jit import warmup as _warmup
            return {"ok": True, "cache": _warmup.cache_stats()}
        if op == "generate":
            toks = srv.generate(
                np.asarray(msg["prompt"], np.int32),
                int(msg["max_new"]),
                timeout=float(msg.get("timeout", 300.0)))
            return {"ok": True, "tokens": [int(t) for t in toks]}
        if op == "prepare_swap":
            sd = {k: _decode_array(v) for k, v in msg["state"].items()}
            prepared = srv.engine.prepare_swap(sd)
            from .serving_supervisor import _count_nonfinite
            bad = _count_nonfinite(prepared)
            token = f"prep-{next(_TOKEN_SEQ)}"
            with self._lock:
                self._prepared[token] = prepared
            return {"ok": True, "token": token, "nonfinite": int(bad)}
        if op == "retain_params":
            token = f"prep-{next(_TOKEN_SEQ)}"
            with self._lock:
                self._prepared[token] = srv.engine.params
            return {"ok": True, "token": token}
        if op == "swap_weights":
            with self._lock:
                prepared = self._prepared.get(msg["prepared"])
            if prepared is None:
                return {"ok": False, "reason": "unknown_token"}
            res = srv.swap_weights(prepared=prepared)
            return {"ok": True, "result": res}
        if op == "shutdown":
            threading.Thread(
                target=self.close,
                kwargs={"drain": bool(msg.get("drain", True))},
                daemon=True).start()
            return {"ok": True}
        return {"ok": False, "reason": f"unknown op {op!r}"}


def replica_main(config: dict) -> None:
    """Child-process entrypoint: boot a supervised ``GenerationServer``
    from ``config`` and serve the fleet RPC until shutdown.

    config keys: ``model`` ({"kind": "tiny_llama", "config": {...},
    "seed": n} builds a seeded toy causal LM — deterministic identical
    weights fleet-wide without a checkpoint; {"kind":
    "inference_model", "path": p} loads a saved artifact), engine
    geometry (``max_slots``/``max_seq``/``block_size``/
    ``prefill_chunk``/``int8``/``eos_id``), ``warm_bundle`` (pre-warm
    against the shared executable cache BEFORE the first admit),
    ``supervised`` (attach the PR 15 supervisor), ``host``/``port``
    (0 = ephemeral), ``metrics_port`` (optional /metrics + /healthz).

    Prints exactly ONE JSON boot line to stdout — ``{"ok": true,
    "port": p, "pid": n, "cache": {hits, misses, writes}}`` — the
    parent's readiness signal AND the 0-fresh-compile evidence
    (``cache.misses == 0`` on a warm boot)."""
    import paddle_tpu as paddle
    from .jit import warmup as _warmup
    from .serving import GenerationServer, PagedLlamaDecodeEngine

    _warmup.ensure_executable_cache()
    model_spec = config.get("model") or {}
    kind = model_spec.get("kind", "tiny_llama")
    if kind == "tiny_llama":
        from .models import LlamaConfig, LlamaForCausalLM
        paddle.seed(int(model_spec.get("seed", 0)))
        model = LlamaForCausalLM(
            LlamaConfig.tiny(**model_spec.get("config", {})))
    elif kind == "inference_model":
        from .inference import load_inference_model
        model = load_inference_model(model_spec["path"])
    else:
        raise ValueError(f"unknown model kind {kind!r}")
    engine = PagedLlamaDecodeEngine(
        model,
        max_slots=int(config.get("max_slots", 2)),
        max_seq=int(config.get("max_seq", 128)),
        block_size=int(config.get("block_size",
                                  flag_value("serving_block_size"))),
        prefill_chunk=int(config.get(
            "prefill_chunk", flag_value("serving_prefill_chunk"))),
        int8=bool(config.get("int8", False)),
        eos_id=config.get("eos_id"))
    prewarm = None
    bundle = config.get("warm_bundle") or None
    if bundle:
        prewarm = _warmup.prewarm(bundle, engine=engine)
    prime = config.get("prime")
    if prime:
        # compile the serving programs BEFORE taking traffic (and
        # before an export_bundle snapshot): one short generation
        # through the engine exercises prefill + decode buckets
        engine.generate(np.asarray(prime, np.int32),
                        max_new_tokens=int(config.get("prime_tokens",
                                                      4)))
        engine.reset_state()
    export = config.get("export_bundle")
    if export:
        _warmup.export_bundle(export)
    server = GenerationServer(engine)
    if config.get("supervised", True):
        from .serving_supervisor import supervise
        server._supervisor = supervise(server)
    if config.get("metrics_port") is not None:
        server.metrics_endpoint(port=int(config["metrics_port"]))
    rs = ReplicaServer(server, host=config.get("host", "127.0.0.1"),
                       port=int(config.get("port", 0)))
    boot = {"ok": True, "port": rs.port, "pid": os.getpid(),
            "cache": _warmup.cache_stats()}
    if prewarm is not None:
        boot["prewarm"] = prewarm
    print(json.dumps(boot), flush=True)
    # serve until the RPC shutdown op (close() sets _stop) or SIGKILL
    while not rs._stop.is_set():
        time.sleep(0.2)


def _parent_holds_tpu() -> bool:
    """True when THIS process has already initialised JAX's TPU
    backend — it then owns every chip of the host, and a child that
    needs one fails or hangs. Never imports or initialises anything."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized() \
        and jax.default_backend() == "tpu"


def launch_replica(config: dict, env: Optional[dict] = None,
                   timeout: float = 300.0, chip: int = 0):
    """Spawn one replica subprocess (``python -m
    paddle_tpu.serving_fleet``, config via env) and block for its boot
    line. Returns ``(proc, port, boot)``.

    The child inherits this process's environment plus ``env``; the
    platform is whatever that names (tests run with ``JAX_PLATFORMS=
    cpu`` and the children inherit it). Unless the child is told
    ``cpu``, it is pinned to chip ``chip`` of the host through
    libtpu's own variables — a chip belongs to one process — and the
    call raises if this process already holds the chips."""
    child_env = dict(os.environ)
    if env:
        child_env.update(env)
    if not env_wants_cpu(child_env):
        if _parent_holds_tpu():
            raise RuntimeError(
                f"launch_replica: this process has initialised the JAX "
                f"TPU backend and holds the host's chips, so replica "
                f"{chip} cannot claim one. Spawn the fleet from a "
                f"process that has not touched JAX, or pass "
                f"env={{'JAX_PLATFORMS': 'cpu'}} for a CPU fleet")
        child_env.update(one_chip_env(chip))
    child_env["PADDLE_TPU_REPLICA_CONFIG"] = json.dumps(config)
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.serving_fleet"],
        env=child_env, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)
    deadline = time.monotonic() + timeout
    line = ""
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.strip():
            break
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica died before boot (rc={proc.returncode})")
    try:
        boot = json.loads(line.strip())
    except (json.JSONDecodeError, ValueError) as e:
        proc.kill()
        raise RuntimeError(f"bad replica boot line {line!r}") from e
    return proc, int(boot["port"]), boot


# ---------------------------------------------------------------------------
# router side
# ---------------------------------------------------------------------------

class FleetSaturated(RuntimeError):
    """Every live replica is at pressure level 3 (or dead): the fleet
    sheds instead of queueing onto a brownout. ``retry_after`` is the
    client hint in seconds."""

    def __init__(self, msg: str, retry_after: float):
        super().__init__(msg)
        self.retry_after = float(retry_after)


class ReplicaHandle:
    """Router-side view of one replica: address, data connection,
    heartbeat state, and the FENCING EPOCH — every dispatch stamps
    ``(idx, epoch)`` on the request, and responses only apply while
    the stamp still matches, so a zombie replica's late answers are
    discarded instead of corrupting a failed-over stream."""

    def __init__(self, idx: int, host: str, port: int,
                 pid: Optional[int] = None, proc=None,
                 spawn: Optional[Callable[[int], "ReplicaHandle"]]
                 = None, kill_cb: Optional[Callable[[], None]] = None):
        self.idx = int(idx)
        self.host, self.port = host, int(port)
        self.pid = pid
        self.proc = proc          # subprocess.Popen, when we own it
        self.spawn = spawn        # resurrection factory
        self.kill_cb = kill_cb    # in-proc kill (tests)
        self.epoch = 0
        self.alive = True
        self.degraded = False     # max_restarts exhausted
        self.health: Optional[dict] = None
        self.misses = 0
        self.restarts = 0
        self.dispatched = 0
        self._conn = None
        self._io_lock = threading.Lock()

    def conn(self):
        if self._conn is None:
            self._conn = _connect(self.host, self.port,
                                  site=f"fleet.rpc.r{self.idx}")
            self._conn.settimeout(10.0)
        return self._conn

    def call(self, msg: dict) -> dict:
        """One request/response over the shared data connection."""
        with self._io_lock:
            conn = self.conn()
            try:
                conn.send(msg)
                return conn.recv()
            except (ConnectionError, OSError, socket.timeout):
                self.drop_conn()
                raise

    def drop_conn(self) -> None:
        if self._conn is not None:
            try:
                self._conn.close()
            finally:
                self._conn = None

    def probe_health(self, timeout: float) -> dict:
        """Heartbeat on a DEDICATED short-timeout connection — a data
        socket wedged behind a long op must not read as a dead
        replica, and a dead replica must not wedge the monitor."""
        conn = _connect(self.host, self.port, timeout=timeout,
                        site=f"fleet.hb.r{self.idx}")
        try:
            conn.settimeout(timeout)
            conn.send({"op": "health"})
            reply = conn.recv()
        finally:
            conn.close()
        if not reply.get("ok"):
            raise ConnectionError(f"health op rejected: {reply}")
        return reply["health"]


class FleetRouter:
    """Place continuous-batching traffic across N replicas; survive
    any of them dying. See the module docstring for the contract.

    ``replicas``: list of :class:`ReplicaHandle`. ``policy``:
    ``"pressure"`` (default — KV-pressure-aware placement from
    heartbeat gauges) or ``"rr"`` (round-robin; kept as the A/B
    baseline the placement test pins against)."""

    def __init__(self, replicas: List[ReplicaHandle], *,
                 policy: str = "pressure",
                 heartbeat_seconds: Optional[float] = None,
                 heartbeat_misses: Optional[int] = None,
                 quarantine_after: int = 2,
                 restart_backoff: Optional[float] = None,
                 restart_backoff_cap: float = 2.0,
                 max_restarts: Optional[int] = None,
                 retry_after: Optional[float] = None,
                 poll_interval: float = 0.005):
        if not replicas:
            raise ValueError("FleetRouter needs at least one replica")
        self.replicas = list(replicas)
        self.policy = str(policy)
        self.heartbeat_seconds = float(
            flag_value("serving_fleet_heartbeat_seconds")
            if heartbeat_seconds is None else heartbeat_seconds)
        self.heartbeat_misses = int(
            flag_value("serving_fleet_heartbeat_misses")
            if heartbeat_misses is None else heartbeat_misses)
        self.quarantine_after = max(int(quarantine_after), 1)
        self.restart_backoff = float(
            flag_value("serving_fleet_restart_backoff")
            if restart_backoff is None else restart_backoff)
        self.restart_backoff_cap = float(restart_backoff_cap)
        self.max_restarts = int(
            flag_value("serving_fleet_max_restarts")
            if max_restarts is None else max_restarts)
        self.retry_after = float(
            flag_value("serving_fleet_retry_after")
            if retry_after is None else retry_after)
        self.poll_interval = float(poll_interval)
        self._lock = threading.Lock()
        self._inflight: Dict[str, dict] = {}
        self._parked: List[dict] = []   # awaiting a live replica
        self._rr_next = 0
        self._stop = threading.Event()
        self.shed = 0
        self.failovers = 0
        self.quarantined = 0
        self.stale_drops = 0
        self.finished = 0
        self.failed = 0
        self._pollers = [
            threading.Thread(target=self._poll_loop, args=(h,),
                             daemon=True, name=f"fleet-poll-{h.idx}")
            for h in self.replicas]
        for t in self._pollers:
            t.start()
        self._monitor_thread = threading.Thread(
            target=self._monitor, daemon=True, name="fleet-monitor")
        self._monitor_thread.start()
        _M_healthy.set(len(self.replicas))
        _flight.record("fleet", "router_up",
                       replicas=len(self.replicas), policy=self.policy)

    # -- submission ---------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int = 32,
               deadline: Optional[float] = None) -> dict:
        """Fleet submit: returns a request dict with the same surface
        as ``GenerationServer.submit`` (``out``/``done``/``error``/
        ``trace_id``) plus fleet bookkeeping. Raises
        :class:`FleetSaturated` (with ``retry_after``) when every live
        replica is at pressure level 3."""
        prompt = [int(t) for t in
                  np.asarray(prompt_ids, np.int32).reshape(-1)]
        req = {"prompt": prompt, "max_new": int(max_new_tokens),
               "out": [], "done": threading.Event(), "error": None,
               "trace_id": f"fleet-{os.getpid()}-{next(_FLEET_SEQ)}",
               "t0": time.monotonic(), "deadline": deadline,
               "strikes": 0, "owner": None, "rid": None, "base": 0,
               "terminal": False}
        _flight.record("fleet", "submit", trace_id=req["trace_id"],
                       max_new=req["max_new"])
        self._dispatch(req, exclude=())
        if isinstance(req["error"], FleetSaturated):
            raise req["error"]  # surfaced like GenerationServer's shed
        return req

    def generate(self, prompt_ids, max_new_tokens: int = 32,
                 timeout: float = 300.0) -> List[int]:
        req = self.submit(prompt_ids, max_new_tokens)
        if not req["done"].wait(timeout):
            raise TimeoutError("fleet generation timed out")
        if req["error"] is not None:
            raise req["error"]
        return list(req["out"])

    # -- placement ----------------------------------------------------------
    def _live(self) -> List[ReplicaHandle]:
        return [h for h in self.replicas if h.alive and not h.degraded]

    def _pick(self, exclude: Tuple[int, ...]) -> Optional[ReplicaHandle]:
        """Choose the placement target, or None when nothing can take
        the request (⇒ shed/park)."""
        live = [h for h in self._live() if h.idx not in exclude]
        if not live:
            return None
        candidates = [h for h in live
                      if (h.health or {}).get("level", 0) < 3]
        if not candidates:
            return None  # everyone at hard shed: fleet-level shed
        if self.policy == "rr":
            ordered = sorted(candidates, key=lambda h: h.idx)
            pick = ordered[self._rr_next % len(ordered)]
            self._rr_next += 1
            return pick
        return min(candidates, key=self._pressure_key)

    def _pressure_key(self, h: ReplicaHandle):
        """Sort key: lowest admission pressure level first, then the
        most free KV blocks (fractional — pools may differ), then the
        shortest backlog, then least recently loaded. A replica that
        has not heartbeat yet sorts as unknown-but-willing (mid)."""
        snap = h.health or {}
        level = int(snap.get("level", 0))
        total = snap.get("blocks_total", -1)
        free = snap.get("blocks_free", -1)
        free_frac = (free / total) if total and total > 0 else 0.5
        backlog = int(snap.get("backlog", 0)) \
            + int(snap.get("in_flight", 0))
        return (level, -free_frac, backlog, h.dispatched)

    # -- dispatch -----------------------------------------------------------
    def _dispatch(self, req: dict, exclude: Tuple[int, ...]) -> None:
        """Place ``req`` (fresh or failed-over) on a replica. The wire
        prompt is prompt + committed tokens and the wire budget the
        REMAINING tokens — decoding is causal in the whole sequence,
        so a re-dispatched greedy stream continues bit-equal."""
        tried = list(exclude)
        while True:
            with self._lock:
                h = self._pick(tuple(tried))
            if h is None:
                self._no_replica(req, tried)
                return
            wire_prompt = req["prompt"] + [int(t) for t in req["out"]]
            wire_budget = req["max_new"] - len(req["out"])
            if wire_budget <= 0:   # failover raced completion
                self._finish(req)
                return
            try:
                reply = h.call({"op": "submit", "prompt": wire_prompt,
                                "max_new": wire_budget,
                                "deadline": req["deadline"]})
            except (ConnectionError, OSError, socket.timeout):
                self._replica_down(h, reason="dispatch_conn")
                tried.append(h.idx)
                continue
            if not reply.get("ok"):
                if reply.get("reason") == "shed":
                    # per-replica admission disagreed with our stale
                    # gauge: respect it and try the next-best replica
                    tried.append(h.idx)
                    continue
                self._fail(req, _rebuild_error(reply.get("error"))
                           or RuntimeError(f"replica rejected: {reply}"))
                return
            with self._lock:
                req["owner"] = (h.idx, h.epoch)
                req["rid"] = reply["rid"]
                # the replica's stream counts from ITS admission —
                # polls must rebase by what was already committed at
                # dispatch or a failed-over stream would skip/duplicate
                req["base"] = len(req["out"])
                self._inflight[req["trace_id"]] = req
                h.dispatched += 1
            _M_dispatched.inc()
            _flight.record("fleet", "dispatch",
                           trace_id=req["trace_id"], replica=h.idx,
                           epoch=h.epoch,
                           committed=len(req["out"]))
            return

    def _no_replica(self, req: dict, tried: List[int]) -> None:
        live = self._live()
        if live:
            # live replicas exist but all are at hard shed (or just
            # shed us): fleet-level shed with the retry hint
            with self._lock:
                self.shed += 1
            _M_shed.inc()
            _flight.record("fleet", "fleet_shed",
                           trace_id=req["trace_id"],
                           retry_after=self.retry_after,
                           live=len(live))
            self._fail(req, FleetSaturated(
                f"every live replica is at admission pressure level 3 "
                f"— retry after {self.retry_after}s",
                self.retry_after), count_shed=True)
            return
        if any(not h.degraded for h in self.replicas):
            # replicas are dead but resurrection is still running:
            # park; the monitor re-dispatches when one rejoins
            with self._lock:
                req["owner"] = None
                self._parked.append(req)
            _flight.record("fleet", "parked", trace_id=req["trace_id"])
            return
        self._fail(req, RuntimeError(
            "fleet degraded: every replica exhausted max_restarts"))

    # -- completion ---------------------------------------------------------
    def _finish(self, req: dict) -> None:
        with self._lock:
            if req["terminal"]:
                return
            req["terminal"] = True
            self._inflight.pop(req["trace_id"], None)
            self.finished += 1
        _flight.record("fleet", "finished", trace_id=req["trace_id"],
                       tokens=len(req["out"]))
        req["done"].set()

    def _fail(self, req: dict, error: BaseException,
              count_shed: bool = False) -> None:
        with self._lock:
            if req["terminal"]:
                return
            req["terminal"] = True
            self._inflight.pop(req["trace_id"], None)
            if not count_shed:
                self.failed += 1
        req["error"] = error
        _flight.record("fleet",
                       "shed" if count_shed else "failed",
                       trace_id=req["trace_id"],
                       error=type(error).__name__,
                       tokens=len(req["out"]))
        req["done"].set()

    # -- polling ------------------------------------------------------------
    def _owned_by(self, h: ReplicaHandle) -> List[dict]:
        with self._lock:
            return [r for r in self._inflight.values()
                    if r["owner"] == (h.idx, h.epoch)]

    def _poll_loop(self, h: ReplicaHandle) -> None:
        while not self._stop.is_set():
            if not h.alive or h.degraded:
                time.sleep(self.poll_interval * 4)
                continue
            work = self._owned_by(h)
            if not work:
                time.sleep(self.poll_interval)
                continue
            for req in work:
                owner = req["owner"]
                since = max(len(req["out"]) - req.get("base", 0), 0)
                try:
                    reply = h.call({"op": "poll", "rid": req["rid"],
                                    "since": since})
                except (ConnectionError, OSError, socket.timeout):
                    self._replica_down(h, reason="poll_conn")
                    break
                if not reply.get("ok"):
                    continue  # unknown rid: re-dispatch owns it now
                self._apply(req, owner, h,
                            reply.get("tokens") or [],
                            bool(reply.get("done")),
                            reply.get("error"))
            time.sleep(self.poll_interval)

    def _apply(self, req: dict, owner, h: ReplicaHandle,
               tokens: List[int], done: bool, error) -> None:
        """Fold one poll response into the fleet stream — IFF the
        dispatch stamp still matches the replica's current epoch.
        A response from a fenced (zombie) epoch is dropped: the
        request has been re-dispatched and folding the zombie's view
        would duplicate or fork the committed stream."""
        with self._lock:
            if req["terminal"]:
                return
            if owner != (h.idx, h.epoch) or req["owner"] != owner:
                self.stale_drops += 1
                _M_stale.inc()
                _flight.record("fleet", "stale_drop",
                               trace_id=req["trace_id"],
                               replica=h.idx,
                               stamped=list(owner) if owner else None,
                               current=h.epoch)
                return
            if tokens:
                req["out"].extend(int(t) for t in tokens)
        # deterministic chaos trigger: a test arms fleet.apply.r<idx>
        # to SIGKILL the replica at an exact stream position
        if h.pid:
            _fi.kill_pid(f"fleet.apply.r{h.idx}", h.pid)
        if done:
            err = _rebuild_error(error)
            if err is not None:
                self._fail(req, err)
            else:
                self._finish(req)

    # -- monitor / failover -------------------------------------------------
    def _monitor(self) -> None:
        while not self._stop.wait(self.heartbeat_seconds):
            for h in list(self.replicas):
                if h.degraded or not h.alive:
                    continue
                try:
                    snap = h.probe_health(
                        timeout=max(self.heartbeat_seconds, 0.1))
                except (ConnectionError, OSError, socket.timeout,
                        ValueError):
                    h.misses += 1
                    if h.misses >= self.heartbeat_misses:
                        self._replica_down(h, reason="heartbeat")
                    continue
                h.misses = 0
                h.health = snap
                if snap.get("gave_up"):
                    # supervisor exhausted ITS restarts: the process
                    # is up but permanently refusing work — treat as
                    # dead so resurrection replaces it
                    self._replica_down(h, reason="gave_up")
            self._retry_parked()
            _M_healthy.set(len(self._live()))

    def _retry_parked(self) -> None:
        with self._lock:
            parked, self._parked = self._parked, []
        for req in parked:
            if req["terminal"]:
                continue
            self._dispatch(req, exclude=())

    def _replica_down(self, h: ReplicaHandle, reason: str) -> None:
        """Fence ``h`` and fail its work over. Idempotent per epoch:
        poller and monitor may both notice the same death."""
        with self._lock:
            if not h.alive:
                return
            h.alive = False
            h.epoch += 1   # the fence: in-flight stamps are now stale
            h.health = None
            victims = [r for r in self._inflight.values()
                       if r["owner"] and r["owner"][0] == h.idx]
        h.drop_conn()
        _M_deaths.inc()
        _flight.record("fleet", "replica_dead", replica=h.idx,
                       reason=reason, epoch=h.epoch,
                       victims=len(victims))
        for req in victims:
            req["strikes"] += 1
            if req["strikes"] >= self.quarantine_after:
                with self._lock:
                    self.quarantined += 1
                _M_quarantined.inc()
                _flight.record("fleet", "quarantined",
                               trace_id=req["trace_id"],
                               strikes=req["strikes"])
                self._fail(req, RuntimeError(
                    f"request quarantined as poison: active at "
                    f"{req['strikes']} consecutive replica deaths"))
                continue
            with self._lock:
                self.failovers += 1
            _M_redispatched.inc()
            _flight.record("fleet", "failover",
                           trace_id=req["trace_id"], from_replica=h.idx,
                           committed=len(req["out"]),
                           strikes=req["strikes"])
            self._dispatch(req, exclude=(h.idx,))
        _M_healthy.set(len(self._live()))
        if h.spawn is not None:
            threading.Thread(target=self._resurrect, args=(h,),
                             daemon=True,
                             name=f"fleet-resurrect-{h.idx}").start()
        elif h.kill_cb is None and h.proc is None:
            pass  # externally managed replica: stays down

    def _resurrect(self, h: ReplicaHandle) -> None:
        """Relaunch a dead replica under bounded full-jittered backoff.
        ``max_restarts`` failures degrade to the surviving fleet —
        journaled, counted, and never an exception out of this
        thread."""
        if h.proc is not None:
            try:
                h.proc.wait(timeout=10)  # reap the SIGKILLed child
            except (subprocess.TimeoutExpired, OSError):
                pass
        attempt = 0
        while not self._stop.is_set():
            attempt += 1
            if attempt > self.max_restarts:
                h.degraded = True
                _M_degraded.inc()
                _flight.record("fleet", "degraded", replica=h.idx,
                               restarts=attempt - 1)
                return
            delay = _backoff.full_jitter(
                min(self.restart_backoff * (2 ** (attempt - 1)),
                    self.restart_backoff_cap))
            if self._stop.wait(delay):
                return
            _flight.record("fleet", "resurrect_attempt",
                           replica=h.idx, attempt=attempt)
            try:
                fresh = h.spawn(h.idx)
            except Exception as e:  # noqa: BLE001 — retried, bounded
                _flight.record("fleet", "resurrect_failed",
                               replica=h.idx, attempt=attempt,
                               error=type(e).__name__)
                continue
            with self._lock:
                h.host, h.port = fresh.host, fresh.port
                h.pid, h.proc = fresh.pid, fresh.proc
                h.kill_cb = fresh.kill_cb
                h.misses = 0
                h.health = None
                h.restarts += attempt
                h.alive = True
            _M_resurrected.inc()
            _flight.record("fleet", "resurrected", replica=h.idx,
                           attempt=attempt, pid=h.pid)
            self._retry_parked()
            return

    # -- admin --------------------------------------------------------------
    def stats(self) -> Dict[str, int]:
        with self._lock:
            inflight = len(self._inflight)
            parked = len(self._parked)
        return {"replicas": len(self.replicas),
                "live": len(self._live()),
                "in_flight": inflight, "parked": parked,
                "finished": self.finished, "failed": self.failed,
                "shed": self.shed, "failovers": self.failovers,
                "quarantined": self.quarantined,
                "stale_drops": self.stale_drops,
                "restarts": sum(h.restarts for h in self.replicas),
                "degraded": sum(int(h.degraded)
                                for h in self.replicas)}

    def shutdown(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop the router and every replica we own (RPC shutdown,
        then terminate the subprocess if it lingers)."""
        self._stop.set()
        for h in self.replicas:
            try:
                h.call({"op": "shutdown", "drain": drain})
            except (ConnectionError, OSError, socket.timeout):
                pass
            h.drop_conn()
            if h.proc is not None:
                try:
                    h.proc.wait(timeout=timeout)
                except subprocess.TimeoutExpired:
                    h.proc.kill()
        _flight.record("fleet", "router_down", **self.stats())


# ---------------------------------------------------------------------------
# rollout over RPC
# ---------------------------------------------------------------------------

class RemotePrepared:
    """Opaque handle to a prepared weight tree living ON the replica.
    ``nonfinite`` carries the replica-side scan so the supervisor's
    ``_count_nonfinite`` never tries to tree-walk a token string."""

    __slots__ = ("token", "nonfinite")

    def __init__(self, token: str, nonfinite: int = 0):
        self.token = token
        self.nonfinite = int(nonfinite)


class _RemoteEngine:
    """The ``srv.engine`` duck-type ``rollout()`` touches, over RPC."""

    def __init__(self, client: "ReplicaClient"):
        self._c = client

    def prepare_swap(self, state_dict) -> RemotePrepared:
        state = {str(k): _encode_array(v)
                 for k, v in state_dict.items()}
        reply = self._c._call({"op": "prepare_swap", "state": state})
        return RemotePrepared(reply["token"], reply["nonfinite"])

    @property
    def params(self) -> RemotePrepared:
        """The retained rollback tree — kept replica-side, referenced
        by token (already finite: it was serving traffic)."""
        reply = self._c._call({"op": "retain_params"})
        return RemotePrepared(reply["token"], 0)


class ReplicaClient:
    """A ``rollout()``-compatible handle for ONE remote replica:
    ``.engine.prepare_swap``/``.engine.params``, ``.generate`` and
    ``.swap_weights(prepared=)`` all run over the fleet RPC, so the
    canary machinery (probe, divergence, rollback) is literally the
    PR 15 code path across a process boundary."""

    def __init__(self, host: str, port: int, timeout: float = 300.0):
        self.host, self.port = host, int(port)
        self._timeout = float(timeout)
        self._conn = None
        self._io_lock = threading.Lock()
        self.engine = _RemoteEngine(self)

    def _call(self, msg: dict) -> dict:
        with self._io_lock:
            if self._conn is None:
                self._conn = _connect(self.host, self.port,
                                      site="fleet.rollout")
                self._conn.settimeout(self._timeout)
            self._conn.send(msg)
            reply = self._conn.recv()
        if not reply.get("ok"):
            err = _rebuild_error(reply.get("error"))
            raise err if err is not None else RuntimeError(
                f"replica op {msg.get('op')!r} failed: {reply}")
        return reply

    def generate(self, prompt_ids, max_new_tokens: int = 32,
                 timeout: float = 300.0) -> List[int]:
        reply = self._call({"op": "generate",
                            "prompt": [int(t) for t in prompt_ids],
                            "max_new": int(max_new_tokens),
                            "timeout": float(timeout)})
        return list(reply["tokens"])

    def swap_weights(self, checkpoint_or_state=None, *,
                     prepared: Optional[RemotePrepared] = None) -> dict:
        if prepared is None:
            raise ValueError(
                "ReplicaClient.swap_weights needs prepared= (a "
                "RemotePrepared from engine.prepare_swap / "
                "engine.params)")
        reply = self._call({"op": "swap_weights",
                            "prepared": prepared.token})
        return reply["result"]

    def close(self) -> None:
        with self._io_lock:
            if self._conn is not None:
                self._conn.close()
                self._conn = None


# ---------------------------------------------------------------------------
# fleet bring-up
# ---------------------------------------------------------------------------

def spawn_fleet(n: int, replica_config: dict,
                env: Optional[dict] = None,
                router_kwargs: Optional[dict] = None) -> FleetRouter:
    """Launch ``n`` replica subprocesses from one config (sharing the
    executable cache + warm bundle the config names) and return the
    router over them, with resurrection wired to relaunch from the
    same config. On a TPU host replica ``i`` runs on chip ``i``."""
    def make_spawn(idx: int):
        def spawn(_idx: int) -> ReplicaHandle:
            proc, port, _boot = launch_replica(dict(replica_config),
                                               env=env, chip=idx)
            return ReplicaHandle(idx, "127.0.0.1", port,
                                 pid=proc.pid, proc=proc, spawn=spawn)
        return spawn

    handles = []
    for i in range(int(n)):
        spawn = make_spawn(i)
        proc, port, _boot = launch_replica(dict(replica_config),
                                           env=env, chip=i)
        handles.append(ReplicaHandle(i, "127.0.0.1", port,
                                     pid=proc.pid, proc=proc,
                                     spawn=spawn))
    return FleetRouter(handles, **(router_kwargs or {}))


def _main() -> int:
    cfg = os.environ.get("PADDLE_TPU_REPLICA_CONFIG")
    if not cfg and len(sys.argv) > 1:
        with open(sys.argv[1], "r") as f:
            cfg = f.read()
    if not cfg:
        print("usage: python -m paddle_tpu.serving_fleet <config.json>"
              " (or PADDLE_TPU_REPLICA_CONFIG in the env)",
              file=sys.stderr)
        return 2
    replica_main(json.loads(cfg))
    return 0


if __name__ == "__main__":
    sys.exit(_main())
