"""Fleet serving fabric (ISSUE 17): multi-replica router with
failover, process-level chaos recovery, and warm replica resurrection.

Contract pinned here: SIGKILL (or socket death) of one replica of N
mid-stream leaves every accepted request BIT-equal to the
uninterrupted oracle (failover re-dispatches prompt + committed
tokens) with exactly ONE terminal fleet flight event; late responses
from the fenced zombie epoch are discarded, never folded into a
failed-over stream; KV-pressure-aware placement sends no traffic to a
block-starved replica while round-robin (the pinned A/B baseline)
defers there; a request active at ``quarantine_after`` consecutive
replica deaths is failed as poison instead of crash-looping the
fleet; the fleet sheds (FleetSaturated + retry_after) only when EVERY
live replica reports admission pressure level 3; and a dead replica
resurrects from the shared executable cache + warm bundle with 0
fresh XLA compiles.

Cost discipline: router logic runs against jax-free fake replicas
(the PR 15 causal fakes behind REAL sockets speaking the REAL fleet
RPC), so the fast tests compile nothing; the real-subprocess chaos
acceptance (SIGKILL a child pid mid-decode via an armed
``fleet.apply.r<idx>`` site, warm resurrection with cache misses
pinned at 0) is slow-marked.
"""
from __future__ import annotations

import io
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from paddle_tpu.observability import flight
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving_fleet import (FleetRouter, FleetSaturated,
                                      ReplicaClient, ReplicaHandle,
                                      ReplicaServer, health_snapshot,
                                      launch_replica)
from paddle_tpu.utils import fault_injection as fi

from test_serving_supervisor import CFG, FakeCausalEngine

FLEET_TERMINAL = {"finished", "failed", "shed"}


def _oracle(prompt, n_new):
    """The uninterrupted greedy stream of the causal fakes — a pure
    recomputation, independent of every server under test."""
    seq = [int(t) for t in prompt]
    out = []
    for _ in range(n_new):
        tok = FakeCausalEngine._next(seq)
        seq.append(tok)
        out.append(tok)
    return out


class StubLevelPolicy:
    """Admission policy double with a hand-set pressure level: admits
    everything replica-side so placement/shed decisions under test are
    exactly the ROUTER's."""

    name = "stub"

    def __init__(self, level=0):
        self.level = level

    def admit_verdict(self, server, prompt_len, max_new, deadline):
        return None

    def on_step(self, server):
        return None


def _mk_replica(idx, engine, policy=None, **handle_kwargs):
    srv = GenerationServer(engine, policy=policy)
    rs = ReplicaServer(srv)
    h = ReplicaHandle(idx, rs.host, rs.port, kill_cb=rs.kill,
                      **handle_kwargs)
    return srv, rs, h


def _teardown(router, replica_servers):
    if router is not None:
        router._stop.set()
    for rs in replica_servers:
        try:
            rs.close(drain=False, timeout=5)
        except Exception:  # noqa: BLE001 — teardown must not mask
            rs.kill()


def _fleet_terminal_counts(trace_ids):
    evs = flight.events(category="fleet")
    return {tid: sum(1 for e in evs
                     if e.get("trace_id") == tid
                     and e["name"] in FLEET_TERMINAL)
            for tid in trace_ids}


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    fi.clear()


# ---------------------------------------------------------------------------
# failover + fencing (jax-free fakes behind real sockets)
# ---------------------------------------------------------------------------

class TestFailover:
    def test_kill_one_of_n_mid_stream_bit_equal(self):
        """The chaos acceptance shape, in-proc: one of 3 replicas dies
        abruptly mid-stream; every request finishes BIT-equal to the
        oracle with exactly one terminal fleet event, and the dead
        replica resurrects via its spawn factory."""
        flight.clear()
        made = []

        def spawn(idx):
            eng = FakeCausalEngine(slots=4, max_seq=64, step_sleep=0.01)
            srv = GenerationServer(eng)
            rs = ReplicaServer(srv)
            made.append(rs)
            return ReplicaHandle(idx, rs.host, rs.port, kill_cb=rs.kill)

        servers, replicas, handles = [], [], []
        for i in range(3):
            eng = FakeCausalEngine(slots=4, max_seq=64, step_sleep=0.01)
            srv, rs, h = _mk_replica(i, eng, spawn=spawn)
            servers.append(srv)
            replicas.append(rs)
            handles.append(h)
        router = FleetRouter(handles, policy="rr",
                             heartbeat_seconds=0.05, heartbeat_misses=2,
                             quarantine_after=3, restart_backoff=0.01,
                             restart_backoff_cap=0.05, max_restarts=5)
        try:
            prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
            reqs = [router.submit(p, 24) for p in prompts]
            time.sleep(0.06)
            assert not all(r["done"].is_set() for r in reqs), \
                "streams finished before the kill — nothing to fail over"
            with router._lock:
                owners = {r["owner"][0] for r in router._inflight.values()
                          if r["owner"]}
            victim = next(h for h in handles if h.idx in owners)
            victim.kill_cb()  # abrupt socket death: the in-proc SIGKILL

            for req, prompt in zip(reqs, prompts):
                assert req["done"].wait(30)
                assert req["error"] is None
                assert req["out"] == _oracle(prompt, 24)
            assert router.failovers >= 1
            counts = _fleet_terminal_counts([r["trace_id"] for r in reqs])
            assert all(c == 1 for c in counts.values()), counts
            names = {e["name"] for e in flight.events(category="fleet")}
            assert {"replica_dead", "failover", "dispatch"} <= names

            deadline = time.monotonic() + 10
            while time.monotonic() < deadline \
                    and router.stats()["live"] < 3:
                time.sleep(0.02)
            assert router.stats()["live"] == 3, \
                "dead replica was not resurrected"
            assert victim.restarts >= 1
            # the rebuilt replica takes traffic like any other
            assert router.generate([9, 9, 7], 6) == _oracle([9, 9, 7], 6)
        finally:
            _teardown(router, replicas + made)

    def test_zombie_epoch_late_response_discarded(self):
        """A fenced replica's late poll responses are dropped by the
        epoch stamp — the failed-over stream stays bit-equal and the
        drop is journaled, never silently folded in."""
        flight.clear()
        servers, replicas, handles = [], [], []
        for i in range(2):
            eng = FakeCausalEngine(slots=2, max_seq=64, step_sleep=0.02)
            srv, rs, h = _mk_replica(i, eng)
            servers.append(srv)
            replicas.append(rs)
            handles.append(h)
        router = FleetRouter(handles, heartbeat_seconds=5.0,
                             quarantine_after=5)
        try:
            req = router.submit([4, 2], 40)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and (
                    req["owner"] is None or len(req["out"]) < 2):
                time.sleep(0.005)
            stale_owner = req["owner"]
            assert stale_owner is not None
            zombie = handles[stale_owner[0]]

            router._replica_down(zombie, reason="test_fence")
            assert req["owner"][0] != zombie.idx, "failover did not move"
            # the zombie is still decoding; simulate its late response
            # arriving after the fence
            router._apply(req, stale_owner, zombie, [123456], False, None)
            assert router.stale_drops >= 1
            assert 123456 not in req["out"]

            assert req["done"].wait(30)
            assert req["error"] is None
            assert req["out"] == _oracle([4, 2], 40)
            evs = flight.events(category="fleet")
            assert any(e["name"] == "stale_drop"
                       and e.get("trace_id") == req["trace_id"]
                       for e in evs)
            assert _fleet_terminal_counts(
                [req["trace_id"]])[req["trace_id"]] == 1
        finally:
            _teardown(router, replicas)

    def test_poison_quarantined_after_two_replica_deaths(self):
        """A request active at quarantine_after consecutive replica
        deaths is failed as poison — one terminal event, counted, and
        never re-dispatched a third time."""
        flight.clear()
        servers, replicas, handles = [], [], []
        for i in range(2):
            eng = FakeCausalEngine(slots=2, max_seq=80, step_sleep=0.02)
            srv, rs, h = _mk_replica(i, eng)
            servers.append(srv)
            replicas.append(rs)
            handles.append(h)
        router = FleetRouter(handles, heartbeat_seconds=5.0,
                             quarantine_after=2)
        try:
            req = router.submit([7, 7, 7], 60)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and req["owner"] is None:
                time.sleep(0.005)
            first = handles[req["owner"][0]]
            router._replica_down(first, reason="death_one")
            assert not req["done"].is_set()
            assert req["strikes"] == 1
            second = handles[req["owner"][0]]
            assert second.idx != first.idx
            router._replica_down(second, reason="death_two")

            assert req["done"].wait(5)
            assert isinstance(req["error"], RuntimeError)
            assert "poison" in str(req["error"])
            assert router.quarantined == 1
            evs = flight.events(category="fleet")
            assert any(e["name"] == "quarantined"
                       and e.get("trace_id") == req["trace_id"]
                       for e in evs)
            assert _fleet_terminal_counts(
                [req["trace_id"]])[req["trace_id"]] == 1
        finally:
            _teardown(router, replicas)


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

class TestPlacement:
    def _run(self, policy):
        """One fleet with replica 0 KV-starved by a hog request that
        holds its ENTIRE block pool; returns (admitted_without_deferral,
        starved_dispatched) for 6 short requests."""
        servers, replicas, handles = [], [], []
        for i in range(3):
            eng = FakeCausalEngine(slots=2, max_seq=64, block_size=8,
                                   num_blocks=(6 if i == 0 else 32),
                                   step_sleep=0.01)
            srv, rs, h = _mk_replica(i, eng, policy=StubLevelPolicy(0))
            servers.append(srv)
            replicas.append(rs)
            handles.append(h)
        router = FleetRouter(handles, policy=policy,
                             heartbeat_seconds=5.0)
        try:
            # the hog goes through replica 0's OWN admission path:
            # prompt 8 + budget 40 = 48 tokens = all 6 blocks, held for
            # 40 slow steps — anything placed there must defer
            hog = servers[0].submit([3] * 8, 40)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and \
                    servers[0].engine._kv.available_blocks() > 0:
                time.sleep(0.005)
            assert servers[0].engine._kv.available_blocks() == 0

            for h in handles:
                h.health = h.probe_health(1.0)
            assert handles[0].health["blocks_free"] == 0
            assert handles[1].health["blocks_free"] == 32

            prompts = [[i + 1, i + 2, 5] for i in range(6)]
            reqs = [router.submit(p, 4) for p in prompts]
            for req, prompt in zip(reqs, prompts):
                assert req["done"].wait(30)
                assert req["error"] is None
                assert req["out"] == _oracle(prompt, 4)
            assert hog["done"].wait(30)
            # every request the router placed on the starved replica
            # sat in its deferred-admission queue behind the hog
            deferred = handles[0].dispatched
            return 6 - deferred, deferred
        finally:
            _teardown(router, replicas)

    def test_pressure_placement_beats_round_robin(self):
        """The evidence-driven pin: under a KV-starved replica, the
        pressure policy admits strictly MORE requests without deferral
        than round-robin, and sends the starved replica nothing."""
        pressure_score, pressure_deferred = self._run("pressure")
        rr_score, rr_deferred = self._run("rr")
        assert pressure_deferred == 0, \
            "pressure policy placed traffic on the starved replica"
        assert rr_deferred >= 1, \
            "round-robin avoided the starved replica — no contrast"
        assert pressure_score > rr_score


# ---------------------------------------------------------------------------
# fleet-level shed
# ---------------------------------------------------------------------------

class TestFleetShed:
    def test_shed_only_when_every_replica_at_level3(self):
        flight.clear()
        servers, replicas, handles = [], [], []
        for i in range(3):
            eng = FakeCausalEngine(slots=2, max_seq=64)
            srv, rs, h = _mk_replica(i, eng, policy=StubLevelPolicy(3))
            servers.append(srv)
            replicas.append(rs)
            handles.append(h)
        router = FleetRouter(handles, heartbeat_seconds=5.0,
                             retry_after=0.25)
        try:
            for h in handles:
                h.health = h.probe_health(1.0)
            with pytest.raises(FleetSaturated) as exc:
                router.submit([1, 2], 4)
            assert exc.value.retry_after == 0.25
            assert router.shed == 1
            evs = flight.events(category="fleet")
            assert any(e["name"] == "fleet_shed"
                       and e["attrs"].get("retry_after") == 0.25
                       for e in evs)

            # ONE replica dropping below hard shed reopens the fleet —
            # and placement goes exactly there
            servers[1].policy.level = 0
            handles[1].health = handles[1].probe_health(1.0)
            assert router.generate([2, 4, 6], 5) == _oracle([2, 4, 6], 5)
            assert handles[1].dispatched == 1
            assert handles[0].dispatched == handles[2].dispatched == 0
        finally:
            _teardown(router, replicas)


# ---------------------------------------------------------------------------
# /healthz — one source of truth with the router probe
# ---------------------------------------------------------------------------

class TestHealthz:
    def test_snapshot_shapes(self):
        srv = GenerationServer(FakeCausalEngine(slots=2, max_seq=64))
        try:
            snap = health_snapshot(srv)
            assert snap["ok"] and snap["loop_alive"]
            assert snap["blocks_total"] == snap["blocks_free"] == 16
            small = GenerationServer(
                FakeCausalEngine(slots=2, max_seq=64, num_blocks=8))
            try:
                psnap = health_snapshot(small)
                assert psnap["blocks_total"] == 8
                assert psnap["blocks_free"] == 8
            finally:
                small.shutdown(drain=False, timeout=5)
        finally:
            srv.shutdown(drain=False, timeout=5)

    def test_healthz_endpoint_reports_readiness(self):
        ok_srv = GenerationServer(FakeCausalEngine(slots=2, max_seq=64))
        bad_srv = GenerationServer(FakeCausalEngine(slots=2, max_seq=64),
                                   policy=StubLevelPolicy(3))
        try:
            ep = ok_srv.metrics_endpoint(port=0)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{ep.port}/healthz",
                    timeout=5) as resp:
                body = json.loads(resp.read())
            assert resp.status == 200
            assert body["ok"] and body["level"] == 0

            ep2 = bad_srv.metrics_endpoint(port=0)
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{ep2.port}/healthz", timeout=5)
            assert exc.value.code == 503
            body = json.loads(exc.value.read())
            assert not body["ok"] and body["level"] == 3
        finally:
            ok_srv.shutdown(drain=False, timeout=5)
            bad_srv.shutdown(drain=False, timeout=5)


# ---------------------------------------------------------------------------
# transport chaos primitives (satellite: fault_injection growth)
# ---------------------------------------------------------------------------

class _ScriptedConn:
    def __init__(self, frames=()):
        self.sent = []
        self.frames = list(frames)
        self.closed = False

    def send(self, obj):
        self.sent.append(obj)

    def recv(self):
        return self.frames.pop(0)

    def close(self):
        self.closed = True


class TestFlakyTransport:
    def test_send_duplicate_and_drop(self):
        conn = _ScriptedConn()
        ft = fi.FlakyTransport(conn, "tx.a")
        fi.inject_transport("tx.a.send", duplicate=True, times=1)
        ft.send({"x": 1})
        ft.send({"x": 2})
        assert conn.sent == [{"x": 1}, {"x": 1}, {"x": 2}]

        conn2 = _ScriptedConn()
        ft2 = fi.FlakyTransport(conn2, "tx.b")
        fi.inject_transport("tx.b.send", drop=True, times=1)
        ft2.send({"x": 1})  # vanishes
        ft2.send({"x": 2})
        assert conn2.sent == [{"x": 2}]

    def test_recv_drop_duplicate_delay_and_passthrough(self):
        ft = fi.FlakyTransport(_ScriptedConn([1, 2, 3]), "tx.c")
        fi.inject_transport("tx.c.recv", drop=True, times=1)
        assert ft.recv() == 2  # frame 1 discarded, next delivered
        assert ft.recv() == 3

        ft2 = fi.FlakyTransport(_ScriptedConn([7, 8]), "tx.d")
        fi.inject_transport("tx.d.recv", duplicate=True, times=1)
        assert ft2.recv() == 7
        assert ft2.recv() == 7  # the replayed duplicate
        assert ft2.recv() == 8

        conn = _ScriptedConn([5])
        ft3 = fi.FlakyTransport(conn, "tx.e")
        fi.inject_transport("tx.e.recv", delay=0.05, times=1)
        t0 = time.monotonic()
        assert ft3.recv() == 5
        assert time.monotonic() - t0 >= 0.05
        ft3.close()  # __getattr__ passthrough
        assert conn.closed

    def test_skip_counts_clean_frames_first(self):
        conn = _ScriptedConn()
        ft = fi.FlakyTransport(conn, "tx.f")
        fi.inject_transport("tx.f.send", drop=True, times=1, skip=2)
        for i in range(4):
            ft.send(i)
        assert conn.sent == [0, 1, 3]  # exactly the 3rd frame vanished

    def test_kill_pid_is_armed_site_gated(self):
        assert fi.kill_pid("fleet.kill.unarmed", os.getpid()) is False
        # refuses the calling process even when armed
        fi.inject("fleet.kill.self")
        assert fi.kill_pid("fleet.kill.self", os.getpid()) is False
        child = subprocess.Popen([sys.executable, "-c",
                                  "import time; time.sleep(60)"])
        try:
            fi.inject("fleet.kill.child", times=1)
            assert fi.kill_pid("fleet.kill.child", child.pid) is True
            assert child.wait(timeout=10) == -signal.SIGKILL
            # the shot was consumed: the site is disarmed again
            assert fi.kill_pid("fleet.kill.child", child.pid) is False
        finally:
            if child.poll() is None:
                child.kill()


class TestReplicaEnvironment:
    """launch_replica decides nothing about the platform: the child
    gets this process's environment plus ``env``. Only a child that is
    NOT told ``cpu`` is pinned to one chip — and refused if this
    process already holds the chips. Popen is faked: no process."""

    @pytest.fixture
    def spawned(self, monkeypatch):
        from paddle_tpu import serving_fleet as sf
        envs = []

        class FakeProc:
            pid, returncode = 4242, None

            def __init__(self):
                self.stdout = io.StringIO(
                    '{"ok": true, "port": 1, "pid": 4242}\n')

            def poll(self):
                return None

        def fake_popen(cmd, env=None, **kw):
            envs.append(env)
            return FakeProc()

        monkeypatch.setattr(sf.subprocess, "Popen", fake_popen)
        return envs

    def test_cpu_parent_env_is_inherited_not_added(self, spawned,
                                                   monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")   # as conftest does
        launch_replica({"model": {}}, chip=3)
        assert spawned[0]["JAX_PLATFORMS"] == "cpu"
        assert "TPU_VISIBLE_CHIPS" not in spawned[0]

    def test_unset_platform_stays_unset_and_pins_chip_i(self, spawned,
                                                        monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS")
        launch_replica({"model": {}}, chip=3)
        assert "JAX_PLATFORMS" not in spawned[0]
        assert spawned[0]["TPU_VISIBLE_CHIPS"] == "3"
        assert spawned[0]["TPU_PROCESS_BOUNDS"] == "1,1,1"

    def test_parent_holding_the_chips_is_refused(self, spawned,
                                                 monkeypatch):
        from paddle_tpu import serving_fleet as sf
        monkeypatch.delenv("JAX_PLATFORMS")
        monkeypatch.setattr(sf, "_parent_holds_tpu", lambda: True)
        with pytest.raises(RuntimeError, match="holds the host's chips"):
            launch_replica({"model": {}})
        assert spawned == []
        # an explicit CPU fleet from the same parent is fine
        launch_replica({"model": {}}, env={"JAX_PLATFORMS": "cpu"})
        assert len(spawned) == 1


# ---------------------------------------------------------------------------
# real-subprocess chaos acceptance (slow: boots child processes and
# compiles the tiny model once to seed the shared executable cache)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestSubprocessFleet:
    def test_sigkill_chaos_bit_equal_and_warm_resurrection(self, tmp_path):
        """The ISSUE acceptance scenario end to end: 3 real replica
        processes warm-booted from one bundle; an armed
        ``fleet.apply.r1`` site SIGKILLs replica 1 mid-decode; every
        accepted request finishes bit-equal to the single-server
        oracle with one terminal fleet event; the replacement replica
        rejoins from the warm bundle with cache misses still 0."""
        cache = tmp_path / "xcache"
        bundle = tmp_path / "warm.npz"
        env = {"FLAGS_executable_cache_dir": str(cache)}
        base = {"model": {"kind": "tiny_llama", "seed": 7, "config": CFG},
                "max_slots": 2, "max_seq": 64, "block_size": 8,
                "prefill_chunk": 8, "supervised": True}

        # ONE cold boot compiles everything, then seals the bundle —
        # and doubles as the uninterrupted single-server oracle
        cold = dict(base, prime=[1, 2, 3, 4], prime_tokens=4,
                    export_bundle=str(bundle))
        proc, port, boot = launch_replica(cold, env=env)
        prompts = [[1, 2, 3], [2, 3, 4], [3, 4, 5], [4, 5, 6], [9, 9]]
        oracle = {}
        try:
            cli = ReplicaClient("127.0.0.1", port)
            for p in prompts:
                oracle[tuple(p)] = cli.generate(p, 16, timeout=120)
            # rollout duck-type over RPC: retain + identity swap
            token = cli.engine.params
            res = cli.swap_weights(prepared=token)
            assert res["seconds"] >= 0
            cli._call({"op": "shutdown", "drain": True})
            cli.close()
        finally:
            proc.wait(timeout=60)
        assert boot["cache"]["misses"] > 0  # the cold boot compiled

        from paddle_tpu.serving_fleet import spawn_fleet
        flight.clear()
        warm = dict(base, warm_bundle=str(bundle))
        router = spawn_fleet(
            3, warm, env=env,
            router_kwargs=dict(policy="rr", heartbeat_seconds=0.2,
                               heartbeat_misses=2, restart_backoff=0.05,
                               max_restarts=4))
        try:
            for h in router.replicas:
                stats = h.call({"op": "cache_stats"})["cache"]
                assert stats["misses"] == 0, \
                    f"replica {h.idx} warm boot compiled fresh: {stats}"

            # SIGKILL replica 1 the moment the router applies its 4th
            # streamed token batch — deterministically mid-decode
            fi.inject("fleet.apply.r1", times=1, skip=3)
            reqs = [router.submit(p, 16) for p in prompts[:4]]
            for req, p in zip(reqs, prompts[:4]):
                assert req["done"].wait(120)
                assert req["error"] is None
                assert req["out"] == oracle[tuple(p)]
            assert router.failovers >= 1
            assert any(e["name"] == "replica_dead"
                       and e["attrs"].get("replica") == 1
                       for e in flight.events(category="fleet"))
            counts = _fleet_terminal_counts([r["trace_id"] for r in reqs])
            assert all(c == 1 for c in counts.values()), counts

            deadline = time.monotonic() + 120
            while time.monotonic() < deadline \
                    and router.stats()["live"] < 3:
                time.sleep(0.1)
            assert router.stats()["live"] == 3, \
                "SIGKILLed replica did not resurrect"
            reborn = router.replicas[1]
            assert reborn.restarts >= 1
            stats = reborn.call({"op": "cache_stats"})["cache"]
            assert stats["misses"] == 0, \
                f"resurrection compiled fresh XLA programs: {stats}"
            # the reborn replica serves bit-equal traffic
            assert router.generate([9, 9], 16, timeout=120) \
                == oracle[(9, 9)]
        finally:
            fi.clear()
            router.shutdown(drain=False, timeout=30)
