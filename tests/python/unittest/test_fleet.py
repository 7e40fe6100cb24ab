"""Cross-host serving fleet (mxnet_tpu/serving/pool.py + worker.py +
autoscaler.py, hedged dispatch in server.py, wire auth — ISSUE 12).

The contracts under test:
  * wire auth — HMAC verified BEFORE unpickling, tampered/keyless frames
    rejected typed, kvstore keeps its trusted no-auth default;
  * fleet membership — join with warmup + half-open probe, heartbeat
    supervision through SUSPECT (routed around) and DEAD (detached,
    in-flight resolved by id), recovery and readmission;
  * a remote worker serves BIT-IDENTICAL outputs through the gateway's
    unchanged dispatch surface (least-loaded, breaker, resubmit);
  * hedged dispatch — an injected straggler replica triggers a hedge,
    first result wins, single resolution, no double counting;
  * autoscaler — hysteresis, cooldown, hard floor, min-worker restore;
  * orphan TTL enforced by TIME, not by traffic;
  * zero-overhead — with fleet/hedging/auth env unset the in-process
    path gains no thread, no hedger, and no per-request env read;
  * the multi-process chaos gate: gateway + 2 REAL worker processes
    under overload, SIGKILL one mid-trace — exactly-once accounting on
    both sides, breaker/fleet health reflect the death, and a restarted
    worker is readmitted and actually serves.
"""
import os
import pickle
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.base import MXNetError
from mxnet_tpu.resilience import faults
from mxnet_tpu.serving import (ModelServer, ServingFrontDoor, ServingClient,
                               FleetPool, ReplicaWorker, Autoscaler,
                               DeadlineExceeded)
from mxnet_tpu.serving import wire
from mxnet_tpu.serving.pool import RemoteReplica

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _net(prefix, hidden=8, classes=3):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden,
                                name=prefix + "_fc0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes,
                                name=prefix + "_fc1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _params(sym, rng):
    shapes, _, _ = sym.infer_shape(data=(4, 6))
    return {n: mx.nd.array(rng.normal(0, 0.5, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}


def _server(model="fl", warm=True, **kw):
    rng = np.random.RandomState(0)
    sym = _net(model)
    srv = ModelServer(**{k: v for k, v in kw.items()
                         if k in ("hedge_ms", "hedge_factor",
                                  "hedge_min_ms", "dispatch_retries",
                                  "breaker_threshold")})
    engine_kw = {k: v for k, v in kw.items()
                 if k not in ("hedge_ms", "hedge_factor", "hedge_min_ms",
                              "dispatch_retries", "breaker_threshold")}
    srv.register(model, sym, _params(sym, rng), ctx=mx.cpu(),
                 buckets=(1, 4), max_delay_ms=0.5,
                 warmup_shapes={"data": (4, 6)} if warm else None,
                 **engine_kw)
    return srv


def _x(rng=None, n=4):
    if rng is None:
        return np.arange(n * 6, dtype=np.float32).reshape(n, 6) / (n * 6.0)
    return rng.normal(0, 1, (n, 6)).astype(np.float32)


def _wait(cond, timeout=30.0, msg="condition", tick=0.02):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out waiting for %s" % msg
        time.sleep(tick)


# ---------------------------------------------------------------------------
# wire auth
# ---------------------------------------------------------------------------

class TestWireAuth:
    KEY = b"fleet-secret"

    def _pair(self):
        a, b = socket.socketpair()
        return a, b

    def test_roundtrip_with_key(self):
        a, b = self._pair()
        try:
            wire.send_msg(a, ("hello", 42), auth_key=self.KEY)
            assert wire.recv_msg(b, auth_key=self.KEY) == ("hello", 42)
        finally:
            a.close()
            b.close()

    def test_unauthenticated_frame_rejected_before_unpickle(self):
        # the payload is a pickle whose deserialization would EXECUTE:
        # an authenticated receiver must reject it while it is still
        # inert bytes (AuthError), never reach pickle.loads
        a, b = self._pair()
        try:
            class _Boom:
                def __reduce__(self):
                    return (pytest.fail,
                            ("unauthenticated frame was unpickled",))
            wire.send_msg(a, _Boom())       # no auth key: plain frame
            with pytest.raises(wire.AuthError):
                wire.recv_msg(b, auth_key=self.KEY)
        finally:
            a.close()
            b.close()

    def test_tampered_frame_rejected(self):
        a, b = self._pair()
        try:
            payload = pickle.dumps(("ping", 1))
            sealed = wire._seal(payload, self.KEY)
            tampered = bytes([sealed[0] ^ 0xFF]) + sealed[1:]
            a.sendall(struct.pack("<Q", len(tampered)) + tampered)
            with pytest.raises(wire.AuthError):
                wire.recv_msg(b, auth_key=self.KEY)
        finally:
            a.close()
            b.close()

    def test_wrong_key_rejected(self):
        a, b = self._pair()
        try:
            wire.send_msg(a, ("x",), auth_key=b"other-key")
            with pytest.raises(wire.AuthError):
                wire.recv_msg(b, auth_key=self.KEY)
        finally:
            a.close()
            b.close()

    def test_auth_error_is_frame_error(self):
        # the front door's eviction machinery counts FrameError strikes;
        # auth failures must ride the same path
        assert issubclass(wire.AuthError, wire.FrameError)

    def test_kvstore_default_ignores_env(self, monkeypatch):
        # the kvstore wrappers call the wire helpers WITHOUT auth_key:
        # even with the env set, the trusted transport stays plain
        # (docs/faq/serving.md trust model — the split is deliberate)
        monkeypatch.setenv("MXNET_SERVING_AUTH_KEY", "envkey")
        a, b = self._pair()
        try:
            wire.send_msg(a, ("plain", 7))
            assert wire.recv_msg(b, max_bytes=None) == ("plain", 7)
        finally:
            a.close()
            b.close()

    def test_auth_key_from_env(self, monkeypatch):
        monkeypatch.delenv("MXNET_SERVING_AUTH_KEY", raising=False)
        assert wire.auth_key_from_env() is None
        monkeypatch.setenv("MXNET_SERVING_AUTH_KEY", "s3")
        assert wire.auth_key_from_env() == b"s3"


def test_frontdoor_auth_end_to_end():
    key = "fd-auth-key"
    srv = _server("fa")
    fd = ServingFrontDoor(srv, port=0, auth_key=key).start()
    try:
        x = _x()
        want = np.asarray(srv.predict("fa", {"data": x})[0])
        cli = ServingClient("127.0.0.1", fd.port, auth_key=key)
        got = np.asarray(cli.predict({"data": x}, model="fa",
                                     timeout=30.0)[0])
        assert np.array_equal(got, want)
        cli.close()
        # keyless client: the hello frame fails auth client-side and
        # the handshake raises typed — nothing was ever unpickled
        with pytest.raises(MXNetError):
            bad = ServingClient("127.0.0.1", fd.port,
                                connect_deadline_s=2.0)
            bad.ping(timeout=5.0)
        # tampered frame on a raw socket: rejected as an auth strike
        ks = socket.create_connection(("127.0.0.1", fd.port), timeout=10.0)
        wire.recv_msg(ks, auth_key=key.encode())
        sealed = wire._seal(pickle.dumps(("ping", "r1")), key.encode())
        tampered = bytes([sealed[0] ^ 0xFF]) + sealed[1:]
        ks.sendall(struct.pack("<Q", len(tampered)) + tampered)
        _wait(lambda: fd.stats()["auth_rejected"] >= 1, 10.0,
              "auth_rejected counter")
        ks.close()
    finally:
        fd.drain(timeout=10.0)
        srv.stop()


# ---------------------------------------------------------------------------
# orphan TTL: time-driven, not traffic-driven
# ---------------------------------------------------------------------------

def test_orphan_ttl_expires_without_new_traffic():
    srv = _server("ot")
    fd = ServingFrontDoor(srv, port=0, orphan_ttl_s=0.3).start()
    try:
        # admit a request on a raw connection, then kill the connection
        # so the reply orphans
        ks = socket.create_connection(("127.0.0.1", fd.port), timeout=10.0)
        hello = wire.recv_msg(ks)
        wire.send_msg(ks, ("predict", "c%d-1" % hello[1],
                           {"model": "ot", "arrays": {"data": _x()},
                            "deadline_ms": None, "priority": 0,
                            "trace": "ttl", "t_send": time.time()}))
        _wait(lambda: fd.stats()["submitted"] >= 1, 15.0, "admission")
        ks.close()
        _wait(lambda: fd.stats()["orphaned"] >= 1, 15.0, "orphaning")
        # NO further traffic: the acceptor's poll tick must expire it
        _wait(lambda: fd.stats()["orphans_held"] == 0, 10.0,
              "time-driven orphan sweep")
        assert fd.stats()["orphan_expired"] >= 1
    finally:
        fd.drain(timeout=10.0)
        srv.stop()


# ---------------------------------------------------------------------------
# ModelServer fleet attach points
# ---------------------------------------------------------------------------

class TestReplicaAttach:
    def test_add_then_remove(self):
        srv = _server("ra")
        eng2 = srv.engine("ra")  # reuse the same engine as a stand-in
        reps = srv.add_replicas("ra", [eng2])
        assert len(reps) == 1
        entry = srv._models["ra"]
        assert len(entry.versions[1]) == 2
        assert srv.remove_replicas("ra", reps) == 1
        assert len(entry.versions[1]) == 1
        srv.stop()

    def test_remove_last_replica_refused(self):
        srv = _server("rl")
        entry = srv._models["rl"]
        with pytest.raises(MXNetError):
            srv.remove_replicas("rl", list(entry.versions[1]))
        srv.stop()

    def test_half_open_probe_shed_releases_probe_slot(self):
        # regression (found by review): a half-open replica whose probe
        # request SHEDS must not stay probing=True forever — the shed
        # is verdict-free and must release the probe slot so the next
        # dispatch becomes the probe
        from mxnet_tpu.serving.server import _Breaker
        b = _Breaker(threshold=1, cooldown_s=0.0)
        b.on_failure(time.monotonic())          # -> open
        now = time.monotonic() + 1.0
        assert b.available(now)                 # cooldown elapsed
        b.note_dispatch(now)                    # half-open probe taken
        assert not b.available(now)             # one probe at a time
        b.on_neutral()                          # the probe shed
        assert b.available(now), \
            "shed probe left the breaker permanently unavailable"
        srv = _server("hp")
        entry = srv._models["hp"]
        rep = entry.versions[1][0]
        rep.breaker.state = "half_open"
        rep.breaker.probing = True
        rep.inflight = 1
        srv._complete(rep, "shed")
        assert rep.breaker.probing is False
        srv.stop()

    def test_unavailable_replica_routed_around(self):
        srv = _server("rv", replicas=2)
        entry = srv._models["rv"]
        reps = entry.versions[1]
        reps[0].available = False
        for _ in range(4):
            rep = srv._acquire("rv", None)
            assert rep is reps[1]
            srv._complete(rep, "success")
        # nothing available at all: forced probe keeps routing
        reps[1].available = False
        rep = srv._acquire("rv", None)
        assert rep in reps
        srv._complete(rep, "success")
        srv.stop()


# ---------------------------------------------------------------------------
# fleet membership (in-process worker: real sockets, one process)
# ---------------------------------------------------------------------------

class TestFleetMembership:
    def _fleet(self, heartbeat_s=0.25, **pool_kw):
        gw = _server("fl")
        pool = FleetPool(gw, port=0, heartbeat_s=heartbeat_s,
                         connect_deadline_s=1.5, **pool_kw).start()
        wsrv = _server("fl")
        worker = ReplicaWorker(("127.0.0.1", pool.port), wsrv, port=0,
                               worker_id="w-test",
                               heartbeat_s=heartbeat_s).start()
        assert worker.joined.wait(30.0), "worker never admitted"
        return gw, pool, worker

    def _teardown(self, gw, pool, worker):
        worker.stop()
        pool.stop()
        gw.stop()

    def test_join_probe_and_bit_identity(self):
        gw, pool, worker = self._fleet()
        try:
            assert worker.stats["probes"] >= 1, \
                "admission skipped the half-open probe"
            x = _x()
            want = np.asarray(gw.predict("fl", {"data": x})[0])
            entry = gw._models["fl"]
            remote = [r for r in entry.versions[1]
                      if isinstance(r.engine, RemoteReplica)]
            assert len(remote) == 1, "remote replica not attached"
            fut = remote[0].engine.predict_async({"data": x})
            got = np.asarray(fut.result_wait(30.0)[0])
            assert np.array_equal(got, want), \
                "remote prediction diverged from local"
            # merged health view
            h = pool.health()
            assert h["workers"]["w-test"]["state"] == "alive"
            assert h["workers_alive"] == 1
        finally:
            self._teardown(gw, pool, worker)

    def test_suspect_then_recover(self):
        gw, pool, worker = self._fleet()
        try:
            handle = pool._workers["w-test"]
            remote = [r for reps in handle.replicas.values() for r in reps]
            # forge staleness just past the SUSPECT threshold (NOT the
            # dead one — the live monitor must see a recoverable state):
            # availability flips off
            handle.last_hb -= pool._suspect_after_s + 0.05
            pool.scan()
            assert handle.state == "suspect"
            assert all(not r.available for r in remote)
            # the worker is actually alive: its next heartbeat recovers
            _wait(lambda: handle.state == "alive", 10.0, "recovery")
            assert all(r.available for r in remote)
            assert pool.stats()["recoveries"] >= 1
        finally:
            self._teardown(gw, pool, worker)

    def test_dead_detaches_and_traffic_survives(self):
        gw, pool, worker = self._fleet()
        try:
            # silence the worker's control loop: no more heartbeats
            worker._stop_evt.set()
            handle = pool._workers["w-test"]
            handle.last_hb -= 1000.0
            pool.scan()                        # -> suspect
            pool.scan()                        # still stale -> dead
            assert handle.state == "dead"
            entry = gw._models["fl"]
            assert all(not isinstance(r.engine, RemoteReplica)
                       for r in entry.versions[1]), "replica not detached"
            x = _x()
            fut = gw.predict_async("fl", {"data": x}, deadline_ms=10000.0)
            fut.result_wait(30.0)              # local floor still serves
            c = gw.stats()["fl"]["counters"]
            assert c["submitted"] == c["served"] + c["shed"] + c["failed"]
        finally:
            self._teardown(gw, pool, worker)

    def test_dead_worker_rejoins_and_is_readmitted(self):
        gw, pool, worker = self._fleet()
        try:
            worker.stop()                       # full worker shutdown
            handle = pool._workers["w-test"]
            # just past the DEAD threshold — NOT an hour: a forged age
            # beyond the reap grace would delete the handle and turn
            # the readmission below into a fresh join
            handle.last_hb -= pool._dead_after_s + 0.1
            pool.scan()
            pool.scan()
            assert handle.state == "dead"
            # restart under the SAME id: must re-pass warmup + probe
            wsrv2 = _server("fl")
            worker2 = ReplicaWorker(("127.0.0.1", pool.port), wsrv2,
                                    port=0, worker_id="w-test",
                                    heartbeat_s=0.25).start()
            try:
                assert worker2.joined.wait(30.0), "readmission failed"
                assert pool.stats()["rejoins"] >= 1
                assert worker2.stats["probes"] >= 1
                entry = gw._models["fl"]
                _wait(lambda: any(isinstance(r.engine, RemoteReplica)
                                  for r in entry.versions[1]),
                      10.0, "replica re-attach")
                x = _x()
                remote = [r for r in entry.versions[1]
                          if isinstance(r.engine, RemoteReplica)][0]
                want = np.asarray(gw.predict("fl", {"data": x})[0])
                got = np.asarray(remote.engine.predict_async(
                    {"data": x}).result_wait(30.0)[0])
                assert np.array_equal(got, want), \
                    "readmitted worker serves wrong outputs"
            finally:
                worker2.stop()
        finally:
            pool.stop()
            gw.stop()

    def test_rollover_fans_out_over_the_control_channel(self):
        gw, pool, worker = self._fleet()
        try:
            x = _x()
            entry = gw._models["fl"]
            local = [r for r in entry.versions[1]
                     if not isinstance(r.engine, RemoteReplica)][0]
            remote = [r for r in entry.versions[1]
                      if isinstance(r.engine, RemoteReplica)][0]
            old = np.asarray(local.engine.predict({"data": x})[0])
            sym = _net("fl")
            new_params = _params(sym, np.random.RandomState(42))
            gw.rollover("fl", new_params)     # blocks on the worker ack
            assert worker.stats["rollovers"] == 1
            want_new = np.asarray(local.engine.predict({"data": x})[0])
            assert not np.array_equal(want_new, old), \
                "rollover did not change the local weights"
            got = np.asarray(remote.engine.predict_async(
                {"data": x}).result_wait(30.0)[0])
            assert np.array_equal(got, want_new), \
                "remote worker serves pre-rollover weights"
        finally:
            self._teardown(gw, pool, worker)

    def test_rollover_partial_failure_is_isolated_and_typed(self):
        # one unreachable replica must not abort the fan-out: the
        # healthy replicas still swap, the error surfaces typed, and
        # (being idempotent) a retry would re-run the whole sweep
        srv = _server("ri")

        class _Down:
            replica = None
            name = "ri"

            def update_params(self, arg_params, aux_params=None):
                raise OSError("no control channel")

            def stop(self):
                pass
        down = _Down()
        srv.add_replicas("ri", [down])
        eng = srv.engine("ri", replica=0)
        x = _x()
        old = np.asarray(eng.predict({"data": x})[0])
        sym = _net("ri")
        new_params = _params(sym, np.random.RandomState(42))
        with pytest.raises(MXNetError, match="1/2"):
            srv.rollover("ri", new_params)
        new = np.asarray(eng.predict({"data": x})[0])
        assert not np.array_equal(new, old), \
            "healthy replica was denied the rollover"
        srv.stop()

    def test_unwarmed_worker_rejected(self):
        gw = _server("fl")
        pool = FleetPool(gw, port=0, heartbeat_s=0.25).start()
        wsrv = _server("fl", warm=False)
        worker = ReplicaWorker(("127.0.0.1", pool.port), wsrv, port=0,
                               worker_id="w-cold", heartbeat_s=0.25,
                               rejoin_backoff_s=30.0).start()
        try:
            _wait(lambda: pool.stats()["rejects"] >= 1, 20.0,
                  "cold-worker rejection")
            assert not worker.joined.is_set()
            assert "w-cold" not in pool.workers()
        finally:
            worker.stop()
            pool.stop()
            gw.stop()

    def test_injected_heartbeat_fault_drives_suspect_cycle(self):
        # dead threshold far out: the suppression window must only be
        # able to reach SUSPECT, so the organic recovery is observable
        gw, pool, worker = self._fleet(dead_after_s=30.0)
        try:
            faults.reset()
            # suppress ~4 worker heartbeats (1s at 0.25s cadence):
            # SUSPECT must fire, then organic recovery
            faults.configure(
                "fleet.heartbeat:side=worker:times=4:raise=OSError")
            handle = pool._workers["w-test"]
            _wait(lambda: handle.state == "suspect", 15.0,
                  "suspect on suppressed heartbeats")
            _wait(lambda: handle.state == "alive", 15.0,
                  "recovery after fault disarms")
        finally:
            faults.reset()
            self._teardown(gw, pool, worker)

    def test_threshold_validation(self):
        gw = _server("fl")
        with pytest.raises(MXNetError):
            FleetPool(gw, port=0, heartbeat_s=1.0, suspect_after_s=5.0,
                      dead_after_s=2.0)
        gw.stop()


# ---------------------------------------------------------------------------
# hedged dispatch
# ---------------------------------------------------------------------------

class TestHedging:
    def test_straggler_triggers_hedge_single_resolution(self):
        srv = _server("hg", hedge_ms=50.0, replicas=2)
        try:
            x = _x(n=1)
            want = np.asarray(srv.predict("hg", {"data": x})[0])
            faults.configure(
                "serving.dispatch:replica=0:mode=async:delay=600")
            tic = time.monotonic()
            fut = srv.predict_async("hg", {"data": x},
                                    deadline_ms=10000.0)
            out = np.asarray(fut.result_wait(30.0)[0])
            lat_ms = (time.monotonic() - tic) * 1e3
            faults.reset()
            assert np.array_equal(out, want)
            c = srv.stats()["hg"]["counters"]
            assert c["hedges"] >= 1, c
            assert c["hedge_wins"] >= 1, c
            # the hedge IS the p99 fix: resolved far below the 600ms
            # straggler (generous bound for CI noise)
            assert lat_ms < 450.0, lat_ms
            # wait out the straggler: its late result must be discarded
            # internally, never re-counted
            time.sleep(0.9)
            c2 = srv.stats()["hg"]["counters"]
            assert c2["served"] == c["served"], \
                "hedge loser double-counted"
            assert c2["submitted"] == c2["served"] + c2["shed"] \
                + c2["failed"]
        finally:
            faults.reset()
            srv.stop()

    def test_no_second_replica_no_hedge(self):
        srv = _server("h1", hedge_ms=10.0, replicas=1)
        try:
            faults.configure(
                "serving.dispatch:replica=0:mode=async:delay=150")
            fut = srv.predict_async("h1", {"data": _x(n=1)},
                                    deadline_ms=10000.0)
            fut.result_wait(30.0)
            faults.reset()
            c = srv.stats()["h1"]["counters"]
            assert c["hedges"] == 0, \
                "hedged onto the same single replica"
            assert c["served"] == c["submitted"]
        finally:
            faults.reset()
            srv.stop()

    def test_hedge_delay_derivation(self):
        # auto mode (hedge_ms=0): floor with no data, factor x p95 once
        # the device histogram has samples
        srv = _server("hd", hedge_ms=0.0, hedge_factor=3.0,
                      hedge_min_ms=7.0)
        try:
            hedger = srv._hedger
            assert hedger is not None
            assert hedger.delay_s("hd", 1) >= 7.0 / 1e3
            profiler.record_latency("serving.hd.device", 20e6)  # 20ms
            hedger._delay_cache.clear()
            delay = hedger.delay_s("hd", 1)
            assert delay >= 3.0 * 0.015, delay  # ~factor x p95 (log buckets)
        finally:
            srv.stop()

    def test_hedging_off_by_default(self, monkeypatch):
        monkeypatch.delenv("MXNET_SERVING_HEDGE_MS", raising=False)
        srv = ModelServer()
        assert srv._hedger is None
        srv.stop()

    def test_env_enables(self, monkeypatch):
        monkeypatch.setenv("MXNET_SERVING_HEDGE_MS", "25")
        srv = ModelServer()
        assert srv._hedger is not None
        assert srv._hedger._fixed_ms == 25.0
        srv.stop()


# ---------------------------------------------------------------------------
# autoscaler
# ---------------------------------------------------------------------------

class _FakeLauncher:
    def __init__(self, alive=1):
        self._alive = alive
        self.launched = 0
        self.terminated = 0

    def launch(self):
        self._alive += 1
        self.launched += 1
        return object()

    def terminate_one(self):
        if self._alive <= 0:
            return None
        self._alive -= 1
        self.terminated += 1
        return object()

    def alive_count(self):
        return self._alive


def _health(q95=0.0, submitted=0, shed=0, avail=3):
    return {"ok": True, "models": {"m": {
        "queue_wait_p95_ms": q95, "submitted": submitted, "shed": shed,
        "replicas_available": avail}}}


class TestAutoscaler:
    def test_scale_up_needs_hysteresis(self):
        launcher = _FakeLauncher(alive=1)
        state = {"h": _health(q95=500.0)}
        asc = Autoscaler(lambda: state["h"], launcher, min_workers=0,
                         max_workers=4, up_queue_ms=100.0, hysteresis=2,
                         cooldown_s=0.0)
        assert asc.tick() is None          # streak 1 of 2
        assert asc.tick() == "up"          # streak 2 -> act
        assert launcher.launched == 1

    def test_windowed_shed_rate_triggers(self):
        launcher = _FakeLauncher(alive=1)
        seq = [_health(submitted=100, shed=0),
               _health(submitted=200, shed=50),   # window rate 0.5
               _health(submitted=300, shed=100)]
        it = iter(seq)
        asc = Autoscaler(lambda: next(it), launcher, min_workers=1,
                         hysteresis=1, cooldown_s=0.0, up_queue_ms=1e9,
                         up_shed_rate=0.1)
        assert asc.tick() is None          # first tick: no window yet
        assert asc.tick() == "up"

    def test_cooldown_holds(self):
        launcher = _FakeLauncher(alive=1)
        asc = Autoscaler(lambda: _health(q95=500.0), launcher,
                         hysteresis=1, cooldown_s=1000.0,
                         up_queue_ms=100.0)
        assert asc.tick() == "up"
        assert asc.tick() is None
        assert asc.stats["held_cooldown"] >= 1
        assert launcher.launched == 1

    def test_scale_down_floor_never_drains_last_replica(self):
        launcher = _FakeLauncher(alive=3)
        asc = Autoscaler(lambda: _health(q95=0.0, avail=1), launcher,
                         min_workers=0, hysteresis=1, cooldown_s=0.0,
                         down_queue_ms=50.0)
        assert asc.tick() is None
        assert asc.stats["held_floor"] >= 1
        assert launcher.terminated == 0

    def test_scale_down_when_safe(self):
        launcher = _FakeLauncher(alive=3)
        asc = Autoscaler(lambda: _health(q95=0.0, avail=4), launcher,
                         min_workers=1, hysteresis=1, cooldown_s=0.0,
                         down_queue_ms=50.0)
        assert asc.tick() == "down"
        assert launcher.terminated == 1

    def test_min_workers_restored_after_death(self):
        launcher = _FakeLauncher(alive=0)    # everything died
        asc = Autoscaler(lambda: _health(), launcher, min_workers=2,
                         hysteresis=5, cooldown_s=0.0)
        assert asc.tick() == "up"            # restore, ignoring streaks
        assert launcher.launched == 1

    def test_max_workers_cap(self):
        launcher = _FakeLauncher(alive=2)
        asc = Autoscaler(lambda: _health(q95=500.0), launcher,
                         max_workers=2, hysteresis=1, cooldown_s=0.0,
                         up_queue_ms=100.0)
        assert asc.tick() is None
        assert launcher.launched == 0


# ---------------------------------------------------------------------------
# zero-overhead contract
# ---------------------------------------------------------------------------

def test_zero_overhead_without_fleet_env(monkeypatch):
    """With fleet/hedging/auth env unset the in-process serving path
    gains NO new thread, NO hedger/pool object, and NO per-request env
    read (the PR 9 contract, extended to ISSUE 12's knobs)."""
    for var in ("MXNET_SERVING_HEDGE_MS", "MXNET_SERVING_AUTH_KEY",
                "MXNET_SERVING_FLEET_PORT", "MXNET_TPU_FAULT_SPEC"):
        monkeypatch.delenv(var, raising=False)
    srv = _server("zo")
    try:
        assert srv._hedger is None
        assert not faults.enabled()
        x = _x(n=1)
        srv.predict_async("zo", {"data": x}).result_wait(30.0)
        before = {t.name for t in threading.enumerate()}
        # per-request env reads are forbidden: every knob was cached at
        # construction. get_env is the framework's only env accessor.
        import mxnet_tpu.base as _base

        def _no_env(name, default=None, typ=str):
            raise AssertionError("per-request env read of %s" % name)
        monkeypatch.setattr(_base, "get_env", _no_env)
        monkeypatch.setattr("mxnet_tpu.serving.wire.get_env", _no_env)
        for _ in range(4):
            fut = srv.predict_async("zo", {"data": x},
                                    deadline_ms=5000.0)
            fut.result_wait(30.0)
        monkeypatch.undo()
        after = {t.name for t in threading.enumerate()}
        new = {n for n in after - before
               if not n.startswith("ThreadPoolExecutor")}
        assert not new, "in-process dispatch grew threads: %s" % new
        c = srv.stats()["zo"]["counters"]
        assert c["hedges"] == 0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# the multi-process chaos gate
# ---------------------------------------------------------------------------

def _spawn_fixture_worker(port, wid):
    """One REAL worker OS process off the shared fixture
    (tools/fleet_worker_fixture.py — same net/params/seed as this
    file's gateway helpers, which is what makes the bit-identity
    assertions meaningful)."""
    return subprocess.Popen(
        [sys.executable,
         os.path.join(ROOT, "tools", "fleet_worker_fixture.py"),
         str(port), wid])


def test_multiprocess_fleet_kill_exactly_once_and_readmission():
    """The ISSUE 12 chaos gate: gateway + 2 REAL worker processes under
    overload; SIGKILL one mid-trace. submitted == served + shed + failed
    with zero lost and zero non-typed failures on both sides; the fleet
    reflects the death; a restarted worker under the same id is
    READMITTED and actually serves."""
    gw = _server("fl", dispatch_retries=3)
    pool = FleetPool(gw, port=0, heartbeat_s=0.25,
                     connect_deadline_s=1.0).start()

    def _spawn(wid):
        return _spawn_fixture_worker(pool.port, wid)
    procs = [_spawn("w1"), _spawn("w2")]
    try:
        _wait(lambda: pool.stats()["workers_alive"] >= 2, 90.0,
              "both workers joining", tick=0.1)
        x = _x()
        want = np.asarray(gw.predict("fl", {"data": x})[0])
        base = gw.stats()["fl"]["counters"]["submitted"]

        # open-loop burst (well past one replica's capacity) with the
        # kill landing mid-trace
        futs = []
        n_req = 400
        t_kill = None
        for i in range(n_req):
            if i == 150:
                procs[0].send_signal(signal.SIGKILL)
                t_kill = time.monotonic()
            futs.append(gw.predict_async("fl", {"data": x},
                                         deadline_ms=8000.0))
        served = shed = failed = 0
        errors = []
        retried = 0
        t_recover = None
        for f in futs:
            try:
                out = f.result_wait(60.0)
                np.testing.assert_array_equal(np.asarray(out[0]), want)
                served += 1
                if f.attempts > 1:
                    retried += 1
                    if t_recover is None or f.t_done < t_recover:
                        t_recover = f.t_done
            except DeadlineExceeded:
                shed += 1
            except Exception as e:
                failed += 1
                if len(errors) < 5:
                    errors.append("%s: %s" % (type(e).__name__,
                                              str(e)[:150]))
        # client-side exactly-once
        assert served + shed + failed == n_req
        assert failed == 0, "non-typed failures under worker kill: %s" \
            % errors
        # server-side invariant
        c = gw.stats()["fl"]["counters"]
        assert c["submitted"] - base == n_req
        assert c["submitted"] == c["served"] + c["shed"] + c["failed"]
        # the kill was actually exercised: requests rerouted
        assert retried > 0, "no request was ever rerouted off the " \
            "killed worker — the trace missed the kill window"
        if t_recover is not None and t_kill is not None:
            assert t_recover - t_kill < 30.0
        # fleet health reflects the death
        _wait(lambda: pool.workers()["w1"]["state"] in ("suspect", "dead"),
              20.0, "death detection", tick=0.1)

        # --- readmission: restart w1 under the SAME id ---------------
        _wait(lambda: pool.workers()["w1"]["state"] == "dead", 20.0,
              "DEAD declaration", tick=0.1)
        procs.append(_spawn("w1"))
        # the handle may be reaped before the replacement finishes its
        # (jax-import-heavy) startup, in which case the same-id join
        # counts as a fresh join rather than a rejoin — what matters is
        # that w1 is back, ALIVE, and admitted through warmup + probe
        _wait(lambda: pool.workers().get("w1", {}).get("state")
              == "alive", 90.0, "readmission", tick=0.1)
        entry = gw._models["fl"]
        _wait(lambda: sum(isinstance(r.engine, RemoteReplica)
                          for r in entry.versions[1]) >= 2, 20.0,
              "replica re-attach", tick=0.1)
        # the readmitted worker actually serves: push directly through
        # its replica
        handle = pool._workers["w1"]
        rep = next(iter(handle.replicas.values()))[0]
        got = np.asarray(rep.engine.predict_async(
            {"data": x}).result_wait(30.0)[0])
        assert np.array_equal(got, want)
    finally:
        pool.stop()
        gw.stop()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()


def test_local_launcher_pins_workers_off_a_held_chip(monkeypatch):
    """One process per chip: a launcher whose own process has initialised
    JAX holds the host's chips, so its local workers are pinned to the CPU
    backend in the env it builds — unless the caller names the platform."""
    import jax
    from mxnet_tpu.serving import autoscaler as _as
    jax.devices()  # this process holds its backend
    seen = []

    class _Proc:
        pid = 0

        def poll(self):
            return None

    monkeypatch.setattr(_as.subprocess, "Popen",
                        lambda cmd, env=None: seen.append(env) or _Proc())
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    _as.LocalProcessLauncher("127.0.0.1:1", "m:f").launch()
    _as.LocalProcessLauncher("127.0.0.1:1", "m:f",
                             env={"JAX_PLATFORMS": "tpu"}).launch()
    assert seen[0]["JAX_PLATFORMS"] == "cpu"
    assert seen[1]["JAX_PLATFORMS"] == "tpu"
