"""Serving-fleet failure matrix (serve/fleet.py + frontend.py,
ISSUE 17): lease expiry ejection, circuit-breaker cycle,
retry-vs-deadline, hedge accounting, drain-completes-queued-work,
replica_crash exactly-once failover, kv_flap last-known-good routing,
and the typed OverloadError wire contract through the HTTP frontend.

Fast cases run thread-backed ReplicaServers (real TCP wire protocol,
toy engines, in-process KV) with millisecond heartbeats; one case runs
the REAL arc — spawned replica processes loading sha256-published
checkpoint weights, SIGKILLed mid-load — on multiprocess CPU.
"""
import http.client
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import dist, elastic, faultinject, telemetry
from mxnet_tpu.serve import fleet
from mxnet_tpu.serve.fleet import ReplicaServer, Router
from mxnet_tpu.serve.frontend import Frontend
from mxnet_tpu.serve.tenancy import (OverloadError, from_wire_error,
                                     http_status, to_wire_error)

pytestmark = pytest.mark.serve

HB = 0.05          # test heartbeat; lease ttl = HB * MISS_K = 0.15s
MISS_K = 3


# ---------------------------------------------------------------------------
# toy engine: the wire/routing layers only need submit()/result()
# ---------------------------------------------------------------------------
class ToyFuture:
    def __init__(self, value, delay=0.0):
        self._value, self._delay = value, delay

    def result(self, timeout=None):
        if self._delay:
            time.sleep(self._delay)
        if isinstance(self._value, BaseException):
            raise self._value
        return self._value


class ToyScheduler:
    def __init__(self, delay=0.0, fail=None, depth=0, scale=2.0):
        self.delay, self.fail, self.depth = delay, fail, depth
        self.scale = scale
        self.calls = 0
        self.closed = False
        self.drained_calls = 0

    def submit(self, *arrays, tenant="default"):
        self.calls += 1
        if self.fail is not None:
            return ToyFuture(self.fail, self.delay)
        return ToyFuture(arrays[0] * self.scale, self.delay)

    def stats(self):
        return {"queue_depth": self.depth, "inflight": 0}

    def close(self, drain=None):
        self.closed = True


def _counter(prefix):
    return sum(v for k, v in telemetry.snapshot()["counters"].items()
               if k.startswith(prefix))


@pytest.fixture()
def kv():
    return dist.KV(dist.LocalKV())


@pytest.fixture(autouse=True)
def _no_faults():
    faultinject.clear()
    yield
    faultinject.clear()


def _mk(kv, rid, sched, **kw):
    return ReplicaServer(sched, rid, kv=kv, heartbeat_s=HB,
                         miss_k=MISS_K, **kw)


def _router(kv, **kw):
    kw.setdefault("heartbeat_s", HB)
    kw.setdefault("miss_k", MISS_K)
    r = Router(kv=kv, **kw)
    r.refresh()
    return r


X = np.arange(8, dtype=np.float32).reshape(2, 4)


# ---------------------------------------------------------------------------
# wire + KV foundations
# ---------------------------------------------------------------------------
def test_wire_frame_roundtrip():
    a, b = socket.socketpair()
    try:
        arrays = [np.arange(6, dtype=np.float32).reshape(2, 3),
                  np.array([[True, False]]),
                  np.arange(4, dtype=np.int64)]
        fleet._send_frame(a, {"op": "infer", "tenant": "t"}, arrays)
        header, got = fleet._recv_frame(b)
        assert header["op"] == "infer" and header["tenant"] == "t"
        assert len(got) == 3
        for x, y in zip(arrays, got):
            assert x.dtype == y.dtype and x.shape == y.shape
            assert np.array_equal(x, y)
    finally:
        a.close()
        b.close()


def test_tcp_kv_and_lease_expiry():
    srv = dist.KVServer()
    try:
        kv = dist.KV(dist.TcpKV(srv.address))
        kv.set("mx/t/a", "1")
        assert kv.try_get("mx/t/a") == "1"
        assert kv.try_get("mx/t/missing") is None
        kv.set("mx/t/b", "2")
        assert kv.dir_get("mx/t/") == {"mx/t/a": "1", "mx/t/b": "2"}
        kv.delete("mx/t/a")
        assert kv.try_get("mx/t/a") is None

        dist.lease_publish(kv, "mx/t/lease", {"addr": "h:1"}, ttl_s=0.1)
        rec = dist.lease_read(kv, "mx/t/lease")
        assert rec["alive"] and rec["payload"]["addr"] == "h:1"
        time.sleep(0.15)
        assert not dist.lease_read(kv, "mx/t/lease")["alive"]

        lease = dist.Lease(kv, "mx/t/renewed", 0.1,
                           lambda: {"n": 1}).start()
        time.sleep(0.3)    # renewal keeps it alive well past one ttl
        assert dist.lease_read(kv, "mx/t/renewed")["alive"]
        lease.stop(drop=True)
        assert dist.lease_read(kv, "mx/t/renewed") is None
    finally:
        srv.close()


def test_consume_kv_notice_tombstone_dedup():
    class NoDelete:
        """Client without key_value_delete: consumption must tombstone."""

        def __init__(self):
            self._kv = dist.LocalKV()
            self.key_value_try_get = self._kv.key_value_try_get

        def key_value_set(self, key, value, allow_overwrite=False):
            self._kv.key_value_set(key, value,
                                   allow_overwrite=allow_overwrite)

    client = NoDelete()
    client.key_value_set("mx/t/drain", "spec-1")
    dedup = [None]
    assert elastic.consume_kv_notice("mx/t/drain", dedup,
                                     client=client) == "spec-1"
    # consumed: tombstoned AND deduped — never replays
    assert elastic.consume_kv_notice("mx/t/drain", dedup,
                                     client=client) is None
    assert client._kv.key_value_try_get("mx/t/drain") == ""
    # a fresh post fires again
    client.key_value_set("mx/t/drain", "spec-2", allow_overwrite=True)
    assert elastic.consume_kv_notice("mx/t/drain", dedup,
                                     client=client) == "spec-2"


def test_fleet_future_first_wins():
    fut = fleet.FleetFuture("id", "t")
    assert fut._set(1, None, replica="a")
    assert not fut._set(2, None, replica="b")   # duplicate discarded
    assert fut.result(0) == 1 and fut.replica == "a"


def test_overload_error_wire_contract():
    e = OverloadError("queue full", code="overload", tenant="paid")
    wire = to_wire_error(e)
    assert wire == {"code": "overload", "message": "queue full",
                    "tenant": "paid"}
    back = from_wire_error(json.loads(json.dumps(wire)))
    assert isinstance(back, OverloadError)
    assert back.code == "overload" and back.tenant == "paid"
    assert (http_status("overload"), http_status("timeout"),
            http_status("drain"), http_status("error")) == (429, 504,
                                                            503, 500)
    # untyped exceptions stay typed-'error', never reprs to parse
    wire = to_wire_error(ValueError("boom"))
    assert wire["code"] == "error" and "boom" in wire["message"]
    assert not isinstance(from_wire_error(wire), OverloadError)


# ---------------------------------------------------------------------------
# routing + resilience ladder
# ---------------------------------------------------------------------------
def test_router_routes_and_spreads(kv):
    sa, sb = ToyScheduler(), ToyScheduler()
    ra, rb = _mk(kv, "ra", sa), _mk(kv, "rb", sb)
    router = _router(kv)
    try:
        out = router.infer(X)
        assert np.allclose(out, X * 2.0)
        futs = [router.submit(X) for _ in range(12)]
        for f in futs:
            assert np.allclose(f.result(5), X * 2.0)
        assert sa.calls > 0 and sb.calls > 0    # both replicas used
        table = router.table()
        assert table["replicas"]["ra"]["alive"]
        assert not table["stale"]
    finally:
        router.close()
        ra.close()
        rb.close()


def test_lease_expiry_ejection(kv):
    sa, sb = ToyScheduler(), ToyScheduler()
    ra, rb = _mk(kv, "ra", sa), _mk(kv, "rb", sb)
    router = _router(kv)
    ej0 = _counter("mx_fleet_ejections_total")
    try:
        # ra freezes: renewal stops but the lease key stays — exactly
        # what a SIGKILL looks like. MISS_K missed heartbeats -> eject.
        ra._lease.stop(drop=False)
        time.sleep(HB * MISS_K + 0.1)
        router.refresh()
        table = router.table()
        assert not table["replicas"]["ra"]["alive"]
        assert table["replicas"]["rb"]["alive"]
        assert _counter("mx_fleet_ejections_total") >= ej0 + 1
        before = sb.calls
        for _ in range(4):
            assert np.allclose(router.infer(X), X * 2.0)
        assert sb.calls == before + 4          # no new work lands on ra
    finally:
        router.close()
        ra.close()
        rb.close()


def test_breaker_open_halfopen_close_cycle(kv):
    sa = ToyScheduler(fail=RuntimeError("engine boom"))
    ra = _mk(kv, "ra", sa)
    router = _router(kv, retries=0, breaker_fails=3, breaker_ms=60)
    t0 = _counter("mx_fleet_breaker_transitions_total")
    try:
        for _ in range(3):
            with pytest.raises(Exception):
                router.infer(X)
        assert sa.calls == 3
        assert router.table()["replicas"]["ra"]["breaker"] == "open"
        # open: requests are shed WITHOUT touching the replica
        with pytest.raises(OverloadError) as ei:
            router.infer(X)
        assert ei.value.code == "overload"
        assert sa.calls == 3                   # breaker held the door
        # heal + wait out the backoff -> ONE half-open probe -> closed
        sa.fail = None
        time.sleep(0.08)
        assert np.allclose(router.infer(X), X * 2.0)
        assert sa.calls == 4
        assert router.table()["replicas"]["ra"]["breaker"] == "closed"
        assert _counter("mx_fleet_breaker_transitions_total") >= t0 + 2
    finally:
        router.close()
        ra.close()


def test_retry_respects_deadline(kv):
    # ra is preferred (rb reports a deep queue) but replies after the
    # request's deadline; the router must fail TYPED-timeout without
    # burning the retry budget on rb past the deadline.
    sa = ToyScheduler(delay=0.3, fail=RuntimeError("slow boom"))
    sb = ToyScheduler(depth=50)
    ra, rb = _mk(kv, "ra", sa), _mk(kv, "rb", sb)
    time.sleep(2 * HB)               # let leases carry the depth signal
    router = _router(kv, retries=2)
    try:
        with pytest.raises(OverloadError) as ei:
            router.infer(X, deadline_ms=120)
        assert ei.value.code == "timeout"
        assert sb.calls == 0         # never retried past the deadline
    finally:
        router.close()
        ra.close()
        rb.close()


def test_hedge_winner_loser_accounting(kv):
    sa, sb = ToyScheduler(delay=0.4), ToyScheduler()
    sa.depth = 0
    sb.depth = 20                    # ra preferred, rb the hedge target
    ra, rb = _mk(kv, "ra", sa), _mk(kv, "rb", sb)
    time.sleep(2 * HB)
    router = _router(kv, retries=1)
    won0 = _counter('mx_fleet_hedges_total{result="won"}')
    lost0 = _counter('mx_fleet_hedges_total{result="lost"}')
    can0 = _counter("mx_fleet_hedge_cancelled_total")
    try:
        out = router.infer(X, hedge_ms=60)
        assert np.allclose(out, X * 2.0)       # hedge (rb) won
        assert sb.calls == 1
        assert _counter('mx_fleet_hedges_total{result="won"}') == won0 + 1
        time.sleep(0.5)              # the loser completes -> cancelled
        assert _counter("mx_fleet_hedge_cancelled_total") == can0 + 1

        # now the primary is slow enough to LAUNCH the hedge but
        # still beats it: hedge launched-and-lost
        sa.delay, sb.delay = 0.1, 0.4
        sa.depth, sb.depth = 0, 20
        time.sleep(2 * HB)
        router.refresh()
        out = router.infer(X, hedge_ms=60)
        assert np.allclose(out, X * 2.0)
        assert _counter('mx_fleet_hedges_total{result="lost"}') \
            == lost0 + 1
    finally:
        router.close()
        ra.close()
        rb.close()


def test_drain_on_sigterm_completes_queued_work(kv):
    # 6 requests in flight on a slow replica; the SIGTERM flag (folded
    # in by the drain poll, elastic.py's lock-free discipline) must let
    # ALL of them finish — zero shed-by-drain for accepted work — while
    # NEW work after the drain is refused.
    sa = ToyScheduler(delay=0.2)
    ra = _mk(kv, "ra", sa)
    router = _router(kv, retries=0)
    shed0 = _counter('mx_fleet_shed_total{code="drain"}')
    try:
        futs = [router.submit(X) for _ in range(6)]
        time.sleep(0.1)              # all six accepted by the replica
        ra._sigterm_flag[0] = True   # what signal.SIGTERM sets
        for f in futs:
            assert np.allclose(f.result(10), X * 2.0)
        assert sa.calls == 6
        ra.wait(timeout=5)
        assert sa.closed             # scheduler got the graceful close
        assert _counter('mx_fleet_shed_total{code="drain"}') == shed0
        router.refresh()
        with pytest.raises(OverloadError):     # fleet is empty now
            router.infer(X, deadline_ms=200)
    finally:
        router.close()
        ra.close()


def test_replica_crash_exactly_once_failover(kv):
    sa, sb = ToyScheduler(), ToyScheduler()
    ra, rb = _mk(kv, "ra", sa), _mk(kv, "rb", sb)
    router = _router(kv, retries=2)
    fo0 = _counter("mx_fleet_failovers_total")
    dup0 = _counter("mx_fleet_discarded_results_total")
    try:
        faultinject.set_fault("replica_crash", 1.0, max_fires=1)
        out = router.infer(X)
        assert np.allclose(out, X * 2.0)
        assert ra.crashed or rb.crashed
        crashed, surviving = (sa, sb) if ra.crashed else (sb, sa)
        # the request EXECUTED on the crashed replica (response lost),
        # then was resubmitted exactly once to the survivor
        assert crashed.calls == 1 and surviving.calls == 1
        assert _counter("mx_fleet_failovers_total") == fo0 + 1
        assert _counter("mx_fleet_discarded_results_total") == dup0
    finally:
        router.close()
        ra.close()
        rb.close()


def test_kv_flap_keeps_last_known_good_table(kv):
    sa, sb = ToyScheduler(), ToyScheduler()
    ra, rb = _mk(kv, "ra", sa), _mk(kv, "rb", sb)
    # slow auto-poll so the manual refresh() below owns the flap draw
    router = _router(kv, heartbeat_s=2.0)
    err0 = _counter("mx_fleet_kv_errors_total")
    try:
        faultinject.set_fault("kv_flap", 1.0, max_fires=1)
        router.refresh()             # poll fails -> degrade, not eject
        table = router.table()
        assert table["stale"]
        assert table["replicas"]["ra"]["alive"]
        assert table["replicas"]["rb"]["alive"]
        assert _counter("mx_fleet_kv_errors_total") == err0 + 1
        # routing still works off the last-known-good table
        assert np.allclose(router.infer(X), X * 2.0)
        router.refresh()             # flap budget spent -> recovery
        assert not router.table()["stale"]
    finally:
        router.close()
        ra.close()
        rb.close()


# ---------------------------------------------------------------------------
# HTTP frontend: typed wire errors, streaming, observability
# ---------------------------------------------------------------------------
class TestFrontend:
    @pytest.fixture()
    def stack(self, kv):
        sched = ToyScheduler()
        server = _mk(kv, "r0", sched)
        router = _router(kv, retries=0)
        fe = Frontend(router).serve_in_thread()
        conn = http.client.HTTPConnection(*fe.addr, timeout=10)
        yield sched, server, router, fe, conn
        conn.close()
        fe.stop()
        router.close()
        server.close()

    @staticmethod
    def _post(conn, body):
        conn.request("POST", "/v1/infer", json.dumps(body),
                     {"Content-Type": "application/json"})
        return conn.getresponse()

    def test_infer_ok(self, stack):
        _, _, _, _, conn = stack
        resp = self._post(conn, {"inputs": [X.tolist()]})
        body = json.loads(resp.read())
        assert resp.status == 200
        assert np.allclose(body["outputs"][0], (X * 2.0).tolist())
        assert body["replica"] == "r0" and body["id"]

    def test_typed_shed_codes_roundtrip_as_http(self, stack):
        sched, _, _, _, conn = stack
        for code, status, retry_after in (("overload", 429, "1"),
                                          ("drain", 503, "1"),
                                          ("timeout", 504, None)):
            sched.fail = OverloadError("shed " + code, code=code,
                                       tenant="paid")
            resp = self._post(conn, {"inputs": [X.tolist()],
                                     "tenant": "paid"})
            err = json.loads(resp.read())["error"]
            assert resp.status == status
            assert err["code"] == code           # typed, not a repr
            assert err["tenant"] == "paid"
            assert resp.getheader("Retry-After") == retry_after

    def test_untyped_error_is_500_with_structure(self, stack):
        sched, _, _, _, conn = stack
        sched.fail = RuntimeError("kernel exploded")
        resp = self._post(conn, {"inputs": [X.tolist()]})
        err = json.loads(resp.read())["error"]
        assert resp.status == 500 and err["code"] == "error"
        assert "kernel exploded" in err["message"]

    def test_bad_body_and_route(self, stack):
        _, _, _, _, conn = stack
        resp = self._post(conn, {"not_inputs": 1})
        assert resp.status == 400
        resp.read()
        conn.request("GET", "/nope")
        resp = conn.getresponse()
        assert resp.status == 404
        resp.read()

    def test_streaming_chunks(self, stack):
        _, _, _, _, conn = stack
        resp = self._post(conn, {"inputs": [X.tolist()],
                                 "stream": True})
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "application/x-ndjson"
        lines = [json.loads(ln) for ln
                 in resp.read().decode().strip().splitlines()]
        assert lines[-1] == {"done": True}
        assert np.allclose(lines[0]["outputs"][0], (X * 2.0).tolist())

    def test_health_fleet_metrics(self, stack):
        _, _, _, _, conn = stack
        conn.request("GET", "/v1/health")
        health = json.loads(conn.getresponse().read())
        assert health["ok"] and health["replicas_live"] == 1
        conn.request("GET", "/v1/fleet")
        table = json.loads(conn.getresponse().read())
        assert table["replicas"]["r0"]["alive"]
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        text = resp.read().decode()
        assert resp.getheader("Content-Type").startswith("text/plain")
        assert "mx_fleet_requests_total" in text


# ---------------------------------------------------------------------------
# the real arc: spawned replica processes, checkpoint weights, SIGKILL
# ---------------------------------------------------------------------------
def test_fleet_multiprocess_sigkill_zero_drop(tmp_path):
    import mxnet_tpu as mx
    from mxnet_tpu import model, nd
    from mxnet_tpu.gluon import nn

    prefix = str(tmp_path / "ck")
    mx.random.seed(7)
    # the replica factory's fixed prefix: this process's auto-prefix
    # counters have drifted by now, and the checkpoint must carry the
    # exact names the replica processes will look up
    net = nn.HybridSequential(prefix="fleetrep_")
    with net.name_scope():
        net.add(nn.Dense(16, in_units=8, activation="relu"),
                nn.Dense(4, in_units=16))
    net.initialize(init=mx.initializer.Xavier())
    params = {k: p.data() for k, p in net.collect_params().items()}
    model.save_checkpoint(prefix, 0, None, params, {}, sync=True)
    x = np.random.RandomState(0).randn(2, 8).astype(np.float32)
    ref = net(nd.array(x)).asnumpy()

    mgr = fleet.ReplicaManager(
        n=2, spec={"ckpt_prefix": prefix, "seed": 99, "platform": "cpu",
                   "heartbeat_s": 0.25, "miss_k": 3})
    router = None
    try:
        mgr.start(timeout=90)
        router = Router(kv=mgr.kv, heartbeat_s=0.25, miss_k=3,
                        retries=2)
        router.refresh()
        # replicas serve the PUBLISHED weights, not their local init
        assert np.allclose(router.infer(x), ref, atol=1e-5)

        results, errors = [], []

        def client():
            for _ in range(8):
                try:
                    results.append(router.submit(x).result(30))
                except Exception as e:       # pragma: no cover
                    errors.append(e)
                time.sleep(0.01)   # pace: the kill lands mid-load

        fo0 = _counter("mx_fleet_failovers_total")
        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        # kill on observed progress, not wall-clock — the load must
        # still be running when r0 dies or nothing observes the kill
        deadline = time.time() + 10.0
        while len(results) < 8 and not errors and time.time() < deadline:
            time.sleep(0.01)
        mgr.kill("r0")                       # SIGKILL mid-load
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert len(results) == 32            # zero dropped
        for out in results:
            assert np.allclose(out, ref, atol=1e-5)
        retried = (_counter("mx_fleet_failovers_total") - fo0
                   + _counter("mx_fleet_retries_total"))
        assert retried >= 1                  # the kill was observed
        # graceful SIGTERM drain of the survivor exits cleanly
        mgr.terminate("r1")
        mgr._procs["r1"].join(timeout=15)
        assert mgr._procs["r1"].exitcode == 0
    finally:
        if router is not None:
            router.close()
        mgr.stop()
