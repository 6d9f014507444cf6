"""Distributed request tracing (tracing.py + serve wiring, ISSUE 18):
context header round-trips through the HTTP edge, edge-once sampling
(a replica never re-flips the decision), retry/hedge attempts sharing
one trace id with distinct span ids, byte-clean wire frames when
tracing is off or the request unsampled, the bounded span ring with
counted drops, cross-process assembly + critical-path explain, the
fleet-aggregated /metrics scrape that degrades (never 500s) during a
KV flap, the heartbeat trace section, and the lease payload-fn
failure fallback that keeps liveness renewing.
"""
import http.client
import json
import os
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import dist, faultinject, telemetry, tracing
from mxnet_tpu.serve import fleet
from mxnet_tpu.serve.fleet import ReplicaServer, Router
from mxnet_tpu.serve.frontend import Frontend

pytestmark = [pytest.mark.serve, pytest.mark.obs]

HB = 0.05
MISS_K = 3
X = np.arange(8, dtype=np.float32).reshape(2, 4)


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
    def __init__(self, delay=0.0, scale=2.0):
        self.delay, self.scale = delay, scale
        self.calls = 0

    def submit(self, *arrays, tenant="default"):
        self.calls += 1
        return ToyFuture(arrays[0] * self.scale, self.delay)

    def stats(self):
        return {"queue_depth": 0, "inflight": 0}

    def close(self, drain=None):
        pass


@pytest.fixture()
def kv():
    return dist.KV(dist.LocalKV())


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("MXNET_TRACE", raising=False)
    monkeypatch.delenv("MXNET_TRACE_SAMPLE", raising=False)
    faultinject.clear()
    tracing.refresh()
    tracing.reset()
    telemetry.reset()
    yield
    faultinject.clear()
    tracing.refresh()
    tracing.reset()
    telemetry.refresh()
    telemetry.reset()


@pytest.fixture()
def traced():
    tracing.enable(True, sample=1.0)
    yield
    tracing.enable(False)


def _mk(kv, rid, sched, **kw):
    return ReplicaServer(sched, rid, kv=kv, heartbeat_s=HB,
                         miss_k=MISS_K, **kw)


def _router(kv, **kw):
    kw.setdefault("heartbeat_s", HB)
    kw.setdefault("miss_k", MISS_K)
    r = Router(kv=kv, **kw)
    r.refresh()
    return r


def _wait_trace(router, ident, timeout=5.0):
    t_dead = time.time() + timeout
    while time.time() < t_dead:
        t = router.trace(ident)
        if t is not None and t["complete"]:
            return t
        time.sleep(0.02)
    raise AssertionError("trace for %r never assembled" % ident)


# ---------------------------------------------------------------------------
# context plumbing: mint / header / wire, edge-once sampling
# ---------------------------------------------------------------------------
class TestContext:
    def test_header_roundtrip(self, traced):
        ctx = tracing.mint(deadline=123.0)
        assert ctx.sampled
        back = tracing.from_header(ctx.to_header(), deadline=123.0)
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id
        assert back.sampled and back.deadline == 123.0
        child = ctx.child()
        assert child.trace_id == ctx.trace_id
        assert child.span_id != ctx.span_id

    def test_malformed_header_yields_none(self, traced):
        for bad in ("", "nodash", "a-", "-b-1", None):
            assert tracing.from_header(bad) is None

    def test_sampling_decided_once_at_edge(self):
        tracing.enable(True, sample=0.0)
        try:
            # rate 0: minted contexts exist but are UNSAMPLED
            assert not tracing.mint().sampled
            # the caller's decision is respected both ways
            assert tracing.from_header("aa-bb-1").sampled
            assert not tracing.from_header("aa-bb-0").sampled
            # only sampled contexts ever ride the wire, so a replica
            # rebinding from_wire can never re-flip the decision
            assert tracing.from_wire({"tid": "aa", "sid": "bb"}).sampled
            assert tracing.from_wire(None) is None
        finally:
            tracing.enable(False)

    def test_off_path_is_noop(self):
        assert not tracing.active()
        assert tracing.mint() is None
        assert tracing.from_header("aa-bb-1") is None
        assert tracing.record_span("x", "fleet", 0.0, 1.0) is None


# ---------------------------------------------------------------------------
# span ring: bounded, drops counted, never silent
# ---------------------------------------------------------------------------
def test_ring_bound_holds_with_counted_drops(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE", "1")
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "1.0")
    monkeypatch.setenv("MXNET_TRACE_RING", "8")
    tracing.refresh()
    tracing.reset()
    ctx = tracing.mint()
    for i in range(50):
        tracing.record_span("s%d" % i, "replica", 0.0, 0.001, ctx=ctx)
    st = tracing.stats()
    assert st["buffered"] <= 8
    assert st["dropped"] == 50 - st["buffered"]
    assert st["recorded"] == 50
    # drained spans are the NEWEST (oldest evicted first)
    spans = tracing.publish_drain(64)
    assert len(spans) == st["buffered"]
    assert spans[-1]["name"] == "s49"


def test_sustained_load_keeps_ring_bounded(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE", "1")
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "1.0")
    monkeypatch.setenv("MXNET_TRACE_RING", "32")
    tracing.refresh()
    tracing.reset()
    stop = threading.Event()

    def writer():
        ctx = tracing.mint()
        while not stop.is_set():
            tracing.record_span("w", "replica", 0.0, 0.001, ctx=ctx)

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for t in threads:
        t.start()
    t_dead = time.time() + 0.3
    while time.time() < t_dead:
        assert tracing.stats()["buffered"] <= 32
        time.sleep(0.01)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    st = tracing.stats()
    assert st["buffered"] <= 32 and st["dropped"] > 0


# ---------------------------------------------------------------------------
# clock skew + critical path
# ---------------------------------------------------------------------------
def test_clock_skew_correction():
    # replica clock 10s ahead; 40ms RTT, 30ms server time
    skew = tracing.clock_skew(t_send=100.000, t_recv=100.040,
                              tr_in=110.005, tr_out=110.035)
    assert abs(skew - 10.0) < 1e-6


def test_critical_path_phases_and_dominant():
    spans = [
        {"cat": "fleet", "dur": 100e3, "args": {}},
        {"cat": "attempt", "dur": 40e3,
         "args": {"outcome": "conn", "error": "boom"}},
        {"cat": "attempt", "dur": 50e3, "args": {"outcome": "ok"}},
        {"cat": "attempt", "dur": 45e3, "args": {"outcome":
                                                 "superseded"}},
        {"cat": "assembly", "dur": 5e3, "args": {}},
        {"cat": "sched", "dur": 10e3, "args": {}},
        {"cat": "engine", "dur": 30e3, "args": {}},
        # nested inside the engine span: must NOT double-count
        {"cat": "serve", "dur": 29e3, "args": {}},
        {"cat": "wire", "dur": 2e3, "args": {}},
        {"cat": "hedge", "dur": 8e3, "args": {}},
    ]
    bd = tracing.critical_path(spans)
    phases = dict(bd["phases"])
    assert bd["total_us"] == 100e3
    assert phases["retry"] == 40e3          # failed attempt only
    assert phases["queue"] == 5e3
    assert phases["batch"] == 10e3
    assert phases["execute"] == 30e3        # serve span not added
    assert phases["wire"] == 2e3
    assert phases["hedge_wait"] == 8e3
    assert bd["dominant"] == "retry"
    text = tracing.render_critical_path(bd, "abcd")
    assert "abcd" in text and "retry" in text and "%" in text


def test_store_ingest_applies_skew_and_dedups():
    store = tracing.TraceStore(cap=4, exemplars=2)
    span = {"name": "replica::handle", "cat": "replica", "ts": 50e6,
            "dur": 1e3, "tid": "t1", "sid": "s1", "psid": "p1",
            "args": {}}
    store.ingest([dict(span)], replica="r0", skew_s=10.0)
    store.ingest([dict(span)], replica="r0", skew_s=10.0)  # dup (sid)
    got = store.get("t1")["spans"]
    assert len(got) == 1
    assert got[0]["replica"] == "r0"
    assert abs(got[0]["ts"] - 40e6) < 1.0   # replica clock unskewed


# ---------------------------------------------------------------------------
# wire contract: off/unsampled requests are byte-clean
# ---------------------------------------------------------------------------
def _spy_frames(monkeypatch):
    sent = []
    real = fleet._send_frame

    def spy(conn, header, arrays=()):
        sent.append(json.loads(json.dumps(header)))
        return real(conn, header, arrays)

    monkeypatch.setattr(fleet, "_send_frame", spy)
    return sent


def test_wire_frames_identical_when_off(kv, monkeypatch):
    """With tracing off, frames must match the pre-tracing protocol: a
    stripped twin (tracing.active bypassed entirely) produces headers
    with the exact same key sets, and no trace/spans/tr key ever
    appears."""
    sent = _spy_frames(monkeypatch)
    server = _mk(kv, "r0", ToyScheduler())
    router = _router(kv, retries=0)
    try:
        assert not tracing.active()
        router.infer(X)
        off_keys = [tuple(sorted(h)) for h in sent]
        del sent[:]
        monkeypatch.setattr(tracing, "active", lambda: False)
        router.infer(X)
        stripped_keys = [tuple(sorted(h)) for h in sent]
        assert off_keys == stripped_keys
        for keys in off_keys:
            assert "trace" not in keys
            assert "spans" not in keys and "tr" not in keys
    finally:
        router.close()
        server.close()


def test_unsampled_request_carries_zero_span_bytes(kv, monkeypatch):
    sent = _spy_frames(monkeypatch)
    tracing.enable(True, sample=0.0)    # tracing ON, nothing sampled
    server = _mk(kv, "r0", ToyScheduler())
    router = _router(kv, retries=0)
    try:
        router.infer(X)
        assert sent
        for h in sent:
            assert "trace" not in h
            assert "spans" not in h and "tr" not in h
    finally:
        tracing.enable(False)
        router.close()
        server.close()


def test_sampled_request_piggybacks_spans(kv, monkeypatch, traced):
    sent = _spy_frames(monkeypatch)
    server = _mk(kv, "r0", ToyScheduler())
    router = _router(kv, retries=0)
    try:
        router.infer(X)
        reqs = [h for h in sent if h.get("op") == "infer"]
        oks = [h for h in sent if h.get("ok") is True]
        assert reqs and "trace" in reqs[0]
        assert oks and oks[0].get("spans") and len(oks[0]["tr"]) == 2
    finally:
        router.close()
        server.close()


# ---------------------------------------------------------------------------
# assembly: retries and hedges share one trace, explain() names phases
# ---------------------------------------------------------------------------
def test_failover_attempts_share_trace_distinct_spans(kv, traced):
    ra = _mk(kv, "ra", ToyScheduler())
    rb = _mk(kv, "rb", ToyScheduler())
    router = _router(kv, retries=2)
    try:
        faultinject.set_fault("replica_crash", 1.0, max_fires=1)
        fut = router.submit(X)
        assert np.allclose(fut.result(30), X * 2.0)
        trace = _wait_trace(router, fut.id)
        spans = trace["spans"]
        atts = [s for s in spans if s["cat"] == "attempt"]
        assert len(atts) == 2
        assert {s["tid"] for s in spans} == {trace["trace_id"]}
        assert len({s["sid"] for s in atts}) == 2
        failed = [s for s in atts if s["args"]["outcome"] != "ok"]
        assert len(failed) == 1
        assert failed[0]["args"]["replica"] in ("ra", "rb")
        assert failed[0]["args"]["error"]
        bd = router.explain(fut.id)
        assert bd["trace_id"] == trace["trace_id"]
        assert "retry" in dict(bd["phases"])
        assert bd["dominant"] != "none"
    finally:
        router.close()
        ra.close()
        rb.close()


def test_hedge_attempts_share_trace(kv, traced):
    # slow primary guarantees the hedge launches and WINS; the loser
    # must surface as a superseded attempt span in the same trace
    ra = _mk(kv, "ra", ToyScheduler(delay=0.4))
    rb = _mk(kv, "rb", ToyScheduler(delay=0.4))
    router = _router(kv, retries=0)
    try:
        router.infer(X, hedge_ms=0)          # warm conn pools untimed
        fut = router.submit(X, hedge_ms=30)
        assert np.allclose(fut.result(30), X * 2.0)
        t_dead = time.time() + 10
        while time.time() < t_dead:
            trace = router.trace(fut.id)
            atts = [s for s in (trace["spans"] if trace else ())
                    if s["cat"] == "attempt"]
            if trace and trace["complete"] and len(atts) == 2:
                break
            time.sleep(0.02)
        kinds = sorted(s["args"]["kind"] for s in atts)
        assert kinds == ["hedge", "primary"]
        outcomes = {s["args"]["kind"]: s["args"]["outcome"]
                    for s in atts}
        assert sorted(outcomes.values()) == ["ok", "superseded"]
        assert len({s["args"]["replica"] for s in atts}) == 2
        hedge_spans = [s for s in trace["spans"]
                       if s["cat"] == "hedge"]
        assert hedge_spans and hedge_spans[0]["name"] == "hedge::wait"
    finally:
        router.close()
        ra.close()
        rb.close()


def test_pull_path_ingests_spans_from_health_lease(kv, traced):
    """Spans stranded replica-side (no reply to piggyback on) must
    still reach the router via the health-lease payload."""
    server = _mk(kv, "r0", ToyScheduler())
    router = _router(kv, retries=0)
    try:
        ctx = tracing.mint()
        # a replica-side span recorded OUTSIDE any wire request
        tracing.record_span("orphan::work", "replica", time.time(),
                            time.time() + 0.001, ctx=ctx)
        t_dead = time.time() + 5
        while time.time() < t_dead:
            t = router.trace(ctx.trace_id)
            if t is not None:
                break
            time.sleep(0.05)
        assert t is not None
        assert t["spans"][0]["name"] == "orphan::work"
        assert t["spans"][0]["replica"] == "r0"
    finally:
        router.close()
        server.close()


def test_real_scheduler_emits_queue_batch_execute_spans(traced):
    """The replica-side span set on a REAL continuous-batching
    scheduler: disjoint sched::queue (submit->admit), sched::batch
    (assembly) and engine::serve.batch (execute) windows, plus the
    session's serve::forward detail, all tagged with the ambient
    trace."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, serve
    from mxnet_tpu.gluon import nn

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=16))
    net.initialize(init=mx.initializer.Xavier())
    sess = net.serve_session(nd.ones((2, 16)), max_batch=4)
    sched = serve.Scheduler(sess, max_wait_ms=0, inflight=2)
    try:
        x = np.ones((2, 16), dtype=np.float32)
        sched.submit(x).result(30)          # warm: compile untraced
        ctx = tracing.mint()
        with tracing.bind(ctx):
            sched.submit(x).result(30)
        t_dead = time.time() + 5
        while time.time() < t_dead:
            spans = tracing.take_for(ctx.trace_id)
            if spans:
                break
            time.sleep(0.02)
        by_cat = {}
        for s in spans:
            by_cat.setdefault(s["cat"], []).append(s)
        assert set(by_cat) >= {"assembly", "sched", "engine", "serve"}
        q = by_cat["assembly"][0]
        b = by_cat["sched"][0]
        e = by_cat["engine"][0]
        # disjoint windows: queue ends where batch starts, batch ends
        # where execute starts (no double-counted critical-path time)
        assert q["ts"] + q["dur"] <= b["ts"] + 1.0
        assert b["ts"] + b["dur"] <= e["ts"] + 1.0
        assert all(s["tid"] == ctx.trace_id for s in spans)
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# HTTP edge: header echo, /v1/trace, aggregated /metrics never 500s
# ---------------------------------------------------------------------------
class TestFrontendTracing:
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
    def _post(conn, body, headers=None):
        hdrs = {"Content-Type": "application/json"}
        hdrs.update(headers or {})
        conn.request("POST", "/v1/infer", json.dumps(body), hdrs)
        return conn.getresponse()

    def test_inbound_header_honored_and_echoed(self, stack, traced):
        _, _, router, _, conn = stack
        resp = self._post(conn, {"inputs": [X.tolist()]},
                          {"x-mxnet-trace": "feedc0de" * 2
                           + "-12345678-1"})
        body = json.loads(resp.read())
        assert resp.status == 200
        assert body["trace_id"] == "feedc0de" * 2
        echo = resp.getheader("x-mxnet-trace")
        assert echo.startswith("feedc0de" * 2 + "-")
        assert echo.endswith("-1")
        _wait_trace(router, body["trace_id"])

    def test_edge_mints_when_no_header(self, stack, traced):
        _, _, router, _, conn = stack
        resp = self._post(conn, {"inputs": [X.tolist()]})
        body = json.loads(resp.read())
        assert body["trace_id"]
        assert resp.getheader("x-mxnet-trace").startswith(
            body["trace_id"] + "-")
        trace = _wait_trace(router, body["trace_id"])
        roots = [s for s in trace["spans"] if s["cat"] == "fleet"]
        assert roots and roots[0]["args"]["outcome"] == "ok"

    def test_unsampled_inbound_stays_unsampled(self, stack, traced):
        # the caller said "-0": the replica/router must NOT re-flip it
        _, _, router, _, conn = stack
        resp = self._post(conn, {"inputs": [X.tolist()]},
                          {"x-mxnet-trace": "aa-bb-0"})
        body = json.loads(resp.read())
        assert resp.status == 200 and "trace_id" not in body
        assert resp.getheader("x-mxnet-trace") == "aa-bb-0"
        assert router.trace("aa") is None

    def test_trace_endpoint_and_404(self, stack, traced):
        _, _, router, _, conn = stack
        resp = self._post(conn, {"inputs": [X.tolist()]})
        tid = json.loads(resp.read())["trace_id"]
        _wait_trace(router, tid)
        conn.request("GET", "/v1/trace/" + tid)
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        assert resp.status == 200
        assert doc["trace_id"] == tid and doc["complete"]
        assert doc["spans"] and doc["critical_path"]["dominant"]
        conn.request("GET", "/v1/trace/unknown123")
        resp = conn.getresponse()
        assert resp.status == 404
        resp.read()

    def test_metrics_aggregates_replica_series(self, stack):
        telemetry.enable(True)
        _, _, _, _, conn = stack
        t_dead = time.time() + 5
        while time.time() < t_dead:
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            text = resp.read().decode()
            assert resp.status == 200
            if 'replica="r0"' in text:
                return
            time.sleep(0.1)
        raise AssertionError("no replica-labeled series in /metrics")

    def test_metrics_never_500s_during_kv_flap(self, stack,
                                               monkeypatch):
        """The satellite bugfix regression: a scrape while the fleet
        KV flaps (and replica aggregation is broken) must degrade to
        router-local series with mx_fleet_routing_stale=1 — not raise
        a 500."""
        telemetry.enable(True)
        _, _, router, _, conn = stack

        def boom(r):
            raise ConnectionError("aggregation broke mid-flap")

        monkeypatch.setattr(fleet, "render_replica_metrics", boom)
        faultinject.set_fault("kv_flap", 1.0, max_fires=1)
        router.refresh()                 # the poll eats the flap
        assert router.table()["stale"]
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        text = resp.read().decode()
        assert resp.status == 200
        assert "mx_fleet_routing_stale 1" in text


# ---------------------------------------------------------------------------
# telemetry integration: heartbeat, exemplars, crash bundle, lease
# ---------------------------------------------------------------------------
def test_heartbeat_gains_trace_section(traced):
    ctx = tracing.mint()
    tracing.record_span("x", "replica", 0.0, 0.001, ctx=ctx)
    line = telemetry.heartbeat_line()
    assert " trace=" in line
    assert "sampled:" in line and "dropped:" in line


def test_heartbeat_trace_section_absent_when_idle():
    assert " trace=" not in telemetry.heartbeat_line()


def test_exemplars_retained_and_in_crash_bundle(tmp_path, traced):
    store = tracing.TraceStore(cap=8, exemplars=2)
    for i, dur in enumerate((5e3, 50e3, 1e3, 20e3)):
        tid = "t%d" % i
        root = {"name": "fleet::request", "cat": "fleet", "ts": 0.0,
                "dur": dur, "tid": tid, "sid": "s%d" % i,
                "psid": None, "args": {"outcome": "ok"}}
        store.add(dict(root))
        store.finish(tid, "req%d" % i, root)
    ex = store.exemplars()
    assert [e["trace_id"] for e in ex] == ["t1", "t3"]  # worst first
    path = telemetry.crash_bundle(reason="test",
                                  dirpath=str(tmp_path))
    with open(os.path.join(path, "traces.json")) as f:
        doc = json.load(f)
    assert doc["stats"]["sampled"] >= 0
    tids = [e["trace_id"] for e in doc["exemplars"]]
    assert "t1" in tids


def test_lease_payload_fn_failure_republishes_last(kv):
    calls = {"n": 0}

    def payload_fn():
        calls["n"] += 1
        if calls["n"] > 1:
            raise RuntimeError("health field exploded")
        return {"good": True}

    lease = dist.Lease(kv, "mx/test/lease", ttl_s=0.3,
                       payload_fn=payload_fn, period_s=0.05).start()
    try:
        time.sleep(0.25)                 # several failing renewals
        rec = json.loads(kv.try_get("mx/test/lease"))
        assert rec["p"] == {"good": True}
        assert lease.errors >= 1
        # liveness kept renewing: the lease stamp is still fresh
        assert time.time() - rec["t"] <= 0.3
    finally:
        lease.stop(drop=True)
