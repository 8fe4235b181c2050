"""Observability tests: span tracer, flight recorder, Chrome-trace
export over the API, watchdog-restart dumps, tracer overhead, and the
replication-lag /metrics gauges (ISSUE 2)."""

import asyncio
import json
import tempfile
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from swarmdb_tpu.api.app import ApiConfig, create_app
from swarmdb_tpu.broker.local import LocalBroker
from swarmdb_tpu.core.runtime import SwarmDB
from swarmdb_tpu.obs import TRACER, FlightRecorder, SpanTracer

CFG = ApiConfig(jwt_secret_key="test-secret", rate_limit_per_minute=10_000)


def api_drive(coro_fn, tmp_path, serving=None):
    async def runner():
        db = SwarmDB(broker=LocalBroker(), save_dir=str(tmp_path / "hist"))
        app = create_app(db, CFG, serving=serving)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await coro_fn(client, db)
        finally:
            await client.close()

    return asyncio.run(runner())


async def get_token(client, username="tester"):
    r = await client.post("/auth/token",
                          json={"username": username, "password": "pw"})
    assert r.status == 200
    return {"Authorization":
            f"Bearer {(await r.json())['access_token']}"}


# ------------------------------------------------------------------ tracer


def test_tracer_records_and_exports_chrome_trace():
    t = SpanTracer(capacity_per_thread=64, enabled=True)
    t0 = t.span_begin()
    t.span_end(t0, "work", cat="test", rid="r1", args={"k": 1})
    t.instant("mark", rid="r1")
    t.span_at("retro", time.time() - 1.0, time.time() - 0.5, rid="r1")
    trace = t.to_chrome_trace()
    json.dumps(trace)  # must be JSON-serializable
    evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in evs} == {"work", "mark", "retro"}
    for e in evs:
        assert e["dur"] >= 0 and isinstance(e["ts"], float)
        assert e["args"]["rid"] == "r1"
    # metadata events name the thread tracks
    assert any(e.get("ph") == "M" and e["name"] == "thread_name"
               for e in trace["traceEvents"])
    assert [e["name"] for e in t.events_for("r1")] \
        == ["retro", "work", "mark"]


def test_tracer_ring_overwrites_and_disabled_is_noop():
    t = SpanTracer(capacity_per_thread=16, enabled=True)
    for i in range(50):
        t.span_end(t.span_begin(), f"s{i}")
    evs = [e for e in t.to_chrome_trace()["traceEvents"]
           if e.get("ph") == "X"]
    assert len(evs) == 16  # bounded; oldest overwritten
    assert evs[-1]["name"] == "s49"
    t.set_enabled(False)
    assert t.span_begin() == 0
    t.span_end(0, "dropped")
    t.instant("dropped")
    assert len([e for e in t.to_chrome_trace()["traceEvents"]
                if e.get("ph") == "X"]) == 16


def test_tracer_span_context_manager_and_reset():
    t = SpanTracer(capacity_per_thread=32, enabled=True)
    with t.span("ctx", cat="test", rid="r9"):
        pass
    assert t.events_for("r9")
    t.reset()
    assert t.snapshot() == []


def test_trace_export_is_bounded_and_filterable():
    """ISSUE 6 satellite: /admin/trace/export must never return an
    unbounded body — last_n / trace_id filters plus a hard event cap,
    truncation declared in metadata, oldest dropped first."""
    t = SpanTracer(capacity_per_thread=256, enabled=True)
    for i in range(100):
        t.span_end(t.span_begin(), f"s{i}", rid=f"r{i % 4}")
    t.instant("ha.promoted", cat="ha")  # HA instants ride every filter

    def span_events(trace):
        return [e for e in trace["traceEvents"] if e.get("ph") == "X"]

    full = t.to_chrome_trace()
    assert len(span_events(full)) == 101
    assert full["metadata"]["truncated"] is False

    last = t.to_chrome_trace(last_n=10)
    evs = span_events(last)
    assert len(evs) == 10
    assert evs[-1]["name"] == "ha.promoted"  # newest kept
    assert last["metadata"]["truncated"] is True
    assert last["metadata"]["total_span_events"] == 101

    capped = t.to_chrome_trace(max_events=7)
    assert len(span_events(capped)) == 7
    assert capped["metadata"]["truncated"] is True

    one = t.to_chrome_trace(rid="r2")
    names = {e["name"] for e in span_events(one)}
    assert names == {f"s{i}" for i in range(100) if i % 4 == 2} | {
        "ha.promoted"}
    for e in span_events(one):
        assert (e.get("args", {}).get("rid") == "r2"
                or e.get("cat") == "ha")


def test_tracer_ring_wrap_under_concurrent_export():
    """ISSUE 6 satellite: N threads emitting spans past ring capacity
    while exports run concurrently must always yield a parseable export
    with no torn spans (the lock-free claim, exercised)."""
    t = SpanTracer(capacity_per_thread=64, enabled=True)
    stop = threading.Event()
    errors = []

    def writer(n):
        i = 0
        while not stop.is_set():
            t0 = t.span_begin()
            t.span_end(t0, f"w{n}.{i % 200}", rid=f"r{i % 8}",
                       args={"i": i})
            i += 1

    threads = [threading.Thread(target=writer, args=(n,), daemon=True)
               for n in range(4)]
    for th in threads:
        th.start()
    try:
        deadline = time.time() + 2.0
        exports = 0
        while time.time() < deadline:
            trace = t.to_chrome_trace()
            payload = json.dumps(trace)  # parseable
            parsed = json.loads(payload)
            for e in parsed["traceEvents"]:
                if e.get("ph") != "X":
                    continue
                # no torn spans: every exported event is well-formed
                if not isinstance(e["name"], str) or e["dur"] < 0:
                    errors.append(e)
            exports += 1
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=5.0)
    assert exports > 0
    assert errors == []
    # every live writer thread's ring is bounded at capacity
    final = [e for e in t.to_chrome_trace()["traceEvents"]
             if e.get("ph") == "X"]
    assert len(final) <= 4 * 64 + 64  # writers + this thread's slack


def test_tracer_retains_dead_thread_rings():
    """A short-lived thread's events (an HA promotion thread's instant)
    must survive thread churn into later exports."""
    t = SpanTracer(capacity_per_thread=32, enabled=True)

    def promote():
        t.instant("ha.promoted", cat="ha", args={"epoch": 2})

    th = threading.Thread(target=promote)
    th.start()
    th.join()
    # churn: many short-lived threads register fresh rings afterwards
    for i in range(8):
        th = threading.Thread(
            target=lambda: t.span_end(t.span_begin(), "churn"))
        th.start()
        th.join()
    names = [e["name"] for e in t.snapshot()]
    assert "ha.promoted" in names


def test_runtime_spans_cover_send_and_receive(tmp_path):
    TRACER.reset()
    db = SwarmDB(broker=LocalBroker(), save_dir=str(tmp_path / "h"))
    mid = db.send_message("a", "b", "hello")
    got = db.receive_messages("b", max_messages=1, timeout=2.0)
    assert got and got[0].id == mid
    db.close()
    names = {e["name"] for e in TRACER.snapshot()}
    assert {"runtime.send", "broker.publish", "runtime.receive",
            "stage.enqueued"} <= names
    # rid joins the hops into one timeline
    rids = {e["name"] for e in TRACER.events_for(mid)}
    assert {"runtime.send", "broker.publish", "runtime.receive"} <= rids


class _CountingLock:
    """A lock that counts the acquisitions made by one thread."""

    def __init__(self, inner, thread_id):
        self._inner, self._thread_id, self.taken = inner, thread_id, 0

    def acquire(self, *a, **kw):
        if threading.get_ident() == self._thread_id:
            self.taken += 1
        return self._inner.acquire(*a, **kw)

    def release(self):
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()


def test_tracer_overhead_smoke(tmp_path, monkeypatch):
    """What the record path does for a routed message, counted and not
    timed (two wall clocks under six workers say nothing; what tracing
    costs on the chip is PERF.md §6, PR 25): once the thread's ring is
    registered, a message costs a constant number of ring writes and
    histogram observations, and neither the tracer's nor the histogram
    registry's lock is taken (an accidental lock or an O(n) walk on the
    record path is what this catches)."""
    from swarmdb_tpu.obs import HISTOGRAMS

    db = SwarmDB(broker=LocalBroker(), save_dir=str(tmp_path / "h"),
                 autosave_interval=1e9)
    was = TRACER.enabled
    me = threading.get_ident()
    reg_lock = _CountingLock(TRACER._reg_lock, me)
    hist_lock = _CountingLock(HISTOGRAMS._lock, me)
    monkeypatch.setattr(TRACER, "_reg_lock", reg_lock)
    monkeypatch.setattr(HISTOGRAMS, "_lock", hist_lock)
    # a window close is the sentinel's work, not the record path's
    db.sentinel.config.window_s = 3600.0
    db.sentinel.set_enabled(True)

    def roundtrip():
        db.send_message("ping", "pong", "ping!")
        assert db.receive_messages("pong", max_messages=1, timeout=1.0)

    def written():
        return (TRACER._ring().idx,
                sum(h.total for h in HISTOGRAMS.all()))

    try:
        TRACER.set_enabled(True)
        HISTOGRAMS.set_enabled(True)
        db.register_agent("ping")
        db.register_agent("pong")
        for _ in range(10):
            roundtrip()  # the ring registers here, under its lock, once
        n = 200
        spans0, observed0 = written()
        reg_lock.taken = hist_lock.taken = 0
        for _ in range(n):
            roundtrip()
        taken = (reg_lock.taken, hist_lock.taken)
        spans1, observed1 = written()
    finally:
        TRACER.set_enabled(was)
        HISTOGRAMS.set_enabled(True)
        db.close()
    assert taken == (0, 0), f"locks taken on the record path: {taken}"
    spans, observed = (spans1 - spans0) / n, (observed1 - observed0) / n
    assert 1 <= spans <= 8, f"{spans} ring writes a message"
    assert 1 <= observed <= 4, f"{observed} histogram observations a message"


# --------------------------------------------------------- flight recorder


def test_flight_recorder_rings_and_dump(tmp_path):
    fr = FlightRecorder(n_steps=16, n_requests=8)
    fr.meta["model"] = "tiny"
    for i in range(40):
        fr.record_step({"i": i})
    for i in range(12):
        fr.record_request({"rid": f"r{i}"})
    assert [r["i"] for r in fr.steps()] == list(range(24, 40))
    assert [r["rid"] for r in fr.requests()] == [f"r{i}" for i in range(4, 12)]
    path = fr.dump_to(str(tmp_path), reason="test")
    data = json.loads(open(path).read())
    assert data["reason"] == "test" and data["meta"]["model"] == "tiny"
    assert len(data["steps"]) == 16
    assert fr.last_dump_path == path
    # auto_dump never raises, even on an unwritable directory
    assert fr.auto_dump("boom", "/proc/definitely/not/writable") is None
    assert fr.last_dump["reason"] == "boom"


def test_flight_concurrent_dumps_both_land(tmp_path):
    """ISSUE 6 satellite: dumps used to be named by millisecond stamp
    alone, so two near-simultaneous dumpers (watchdog restart + HA
    promotion) could overwrite each other. Node id + a monotonic
    sequence in the filename make every dump land."""
    a = FlightRecorder(n_steps=8)
    a.meta["node_id"] = "node-a"
    b = FlightRecorder(n_steps=8)
    b.meta["node_id"] = "node-a"  # same identity, same instant: worst case
    a.record_step({"i": 1})
    b.record_step({"i": 2})
    barrier = threading.Barrier(2)
    paths = [None, None]

    def dump(idx, fr):
        barrier.wait()
        paths[idx] = fr.dump_to(str(tmp_path), reason="race")

    threads = [threading.Thread(target=dump, args=(0, a)),
               threading.Thread(target=dump, args=(1, b))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert all(paths) and paths[0] != paths[1]
    dumps = sorted(tmp_path.glob("flight_*_race.json"))
    assert len(dumps) == 2, [p.name for p in dumps]
    for p in dumps:
        assert "node-a" in p.name
        assert json.loads(p.read_text())["reason"] == "race"


# ------------------------------------------------- end-to-end acceptance


@pytest.fixture(scope="module")
def serving():
    from swarmdb_tpu.backend.service import ServingService

    tmp = tempfile.mkdtemp()
    db = SwarmDB(broker=LocalBroker(), save_dir=tmp)
    svc = ServingService.from_model_name(
        db, "tiny-debug", backend_id="tpu-0",
        max_batch=2, max_seq=64, decode_chunk=2)
    svc.start()
    yield svc
    svc.stop()
    db.close()


def test_trace_export_covers_full_request_path(tmp_path, serving):
    """Acceptance: GET /admin/trace/export returns valid Chrome
    trace-event JSON with spans for the API route, runtime send/receive,
    broker publish, engine admission, prefill, and >= 2 decode chunks of
    a streamed request."""
    TRACER.reset()

    async def drive(client, db):
        hdrs = await get_token(client, "alice")
        admin = await get_token(client, "admin")
        # non-admin may not export
        r = await client.get("/admin/trace/export", headers=hdrs)
        assert r.status == 403
        # streamed request through the API route (decode_chunk=2,
        # 8 new tokens => >= 3 decode chunks)
        r = await client.post("/messages", json={
            "receiver_id": "assistant", "content": "tell me things",
            "stream": True,
            "metadata": {"generation": {"max_new_tokens": 8,
                                        "temperature": 0.0}},
        }, headers=hdrs)
        assert r.status == 200
        body = await r.text()
        first = next(l for l in body.splitlines()
                     if l.startswith("data: ") and '"id"' in l)
        msg_id = json.loads(first[len("data: "):])["id"]
        # the assistant drains its inbox over the API (runtime.receive)
        a_hdrs = await get_token(client, "assistant")
        r = await client.post("/agents/receive",
                              json={"max_messages": 4, "timeout": 2.0},
                              headers=a_hdrs)
        assert r.status == 200

        r = await client.get("/admin/trace/export", headers=admin)
        assert r.status == 200
        trace = await r.json()
        events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        names = {e["name"] for e in events}
        assert {"api.request", "runtime.send", "runtime.receive",
                "broker.publish", "engine.admit",
                "engine.prefill"} <= names, names
        # join: message id -> engine request id via the serve span
        serve_spans = [e for e in events if e["name"] == "serve.request"
                       and e.get("args", {}).get("rid") == msg_id]
        assert serve_spans, "no serve.request span for the streamed msg"
        erid = serve_spans[0]["args"]["engine_rid"]
        chunks = [e for e in events if e["name"] == "engine.decode_chunk"
                  and e.get("args", {}).get("rid") == erid]
        assert len(chunks) >= 2, f"only {len(chunks)} decode-chunk spans"
        for e in events:
            assert e["dur"] >= 0
        # the API route span covers the whole streamed response
        api_spans = [e for e in events if e["name"] == "api.request"
                     and e["args"]["path"] == "/messages"]
        assert api_spans and api_spans[0]["args"]["status"] == 200

    api_drive(drive, tmp_path, serving=serving)


def test_flight_endpoint_and_watchdog_restart_dump(tmp_path, serving):
    """Acceptance: killing the decode loop (watchdog restart path)
    produces a flight-record dump whose last engine-step records match
    the metrics counters; GET /admin/flight serves the rings."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    eng = serving.engine
    db = serving.db
    # generate some work so the rings hold steps/requests
    toks, reason = eng.generate_sync([1, 5, 9],
                                     SamplingParams(max_new_tokens=6),
                                     timeout=120)
    assert reason in ("length", "eos")
    deadline = time.time() + 10
    while time.time() < deadline and not eng.flight.steps():
        time.sleep(0.05)
    # let the trailing "settled" step record (idle iteration after work)
    time.sleep(0.7)

    async def drive(client, _db):
        admin = await get_token(client, "admin")
        r = await client.get("/admin/flight", headers=admin)
        assert r.status == 200
        dump = await r.json()
        assert dump["steps"] and dump["reason"] == "on_demand"
        assert dump["meta"]["model"] == "tiny-debug"
        last = dump["steps"][-1]
        for key in ("active", "queued_by_priority", "in_flight_chunks",
                    "prefill_padding_tokens", "host_syncs",
                    "compiled_variants", "tokens_generated"):
            assert key in last, f"step record missing {key}"
        assert dump["requests"][-1]["reason"] in ("length", "eos")

    api_drive(drive, tmp_path, serving=serving)

    # ---- watchdog restart dump
    with eng._cv:
        eng._stop = True
        eng._cv.notify_all()
    eng._thread.join(timeout=10)
    assert not eng.alive()
    deadline = time.time() + 30
    while not eng.alive() and time.time() < deadline:
        time.sleep(0.05)
    assert eng.alive(), "watchdog did not restart the engine"
    dump = eng.flight.last_dump
    assert dump is not None and dump["reason"] == "engine_restart"
    # the dump was also written under the service's flight dir
    assert dump["steps"], "restart dump carries no step records"
    assert eng.flight.last_dump_path and \
        json.loads(open(eng.flight.last_dump_path).read())["reason"] \
        == "engine_restart"
    # last step records match the metrics counters (the loop is dead, so
    # nothing advanced the engine-thread counters after that step)
    last = dump["steps"][-1]
    c = db.metrics.counters
    assert last["tokens_generated"] == c["tokens_generated"].value
    assert last["host_syncs"] == c["engine_host_syncs"].value
    assert last["prompt_tokens"] == c["prompt_tokens"].value


# -------------------------------------------------- replication lag gauges


def test_metrics_exports_replica_lag(tmp_path):
    async def drive(client, db):
        db.broker.replication_stats = lambda: [
            {"target": "10.0.0.7:9444", "lag_records": 7,
             "lag_seconds": 1.25, "connected": False, "gapped": 1},
        ]
        r = await client.get("/metrics")
        assert r.status == 200
        text = await r.text()
        assert ('swarmdb_replica_lag_records{follower="10.0.0.7:9444"} 7'
                in text)
        assert ('swarmdb_replica_lag_seconds{follower="10.0.0.7:9444"} '
                '1.25' in text)
        assert ('swarmdb_replica_connected{follower="10.0.0.7:9444"} 0'
                in text)
        assert ('swarmdb_replica_gapped_partitions'
                '{follower="10.0.0.7:9444"} 1' in text)

    api_drive(drive, tmp_path)


def test_metrics_without_replication_has_no_replica_gauges(tmp_path):
    async def drive(client, db):
        r = await client.get("/metrics")
        assert r.status == 200
        assert "swarmdb_replica_" not in await r.text()

    api_drive(drive, tmp_path)


# ---------------------------------------------------- latency histograms


# the ladders are the wire contract — recording rules key on `le` values
EXPECTED_HISTOGRAMS = {
    "swarmdb_ttft_seconds": "0.001",
    "swarmdb_queue_wait_seconds": "0.001",
    "swarmdb_decode_chunk_seconds": "0.0001",
    "swarmdb_dataplane_rtt_seconds": "0.0001",
    "swarmdb_replication_commit_seconds": "0.001",
    "swarmdb_broker_publish_seconds": "0.0001",
}


def test_histogram_observe_and_prometheus_rendering():
    from swarmdb_tpu.obs.metrics import Histogram

    h = Histogram("unit_seconds", (0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    lines = h.render_prometheus()
    assert lines[0] == "# TYPE swarmdb_unit_seconds histogram"
    assert 'swarmdb_unit_seconds_bucket{le="0.01"} 1' in lines
    assert 'swarmdb_unit_seconds_bucket{le="0.1"} 3' in lines  # cumulative
    assert 'swarmdb_unit_seconds_bucket{le="1"} 4' in lines
    assert 'swarmdb_unit_seconds_bucket{le="+Inf"} 5' in lines
    assert "swarmdb_unit_seconds_count 5" in lines
    # boundary membership: an observation exactly on a bound lands in
    # that bound's bucket (Prometheus `le` semantics)
    h2 = Histogram("edge_seconds", (0.1, 1.0))
    h2.observe(0.1)
    assert h2.counts[0] == 1
    # disabled recording is a no-op
    h2.enabled = False
    h2.observe(0.2)
    assert sum(h2.counts) == 1


def test_metrics_exports_histograms(tmp_path):
    """ISSUE 6 acceptance: /metrics exposes >= 4 Prometheus histograms
    with stable bucket boundaries, and a recorded observation shows up
    in the cumulative buckets."""
    from swarmdb_tpu.obs.metrics import HIST_TTFT

    HIST_TTFT.observe(0.021)

    async def drive(client, db):
        # the echo path itself feeds broker_publish_seconds
        db.send_message("a", "b", "hello")
        r = await client.get("/metrics")
        assert r.status == 200
        text = await r.text()
        histogram_families = {
            line.split()[2] for line in text.splitlines()
            if line.startswith("# TYPE") and line.endswith("histogram")}
        assert len(histogram_families) >= 4, histogram_families
        for family, first_bucket in EXPECTED_HISTOGRAMS.items():
            assert family in histogram_families, family
            assert f'{family}_bucket{{le="{first_bucket}"}}' in text, family
            assert f'{family}_bucket{{le="+Inf"}}' in text
            assert f"{family}_count" in text
        # the TTFT observation above landed at le=0.025 and is cumulative
        ttft_lines = [l for l in text.splitlines()
                      if l.startswith("swarmdb_ttft_seconds_bucket")]
        inf = int(ttft_lines[-1].rsplit(" ", 1)[1])
        assert inf >= 1
        # the publish histogram observed this request's send
        pub = [l for l in text.splitlines()
               if l.startswith("swarmdb_broker_publish_seconds_count")]
        assert pub and int(pub[0].rsplit(" ", 1)[1]) >= 1

    api_drive(drive, tmp_path)
