"""The service's hops as spans by message id (PR 57): ``serve.pickup``
(consumer thread: the ``enqueued`` stamp -> handed to ``serve_message``),
``serve.request`` (its history read and its submit apart) and
``serve.reply`` (reply thread: the wait in the reply queue, the decode,
the send), and the consumer's three duty counters. Tiny model on the CPU;
nothing here reads a time as a performance number."""

import asyncio
import threading
import time

import pytest

from swarmdb_tpu.backend.service import ServingService
from swarmdb_tpu.broker.local import LocalBroker
from swarmdb_tpu.core.runtime import SwarmDB
from swarmdb_tpu.obs import TRACER

HOPS = ("serve.pickup", "serve.request", "serve.reply")
COUNTERS = ("serve_poll_rounds", "serve_poll_rounds_idle",
            "serve_poll_sleep_us")
GEN = {"generation": {"max_new_tokens": 4}}


def build(tmp, backend_id):
    db = SwarmDB(broker=LocalBroker(), save_dir=str(tmp))
    svc = ServingService.from_model_name(db, "tiny-debug", max_batch=4,
                                         backend_id=backend_id, max_seq=128)
    return db, svc


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    was = TRACER.enabled
    TRACER.set_enabled(True)
    db, svc = build(tmp_path_factory.mktemp("hops"), "tpu-0")
    svc.start()
    for bot in ("bot", "bot2"):
        db.register_agent(bot)
        db.assign_llm_backend(bot, "tpu-0")
    yield db, svc
    svc.stop()
    db.close()
    TRACER.set_enabled(was)


def wait_for(cond, timeout=90.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def serving_spans(mid):
    out = {}
    for e in TRACER.events_for(mid):
        if e["cat"] == "serving":
            out.setdefault(e["name"], []).append(e)
    return out


def hops(db, mid):
    """The message's serving spans once its reply is out and the reply
    thread has closed its span."""
    assert wait_for(lambda: "reply_id" in db.get_message(mid).metadata)
    assert wait_for(lambda: "serve.reply" in serving_spans(mid), 5.0)
    return serving_spans(mid)


def end_s(e):
    return e["start_s"] + e["dur_us"] * 1e-6


def counters(db):
    c = db.metrics.snapshot()["counters"]
    return {k: c.get(k) for k in COUNTERS}


def after_a_wake(db, svc, poll_interval):
    """Return just after the consumer began a sleep of ``poll_interval``:
    the idle counter moves when a sleep ends, and the round that follows
    an empty inbox takes a fraction of a millisecond."""
    svc.poll_interval = poll_interval
    for _ in range(2):      # the first may still be a sleep of the old one
        seen = counters(db)["serve_poll_rounds_idle"]
        assert wait_for(lambda: counters(db)["serve_poll_rounds_idle"]
                        > seen, 5.0)
    time.sleep(0.02)


@pytest.fixture()
def slow_poll(served):
    db, svc = served
    was = svc.poll_interval
    yield lambda s: after_a_wake(db, svc, s)
    svc.poll_interval = was


# ------------------------------------------- a message the consumer took up

@pytest.fixture(scope="module")
def consumed(served):
    db, _svc = served
    mid = db.send_message("user", "bot", "hello bot", metadata=GEN)
    return db, mid, hops(db, mid)


def test_a_consumed_message_leaves_one_of_each_hop_and_they_tile(consumed):
    _db, _mid, spans = consumed
    assert {k: len(v) for k, v in spans.items()} == {k: 1 for k in HOPS}
    pick, req, rep = (spans[k][0] for k in HOPS)
    # serve.pickup ends where serve.request begins
    assert abs(req["start_s"] - end_s(pick)) < 1e-3
    assert 0 <= pick["args"]["slept_us"] <= pick["dur_us"]
    # the reply's hop lies behind the request's, on the reply thread
    assert rep["start_s"] >= end_s(req)
    assert pick["thread"] == req["thread"] == "tpu-backend-tpu-0"
    assert rep["thread"] == "tpu-replies-tpu-0"


@pytest.mark.parametrize("name, keys", [
    ("serve.pickup", {"slept_us", "behind", "agents"}),
    ("serve.request", {"engine_rid", "prompt_tokens", "build_us",
                       "submit_us"}),
    ("serve.reply", {"queued_us", "decode_us", "send_us", "tokens",
                     "attempts"}),
])
def test_each_hop_carries_its_arguments(consumed, name, keys):
    db, mid, spans = consumed
    e, = spans[name]
    assert e["rid"] == mid and set(e["args"]) == keys
    a = e["args"]
    if name == "serve.pickup":
        assert a["behind"] == 0 and a["agents"] == 2
    elif name == "serve.request":
        assert a["prompt_tokens"] > 0
        assert 0 <= a["build_us"] + a["submit_us"] <= e["dur_us"] + 1
    else:
        reply = db.get_message(db.get_message(mid).metadata["reply_id"])
        assert a["tokens"] == reply.metadata["completion_tokens"]
        assert a["attempts"] == 1
        assert a["queued_us"] >= 0
        assert 0 < a["decode_us"] + a["send_us"] <= e["dur_us"]


def test_a_message_sent_into_a_sleep_waits_for_its_end(served, slow_poll):
    db, _svc = served
    slow_poll(0.2)
    mid = db.send_message("user", "bot", "are you asleep", metadata=GEN)
    pick, = hops(db, mid)["serve.pickup"]
    assert 0 < pick["args"]["slept_us"] <= pick["dur_us"]
    assert pick["dur_us"] < (0.2 + 0.1) * 1e6
    # what is not sleep is the publish and the round's walk
    assert pick["dur_us"] - pick["args"]["slept_us"] < 0.05 * 1e6


def test_the_second_message_of_a_round_was_behind_one(served, slow_poll):
    db, _svc = served
    slow_poll(0.5)
    mids = [db.send_message("user", bot, "two of a round", metadata=GEN)
            for bot in ("bot", "bot2")]
    picks = [hops(db, m)["serve.pickup"][0] for m in mids]
    assert sorted(p["args"]["behind"] for p in picks) == [0, 1]
    first, second = sorted(picks, key=lambda p: p["args"]["behind"])
    # served one after another: the second's pickup holds the first's
    # whole serve.request
    req, = serving_spans(first["rid"])["serve.request"]
    assert end_s(second) >= end_s(req)


# ------------------------------------------------- served without the consumer

def direct(svc, msg):
    svc.serve_message(msg)


def streamed(svc, msg):
    async def drain():
        async for _ in svc.stream_reply(msg):
            pass
    asyncio.run(drain())


def streamed_group(svc, msg):
    async def drain():
        async for _ in svc.stream_group([msg]):
            pass
    asyncio.run(drain())


@pytest.mark.parametrize("serve", [direct, streamed, streamed_group])
def test_a_message_served_without_the_consumer_has_no_pickup(served, serve):
    db, svc = served
    # "nobody" has no backend: the consumer never sees this inbox
    mid = db.send_message("user", "nobody", "served by hand", metadata=GEN)
    serve(svc, db.get_message(mid))
    spans = hops(db, mid)
    assert {k: len(v) for k, v in spans.items()} == {
        "serve.request": 1, "serve.reply": 1}


def test_n_completions_write_one_reply_span(served):
    db, svc = served
    mid = db.send_message("user", "nobody", "two answers", metadata={
        "generation": {"max_new_tokens": 4, "n": 2}})
    svc.serve_message(db.get_message(mid))
    hops(db, mid)
    time.sleep(0.2)         # a second span would have come by now
    spans = serving_spans(mid)
    assert len(spans["serve.reply"]) == 1 and len(spans["serve.request"]) == 1
    reply = db.get_message(db.get_message(mid).metadata["reply_id"])
    assert len(reply.metadata["alternatives"]) == 1
    assert spans["serve.reply"][0]["args"]["tokens"] == \
        reply.metadata["completion_tokens"]


def test_a_retried_emit_reports_two_attempts(served, monkeypatch):
    db, svc = served

    class LeaderMoved(RuntimeError):
        retryable = True

    send, failed = db.send_message, []

    def flaky(*args, **kwargs):
        if (threading.current_thread().name.startswith("tpu-replies")
                and not failed):
            failed.append(args)
            raise LeaderMoved("partition moved")
        return send(*args, **kwargs)

    monkeypatch.setattr(db, "send_message", flaky)
    before = db.metrics.snapshot()["counters"].get("reply_retries", 0)
    mid = db.send_message("user", "bot", "retry my reply", metadata=GEN)
    rep, = hops(db, mid)["serve.reply"]
    assert len(failed) == 1 and rep["args"]["attempts"] == 2
    assert db.metrics.snapshot()["counters"]["reply_retries"] == before + 1
    # the span runs to the last attempt's end: the backoff is inside it
    assert rep["dur_us"] >= 0.05 * 1e6 > rep["args"]["send_us"]


# ------------------------------------------------------------- the counters

def test_the_duty_counters_are_registered_at_zero(tmp_path):
    db, svc = build(tmp_path, "tpu-9")
    assert counters(db) == {k: None for k in COUNTERS}
    svc._consume_loop = lambda: None      # a consumer that never polls
    svc.start()
    try:
        assert counters(db) == {k: 0 for k in COUNTERS}
    finally:
        svc.stop()
        db.close()


def test_the_duty_counters_move_with_the_consumer(served):
    db, svc = served
    before, t = counters(db), time.monotonic()
    assert wait_for(lambda: counters(db)["serve_poll_rounds_idle"]
                    >= before["serve_poll_rounds_idle"] + 5, 10.0)
    after, took = counters(db), time.monotonic() - t
    rounds, idle, slept_us = (after[k] - before[k] for k in COUNTERS)
    assert rounds >= idle >= 5
    # an idle round sleeps poll_interval, and nothing sleeps longer than
    # the clock ran (one sleep may straddle the first reading)
    assert idle * svc.poll_interval * 0.5e6 <= slept_us
    assert slept_us <= (took + svc.poll_interval) * 1e6 + 1e5


# --------------------------------------------------------- the tracer off

def test_with_the_tracer_off_no_hop_is_written_and_the_reply_comes(served):
    db, _svc = served
    TRACER.set_enabled(False)
    try:
        mid = db.send_message("user", "bot", "nobody is watching",
                              metadata=GEN)
        assert wait_for(lambda: "reply_id" in db.get_message(mid).metadata)
        time.sleep(0.2)
    finally:
        TRACER.set_enabled(True)
    assert serving_spans(mid) == {}
    reply = db.get_message(db.get_message(mid).metadata["reply_id"])
    assert reply.metadata["reply_to"] == mid
