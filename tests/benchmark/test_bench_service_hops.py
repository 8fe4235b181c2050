"""The four readers of the service's hops (PR 57), each on hand-made
spans and rings: what it reads, the join to the window's rows, its notes,
and ``None`` where the program writes no such span or a ring was lapped
inside the window. One case runs the tiny service on the CPU and hands the
readers ``TRACER.snapshot()``; one holds ``BENCHMARK.json`` to its four
entries."""

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec as specs  # noqa: E402

T0, SECONDS = 1000.0, 50.0
CONSUMER, REPLIES, ENGINE = 31, 32, 11      # thread ids
PICKUP = "service_pickup_wait_ms_p90"
REQUEST = "service_request_ms_p50"
REPLY = "service_reply_ms_p50"
PENDING = "engine_wait_message_pending_share"
READERS = (PICKUP, REQUEST, REPLY, PENDING)
CELLS = ["mistral7b.chat", "lfm2-8b-a1b.chat", "deepseek-v2.chat",
         "nemotron3-nano.chat"]


def span(name, rid, start, dur_ms, tid=CONSUMER, cat="serving", **args):
    return {"name": name, "cat": cat, "rid": rid, "start_s": T0 + start,
            "dur_us": dur_ms * 1e3, "tid": tid, "thread": f"t{tid}",
            "args": args or None}


def ring(tid, lost=0, oldest_end=-5.0, cap=32768):
    return {"tid": tid, "thread": f"t{tid}", "written": cap + lost,
            "capacity": cap, "lost": lost, "oldest_end_s": T0 + oldest_end}


def ctx_of(spans, rows, rings=None):
    return {"t0": T0, "seconds": SECONDS, "spans": spans, "notes": {},
            "ring_stats": rings if rings is not None else [
                ring(CONSUMER), ring(REPLIES), ring(ENGINE)],
            "window_rows": [{"id": r} for r in rows]}


def read(name, ctx):
    return specs.load_reader(name).read(ctx)


def message(rid, at, pickup_ms, slept_ms=0.0, behind=0, request_ms=2.0,
            build_us=1500, submit_us=400, tokens=900, done_at=None,
            queued_us=100, reply_ms=1.0, attempts=1):
    """One message's three spans: published at ``at``, replied from
    ``done_at`` (a second after the submit if not given)."""
    submit = at + pickup_ms * 1e-3
    done = done_at if done_at is not None else submit + 1.0
    return [
        span("serve.pickup", rid, at, pickup_ms, slept_us=int(slept_ms * 1e3),
             behind=behind, agents=4),
        span("serve.request", rid, submit, request_ms, engine_rid="e-" + rid,
             prompt_tokens=tokens, build_us=build_us, submit_us=submit_us),
        span("serve.reply", rid, done + queued_us * 1e-6, reply_ms,
             tid=REPLIES, queued_us=queued_us, decode_us=200, send_us=600,
             tokens=32, attempts=attempts)]


def wait(start, dur_ms, tid=ENGINE):
    return span("engine.wait", None, start, dur_ms, tid=tid, cat="engine",
                step=1)


# --------------------------------------------- service_pickup_wait_ms_p90

def test_pickup_is_the_windows_messages_p90_with_its_parts_in_the_notes():
    spans, rows = [], []
    for i in range(10):         # 5, 10 ... 50 ms, nine tenths of it asleep
        ms = 5.0 * (i + 1)
        spans += message(f"m{i}", 1.0 + i, ms, slept_ms=0.9 * ms,
                         behind=i % 2)
        rows.append(f"m{i}")
    # a warm-phase message's spans are held too and left out
    spans += message("warm", -3.0, 400.0, slept_ms=0.0, behind=7)
    ctx = ctx_of(spans, rows + [None])
    assert read(PICKUP, ctx) == pytest.approx(45.0)
    note = ctx["notes"][PICKUP]
    assert note["messages"] == 10
    assert note["p50"] == pytest.approx(25.0)
    assert note["mean"] == pytest.approx(27.5)
    assert note["slept_share"] == pytest.approx(0.9)
    assert note["behind_mean"] == pytest.approx(0.5)


# ------------------------------------------------- service_request_ms_p50

def test_request_is_the_median_with_build_and_submit_apart():
    spans, rows = [], []
    for i in range(5):
        spans += message(f"m{i}", 1.0 + i, 10.0, request_ms=1.0 + i,
                         build_us=800 + 1000 * i, submit_us=100 + 10 * i,
                         tokens=500 + 100 * i)
        rows.append(f"m{i}")
    spans += message("warm", -3.0, 10.0, request_ms=90.0)
    ctx = ctx_of(spans, rows)
    assert read(REQUEST, ctx) == pytest.approx(3.0)
    note = ctx["notes"][REQUEST]
    assert note["messages"] == 5
    assert note["build_us"] == {"p50": 2800, "p90": 4800}
    assert note["submit_us"] == {"p50": 120, "p90": 140}
    assert note["prompt_tokens_mean"] == pytest.approx(700.0)


def test_request_reads_an_older_programs_span_and_notes_no_parts():
    # the parent commit: serve.request with engine_rid alone, no pickup
    spans = [span("serve.request", f"m{i}", 1.0 + i, 2.0 + i,
                  engine_rid=f"e{i}") for i in range(3)]
    ctx = ctx_of(spans, ["m0", "m1", "m2"])
    assert read(REQUEST, ctx) == pytest.approx(3.0)
    note = ctx["notes"][REQUEST]
    assert note["build_us"] == {"p50": None, "p90": None}
    assert note["prompt_tokens_mean"] is None


# --------------------------------------------------- service_reply_ms_p50

def test_reply_is_the_wait_in_the_queue_plus_the_span():
    spans, rows = [], []
    for i, (queued_us, ms) in enumerate([(100, 1.0), (300, 1.2),
                                         (5000, 1.1), (200, 60.0),
                                         (150, 0.9)]):
        spans += message(f"m{i}", 1.0 + i, 10.0, queued_us=queued_us,
                         reply_ms=ms, attempts=2 if ms > 50 else 1)
        rows.append(f"m{i}")
    spans += message("cool", 51.0, 10.0, queued_us=90000, reply_ms=80.0,
                     attempts=3)
    ctx = ctx_of(spans, rows)
    # sums: 1.1, 1.5, 6.1, 60.2, 1.05
    assert read(REPLY, ctx) == pytest.approx(1.5)
    note = ctx["notes"][REPLY]
    assert note["messages"] == 5 and note["attempts_max"] == 2
    assert note["p50_us"]["queued_us"] == 200
    assert note["p50_us"]["decode_us"] == 200
    assert note["p50_us"]["send_us"] == 600
    assert note["p50_us"]["rest_us"] == pytest.approx(1100 - 800)


# -------------------------------------- engine_wait_message_pending_share

def test_pending_share_is_the_overlap_of_waits_and_pending_messages():
    spans = [wait(10.0, 2000.0), wait(20.0, 1000.0),
             wait(-4.0, 3000.0),            # before the window
             # a: wholly inside the first wait, 30 + 2 ms
             *message("a", 10.5, 30.0, request_ms=2.0),
             # b: begins 10 ms before the second wait's end, 40 + 2 ms
             *message("b", 20.99, 40.0, request_ms=2.0),
             # c: while the engine ran
             *message("c", 30.0, 25.0, request_ms=2.0),
             # the warm phase's, inside no window row
             *message("warm", 10.1, 45.0)]
    ctx = ctx_of(spans, ["a", "b", "c"])
    assert read(PENDING, ctx) == pytest.approx(100 * (0.032 + 0.010) / 50.0)
    note = ctx["notes"][PENDING]
    assert note["wait_s"] == pytest.approx(3.0)
    assert note["pending_s"] == pytest.approx(0.032 + 0.042 + 0.027)
    assert note["overlap_s"] == pytest.approx(0.042)


def test_pending_share_counts_a_second_covered_by_two_once():
    # two lanes wait at once, two messages pend at once
    spans = [wait(5.0, 1000.0), wait(5.5, 1000.0, tid=ENGINE + 1),
             *message("a", 5.9, 98.0, request_ms=2.0),
             *message("b", 5.95, 48.0, request_ms=2.0, behind=1)]
    ctx = ctx_of(spans, ["a", "b"], [ring(CONSUMER), ring(ENGINE),
                                     ring(ENGINE + 1)])
    assert read(PENDING, ctx) == pytest.approx(100 * 0.1 / 50.0)
    assert ctx["notes"][PENDING]["wait_s"] == pytest.approx(1.5)
    assert ctx["notes"][PENDING]["pending_s"] == pytest.approx(0.1)


def test_pending_share_is_zero_where_the_engine_never_waited():
    spans = message("a", 1.0, 30.0) + [
        span("engine.session", None, 0.5, 900.0, tid=ENGINE, cat="engine")]
    ctx = ctx_of(spans, ["a"])
    value = read(PENDING, ctx)
    assert value == 0.0 and isinstance(value, float)
    assert ctx["notes"][PENDING]["overlap_s"] == 0.0
    assert ctx["notes"][PENDING]["pending_s"] == pytest.approx(0.032)


def test_pending_share_cuts_a_stretch_at_the_windows_end():
    spans = [wait(49.0, 3000.0), *message("a", 49.98, 45.0)]
    ctx = ctx_of(spans, ["a"])
    assert read(PENDING, ctx) == pytest.approx(100 * 0.02 / 50.0)


# ----------------------------------- none to read, or part of it gone

@pytest.mark.parametrize("name", [PICKUP, REPLY, PENDING])
def test_a_program_without_the_hops_gives_none(name):
    # the parent commit: serve.request alone; and no span at all
    older = [span("serve.request", "a", 1.0, 2.0, engine_rid="e"),
             wait(0.5, 2000.0)]
    ctx = ctx_of(older, ["a"])
    assert read(name, ctx) is None and not ctx["notes"]
    assert read(name, ctx_of([], ["a"])) is None


@pytest.mark.parametrize("name", READERS)
def test_a_window_without_served_messages_gives_none(name):
    ctx = ctx_of(message("warm", -3.0, 10.0) + [wait(1.0, 100.0)], [])
    assert read(name, ctx) is None and not ctx["notes"]


@pytest.mark.parametrize("name, lapped", [
    (PICKUP, CONSUMER), (REQUEST, CONSUMER), (REPLY, REPLIES),
    (PENDING, CONSUMER), (PENDING, ENGINE)])
def test_a_lapped_ring_gives_none_and_a_note(name, lapped):
    spans = message("a", 10.0, 30.0) + [wait(9.0, 2000.0)]
    rings = [ring(t, lost=300 if t == lapped else 0,
                  oldest_end=8.0 if t == lapped else -5.0)
             for t in (CONSUMER, REPLIES, ENGINE)]
    ctx = ctx_of(spans, ["a"], rings)
    assert read(name, ctx) is None
    assert "lapped inside the window" in ctx["notes"][name]["unread"]
    # a ring that lapped before the window began is whole for the window
    early = [ring(t, lost=300 if t == lapped else 0, oldest_end=-2.0)
             for t in (CONSUMER, REPLIES, ENGINE)]
    assert read(name, ctx_of(spans, ["a"], early)) is not None


# ------------------------------------------- the tiny service, on the CPU

def test_the_readers_take_a_live_services_snapshot(tmp_path):
    from swarmdb_tpu.backend.service import ServingService
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.core.runtime import SwarmDB
    from swarmdb_tpu.obs import TRACER

    was = TRACER.enabled
    TRACER.set_enabled(True)
    db = SwarmDB(broker=LocalBroker(), save_dir=str(tmp_path))
    svc = ServingService.from_model_name(db, "tiny-debug", max_batch=4,
                                         backend_id="tpu-7", max_seq=128)
    svc.start()
    try:
        db.register_agent("bot")
        db.assign_llm_backend("bot", "tpu-7")
        time.sleep(0.3)         # the engine waits, the consumer sleeps
        t0 = time.time()
        mids = [db.send_message(f"u{i}", "bot", f"turn {i}", metadata={
            "generation": {"max_new_tokens": 4}}) for i in range(3)]
        deadline = time.time() + 90
        while time.time() < deadline and not all(
                "reply_id" in db.get_message(m).metadata for m in mids):
            time.sleep(0.02)
        time.sleep(0.3)         # the reply thread closes its last span
    finally:
        svc.stop()
        db.close()
        TRACER.set_enabled(was)
    ctx = {"t0": t0 - 0.01, "seconds": time.time() - t0, "notes": {},
           "spans": TRACER.snapshot(), "ring_stats": TRACER.ring_stats(),
           "window_rows": [{"id": m} for m in mids]}
    poll_ms = svc.poll_interval * 1e3
    assert 0.0 < read(PICKUP, ctx) < poll_ms + 500.0
    assert 0.0 < read(REQUEST, ctx) < 500.0
    assert 0.0 < read(REPLY, ctx) < 500.0
    assert 0.0 < read(PENDING, ctx) <= 100.0    # sent to a waiting engine
    notes = ctx["notes"]
    assert notes[PICKUP]["messages"] == notes[REPLY]["messages"] == 3
    assert 0.0 < notes[PICKUP]["slept_share"] <= 1.0
    assert 0.0 <= notes[PICKUP]["behind_mean"] <= 1.0    # 0, 1, 2 of a round
    assert notes[REQUEST]["build_us"]["p50"] > 0
    assert notes[REPLY]["attempts_max"] == 1
    assert notes[PENDING]["overlap_s"] <= notes[PENDING]["pending_s"]


# ------------------------------------------------------ BENCHMARK.json

@pytest.mark.parametrize("name, unit, moves", [
    (PICKUP, "ms", "ttft_p90_ms"), (REQUEST, "ms", "ttft_p90_ms"),
    (REPLY, "ms", "reply_p90_ms"), (PENDING, "%", "ttft_p90_ms")])
def test_the_benchmark_declares_the_reader_in_the_four_cells(name, unit,
                                                            moves):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_span", "layer": "service",
                     "moves": moves, "workloads": CELLS}
    assert callable(specs.load_reader(name).read)
    assert moves in {m["name"] for m in bench["end_to_end"]}
