"""ServingService tests: message → generation → reply wiring, streaming,
backend consumer, tool-use replies, health. Tiny model on CPU."""

import asyncio
import tempfile
import threading
import time

import pytest

from swarmdb_tpu.backend.service import ServingService, build_prompt, sampling_from_message
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.broker.local import LocalBroker
from swarmdb_tpu.core.messages import Message, MessageType
from swarmdb_tpu.core.runtime import SwarmDB


@pytest.fixture(scope="module")
def served_db(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("serve")
    db = SwarmDB(broker=LocalBroker(), save_dir=str(tmp))
    svc = ServingService.from_model_name(db, "tiny-debug", backend_id="tpu-0",
                                         max_batch=4, max_seq=128)
    svc.start()
    yield db, svc
    svc.stop()
    db.close()


def _wait_for(cond, timeout=60.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return False


def test_serve_message_emits_reply(served_db):
    db, svc = served_db
    db.register_agent("user1")
    db.register_agent("assistant")
    mid = db.send_message("user1", "assistant", "hello assistant",
                          metadata={"generation": {"max_new_tokens": 6}})
    svc.serve_message(db.get_message(mid))
    assert _wait_for(lambda: "reply_id" in db.get_message(mid).metadata)
    reply = db.get_message(db.get_message(mid).metadata["reply_id"])
    assert reply.sender_id == "assistant" and reply.receiver_id == "user1"
    assert reply.type == MessageType.CHAT
    assert reply.metadata["reply_to"] == mid
    assert reply.metadata["backend_id"] == "tpu-0"
    assert reply.metadata["finish_reason"] in ("length", "eos")
    # source marked processed; stage stamps present
    src = db.get_message(mid)
    assert src.status.value == "processed"
    stages = src.metadata["stages"]
    assert {"enqueued", "admitted", "first_token", "done"} <= set(stages)


def test_function_call_gets_function_result(served_db):
    db, svc = served_db
    mid = db.send_message(
        "tool_user", "assistant",
        {"tool": "search", "args": {"q": "weather"}},
        message_type=MessageType.FUNCTION_CALL,
        metadata={"generation": {"max_new_tokens": 4}},
    )
    svc.serve_message(db.get_message(mid))
    assert _wait_for(lambda: "reply_id" in db.get_message(mid).metadata)
    reply = db.get_message(db.get_message(mid).metadata["reply_id"])
    assert reply.type == MessageType.FUNCTION_RESULT


def test_backend_consumer_drains_assigned_agents(served_db):
    """The north-star wiring: assign an agent to the backend, send it a chat
    message through normal SwarmDB routing, and the reply appears with no
    explicit serve_message call."""
    db, svc = served_db
    db.register_agent("llm_bot")
    db.set_llm_load_balancing(True)
    db.assign_llm_backend("llm_bot", "tpu-0")
    mid = db.send_message("human", "llm_bot", "ping the bot",
                          metadata={"generation": {"max_new_tokens": 4}})
    assert _wait_for(lambda: "reply_id" in db.get_message(mid).metadata, 90)
    reply = db.get_message(db.get_message(mid).metadata["reply_id"])
    assert reply.sender_id == "llm_bot" and reply.receiver_id == "human"
    # and the human can receive it through the broker
    got = db.receive_messages("human", timeout=2.0)
    assert reply.id in [m.id for m in got]


def test_stream_reply_yields_text(served_db):
    db, svc = served_db
    mid = db.send_message("s", "r", "stream this",
                          metadata={"generation": {"max_new_tokens": 5}})

    async def collect():
        chunks = []
        async for text in svc.stream_reply(db.get_message(mid)):
            chunks.append(text)
        return chunks

    chunks = asyncio.run(collect())
    assert isinstance(chunks, list)
    # reply message exists and its text equals the streamed concatenation
    reply = db.get_message(db.get_message(mid).metadata["reply_id"])
    assert "".join(chunks) == reply.content


def test_stream_group_interleaves(served_db):
    db, svc = served_db
    db.add_agent_group("panel", ["askr", "bot1", "bot2"])
    ids = db.send_to_group("askr", "panel", "hello panel",
                           metadata={"generation": {"max_new_tokens": 3}})
    msgs = [db.get_message(i) for i in ids]

    async def collect():
        events = []
        async for ev in svc.stream_group(msgs):
            events.append(ev)
        return events

    events = asyncio.run(collect())
    done = [e for e in events if e["event"] == "reply_done"]
    assert {e["message_id"] for e in done} == set(ids)


def test_build_prompt_includes_history(served_db):
    db, svc = served_db
    db.send_message("alice", "bob", "first message")
    db.send_message("bob", "alice", "the response")
    mid = db.send_message("alice", "bob", "follow-up")
    ids = build_prompt(db, db.get_message(mid), svc.tokenizer)
    text = svc.tokenizer.decode(ids)
    assert "first message" in text and "the response" in text
    assert text.rstrip().endswith("bob:")


def test_sampling_from_message_defaults():
    m = Message(sender_id="a", receiver_id="b", content="x")
    s = sampling_from_message(m)
    assert s.temperature == 0.0 and s.max_new_tokens == 64
    m2 = Message(sender_id="a", receiver_id="b", content="x",
                 metadata={"generation": {"temperature": 0.7, "top_k": 40,
                                          "max_new_tokens": 9}})
    s2 = sampling_from_message(m2)
    assert s2.temperature == 0.7 and s2.top_k == 40 and s2.max_new_tokens == 9


def test_health_probe(served_db):
    db, svc = served_db
    h = svc.health()
    assert h["status"] == "healthy"
    assert "engine" in h and h["engine"]["max_batch"] == 4
    assert h["probe_ms"] >= 0


def test_merge_env_selects_scatter(monkeypatch):
    """SWARMDB_MERGE=scatter wires the scatter-form chunk merge into the
    engine's chunked decode (dense mode only; paged has its own merge)."""
    from swarmdb_tpu.backend.service import ServingService
    from swarmdb_tpu.models import llama

    monkeypatch.setenv("SWARMDB_MERGE", "scatter")
    monkeypatch.setenv("SWARMDB_PAGED", "0")
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        try:
            svc = ServingService.from_model_name(
                db, "tiny-debug", backend_id="b0", max_batch=2, max_seq=32,
                decode_chunk=4)
            assert svc.engine._chunked_fns is not None
            assert svc.engine._chunked_fns[2] is llama.merge_chunk_scatter
        finally:
            db.close()


def test_chunked_env_is_read_nowhere(monkeypatch):
    """Nothing reads SWARMDB_CHUNKED: with it set to 0 a paged engine is
    the engine it is without it, the same programs in its warm-up plan,
    the chunk triple of its page pool, the same greedy tokens."""
    from swarmdb_tpu.backend.sampling import SamplingParams
    from swarmdb_tpu.backend.service import build_backend_engine

    def build():
        eng, _tok = build_backend_engine(
            "tiny-debug", paged=True, max_batch=2, max_seq=64, page_size=8,
            decode_chunk=4)
        names = [getattr(fn, "__name__", repr(fn))
                 for fn, _args in eng.warmup_call_plan()]
        assert eng._chunked_fns is eng.paged.chunked_fns
        eng.start()
        try:
            toks = [eng.generate_sync(p, SamplingParams(max_new_tokens=9))
                    for p in ([1, 5, 9], list(range(3, 20)))]
        finally:
            eng.stop()
        return names, toks

    monkeypatch.delenv("SWARMDB_CHUNKED", raising=False)
    want = build()
    monkeypatch.setenv("SWARMDB_CHUNKED", "0")
    got = build()
    assert want[0] and got == want


def test_build_prompt_window_is_anchor_stable(monkeypatch):
    """Prompts must stay prefix-stable (each turn extends the previous
    prompt) even after the conversation exceeds SWARMDB_HISTORY_LIMIT:
    the message window drops old turns in half-limit hysteresis steps
    anchored at the STREAM position, not a newest-N slice that slides
    every turn (which made the prefix cache go dark after ~limit/2
    turns)."""
    from swarmdb_tpu.backend.tokenizer import ByteTokenizer

    monkeypatch.setenv("SWARMDB_HISTORY_LIMIT", "16")
    tok = ByteTokenizer(vocab_size=512)
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        try:
            db.register_agent("u")
            db.register_agent("a")
            prev = None
            jumps = 0
            turns = 60  # well past the 16-message window
            for i in range(turns):
                mid = db.send_message("u", "a", f"turn {i} says hello")
                msg = db.get_message(mid)
                prompt = tok.decode(build_prompt(db, msg, tok))
                # drop the trailing "a:" assistant cue: the next turn
                # continues from there
                body = prompt.rsplit("\na:", 1)[0]
                if prev is not None and not body.startswith(prev):
                    jumps += 1
                prev = body
            # anchor may move only at hysteresis boundaries: with
            # limit=16/step=8 that is ~once per 8 turns past the limit,
            # not every turn (the old behavior: ~44 jumps here)
            assert jumps <= turns // 8 + 1, jumps
        finally:
            db.close()


def test_trim_prompt_sink_anchor_head_is_stable():
    """The sink-anchored two-segment window (VERDICT r5 #4): once a
    conversation overflows the token budget, every trimmed prompt starts
    with the SAME page-aligned head — the hit-rate floor that a sliding
    trim cannot provide at short S (each recompute-from-length jump
    re-anchors position 0 and invalidates every cached page)."""
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        try:
            svc = ServingService.from_model_name(
                db, "tiny-debug", backend_id="b0", max_batch=2, max_seq=128)
            assert svc.engine._prefix is not None
            ps = svc.engine._prefix_ps
            msg = Message(sender_id="u", receiver_id="a", content="x")
            budget = 100
            # growing prompts, ~35 tokens per turn (the dpserve shape:
            # per-turn delta comparable to the whole budget)
            base = list(range(3, 38))
            heads = set()
            for turn in range(2, 12):
                prompt = (base * turn)[: 35 * turn]
                out = svc._trim_prompt(msg, list(prompt), budget)
                assert len(out) <= budget
                head = svc._anchors[("u", "a")]
                assert len(head) % ps == 0 and len(head) >= ps
                assert out[: len(head)] == head
                heads.add(tuple(head))
            assert len(heads) == 1  # captured once, immutable
            # a second conversation gets its OWN head
            msg2 = Message(sender_id="u2", receiver_id="a", content="x")
            out2 = svc._trim_prompt(msg2, list(range(50, 250)), budget)
            head2 = svc._anchors[("u2", "a")]
            assert out2[: len(head2)] == head2
            assert head2 != svc._anchors[("u", "a")]
        finally:
            db.close()


def test_trim_prompt_anchor_disabled_falls_back(monkeypatch):
    """SWARMDB_ANCHOR_HEAD=0 restores the sliding page-aligned hysteresis
    trim (and stores no anchors)."""
    monkeypatch.setenv("SWARMDB_ANCHOR_HEAD", "0")
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        try:
            svc = ServingService.from_model_name(
                db, "tiny-debug", backend_id="b0", max_batch=2, max_seq=128)
            msg = Message(sender_id="u", receiver_id="a", content="x")
            out = svc._trim_prompt(msg, list(range(3, 203)), 100)
            assert len(out) <= 100
            assert not svc._anchors
        finally:
            db.close()


def test_short_seq_conversation_keeps_prefix_hits():
    """End-to-end short-S regression (the dpserve 3.9%-hit class): a
    conversation whose per-turn delta rivals the whole window must STILL
    hit the prefix cache every turn once anchored — the head pages are
    position-stable by construction. Asserts the post-overflow hit rate
    clears 20% (acceptance bar; the sliding trim measured ~4%)."""
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        try:
            svc = ServingService.from_model_name(
                db, "tiny-debug", backend_id="b0", max_batch=2, max_seq=128)
            svc.start(warmup=False)
            db.register_agent("u")
            db.register_agent("a")
            stats0 = None
            for turn in range(14):
                mid = db.send_message(
                    "u", "a",
                    f"turn {turn}: the quick brown fox jumps over #{turn}",
                    metadata={"generation": {"max_new_tokens": 4,
                                             "temperature": 0.0}})
                svc.serve_message(db.get_message(mid))
                assert _wait_for(
                    lambda: "reply_id" in db.get_message(mid).metadata)
                if turn == 7 and svc._anchors:
                    # anchored by now: measure hits from here on
                    stats0 = dict(svc.engine._prefix.stats())
            assert svc._anchors, "budget never overflowed — test shape bug"
            assert stats0 is not None, "anchor appeared too late"
            s1 = svc.engine._prefix.stats()
            hit = s1["hit_tokens"] - stats0["hit_tokens"]
            miss = s1["miss_tokens"] - stats0["miss_tokens"]
            assert hit + miss > 0
            rate = hit / (hit + miss)
            assert rate >= 0.2, f"post-anchor hit rate {rate:.3f}"
        finally:
            svc.stop()
            db.close()
