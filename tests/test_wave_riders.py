"""Running requests ride the round's prefill wave (ISSUE 43): when an
admission round dispatches its ragged wave, the slots whose state the host
has confirmed join it as one-token rows, advance by one position and have
the token they sampled surfaced with their next block. CPU, tiny paged
engines: the path a token took does not change it. Since ISSUE 56 an
engine with snapshots (``tiny-nemotron``: a Mamba-2 state a slot) rides
too: a rider's state goes from its slot back to its slot."""

import importlib
import json
import os
import sys
import threading
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from swarmdb_tpu.backend.engine import GenRequest
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.service import build_backend_engine
from swarmdb_tpu.models import nemotron_h
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.obs import TRACER

ROOT = Path(__file__).resolve().parents[1]
PS, K, B, MAX_SEQ = 8, 4, 4, 128
# five requests over four slots; each is sent when the one before it has
# streamed its third token, so every admission round but the first finds
# rows that are decoding, and the fifth waits for a slot
LENGTHS = (19, 5, 11, 3, 13)
BUDGETS = (30, 25, 17, 22, 9)


def _build(name="tiny-debug", scan=False, **cfg):
    was = os.environ.get("SWARMDB_EMIT_RING")
    if scan:
        os.environ["SWARMDB_EMIT_RING"] = "0"
    try:
        eng, _tok = build_backend_engine(
            get_config(name, **cfg), max_seq=MAX_SEQ, paged=True,
            page_size=PS, decode_chunk=K, max_batch=B)
    finally:
        if scan:
            if was is None:
                os.environ.pop("SWARMDB_EMIT_RING")
            else:
                os.environ["SWARMDB_EMIT_RING"] = was
    assert eng._use_resident() == (not scan)
    if scan:
        # every chunk processed before the next round, as between two
        # resident sessions: with one in flight nobody rides
        eng.pipeline_depth = 1
    return eng


@pytest.fixture
def float32(request, monkeypatch):
    """``tiny-nemotron`` built in float32, weights and pools. In bfloat16
    a state that took one more rounding (a rider's once a wave, a resumed
    snapshot's) turns the near-ties of a random tiny model, with or
    without riders. A case on another family is built as it was."""
    if request.node.callspec.params.get("name") != "tiny-nemotron":
        return
    monkeypatch.setenv("SWARMDB_KV_DTYPE", "f32")
    init = nemotron_h.init_params
    monkeypatch.setattr(
        nemotron_h, "init_params",
        lambda cfg, key, dtype=jnp.float32: init(cfg, key, dtype))


# the engines a case runs on: the dense stack, and the one with snapshots
# on both decode paths
ENGINES = pytest.mark.parametrize("name,scan", [
    ("tiny-debug", False), ("tiny-nemotron", False), ("tiny-nemotron", True)],
    ids=["dense", "snapshots", "snapshots-scan"])


def _prompts(name="tiny-debug", lengths=LENGTHS):
    rng = np.random.default_rng(43)
    vocab = get_config(name).vocab_size
    return [rng.integers(3, vocab, size=n).tolist() for n in lengths]


def _submit(eng, prompt, max_new, on_token=None, **sampling):
    seen, done = {"stream": []}, threading.Event()
    req = GenRequest(prompt=list(prompt), sampling=SamplingParams(
        max_new_tokens=max_new, **sampling))

    def _on_token(_rid, tok):
        seen["stream"].append(tok)
        if on_token is not None:
            on_token(len(seen["stream"]))

    def _on_done(_rid, toks, reason):
        seen.update(prompt=list(prompt), tokens=list(toks), reason=reason,
                    streamed=list(seen["stream"]), routing=req.routing,
                    routing_complete=req.routing_complete, resume_len=0)
        done.set()

    req.on_token, req.on_done = _on_token, _on_done
    seen["rid"] = eng.submit(req)
    return done, seen


def _alone(eng, prompts, budgets=BUDGETS, **sampling):
    out = []
    for p, m in zip(prompts, budgets):
        done, seen = _submit(eng, p, m, **sampling)
        assert done.wait(180)
        out.append(seen)
    return out


def _staggered(eng, prompts, budgets=BUDGETS, **sampling):
    """Each request is submitted from the third token of the one before
    it (on the engine thread, as a client that streams would)."""
    pending = []

    def after(j):
        def on_token(n):
            if n == 3 and j + 1 < len(prompts):
                pending.append(_submit(eng, prompts[j + 1], budgets[j + 1],
                                       after(j + 1), **sampling))
        return on_token

    pending.append(_submit(eng, prompts[0], budgets[0], after(0),
                           **sampling))
    out = []
    for j in range(len(prompts)):
        while len(pending) <= j:
            assert pending[-1][0].wait(180)
        done, seen = pending[j]
        assert done.wait(180)
        out.append(seen)
    return out


def _riders(eng):
    return eng.metrics.counters["wave_rider_tokens"].value


def _unseated(eng):
    return eng.metrics.counters["wave_riders_unseated"].value


def _watch_rides(eng, at=None):
    """Which requests rode, and how often: a slot that holds the same
    request a position further after a wave than before it. ``at`` takes
    ``(request id, the position its token was written at)`` a ride."""
    rides = {}
    waves = eng._prefill_ragged_waves

    def watched(batch):
        before = {i: (s.request.request_id, s.position)
                  for i, s in enumerate(eng.slots) if s.active}
        waves(batch)
        for i, (rid, pos) in before.items():
            s = eng.slots[i]
            if s.request is not None and s.request.request_id == rid \
                    and s.position == pos + 1:
                assert s.pending_token
                rides[rid] = rides.get(rid, 0) + 1
                if at is not None:
                    at.append((rid, pos))

    eng._prefill_ragged_waves = watched
    return rides


# ------------------------------------------------- (a) the dense stack


@ENGINES
@pytest.mark.parametrize("sampling", [
    {}, {"temperature": 0.8, "seed": 7}], ids=["greedy", "seeded"])
def test_a_token_is_the_same_whichever_pass_sampled_it(float32, name, scan,
                                                       sampling):
    eng = _build(name, scan)
    prompts = _prompts(name)
    eng.start()
    try:
        alone = _alone(eng, prompts, **sampling)
        assert _riders(eng) == 0       # nothing live when each was admitted
        rides = _watch_rides(eng)
        together = _staggered(eng, prompts, **sampling)
        assert _riders(eng) > 0 and sum(rides.values()) == _riders(eng)
        assert _unseated(eng) == 0     # every round had a seat for each
        for a, t in zip(alone, together):
            # one backend: no tie between two logits decided otherwise by
            # the wave's attention (and scan) than by the decode step's
            assert t["tokens"] == a["tokens"] and t["reason"] == a["reason"]
            assert t["streamed"] == t["tokens"]
        if eng._snapshots:
            # the second pass found the first's pages and resumed from
            # their snapshots, with rows riding beside it
            assert eng.metrics.counters["ssm_state_tokens_resumed"].value
    finally:
        eng.stop()


def test_the_scan_path_rides_where_nothing_is_in_flight_and_only_there():
    """``pipeline_depth`` 1 processes every chunk before the next round,
    so rows ride as between resident sessions; at 2 a chunk is always in
    flight while rows are live (the device is ahead of ``generated``),
    and nobody rides. The tokens are the resident engine's either way."""
    prompts = _prompts()
    want = None
    for depth, rides in ((1, True), (2, False)):
        eng = _build(scan=True)
        eng.pipeline_depth = depth
        eng.start()
        try:
            got = [s["tokens"] for s in _staggered(eng, prompts)]
            assert (_riders(eng) > 0) == rides, depth
            want = want or got
            assert got == want
        finally:
            eng.stop()


# ------------------------------------- (b) conv state, routed experts


@pytest.mark.parametrize("name,held", [
    ("tiny-lfm2", {}),
    # as the tiny file has it: half of the experts on this chip
    ("tiny-nemotron", dict(first_held_expert=2, n_experts_held=4))],
    ids=["conv", "snapshots"])
@pytest.mark.parametrize("sampling", [
    {}, {"temperature": 0.8, "seed": 7}], ids=["greedy", "seeded"])
def test_a_rider_carries_its_conv_state_and_its_routing_row(float32, name,
                                                            held, sampling):
    sys.path.insert(0, str(ROOT))
    from benchmark.harness import check

    cfg_file = json.loads((ROOT / "tests" / "benchmark" / "tiny"
                           / f"{name}.json").read_text())
    reference = importlib.import_module(
        "benchmark.reference." + Path(cfg_file["reference"]).stem)
    eng = _build(name, **held)
    prompts = _prompts(name)
    eng.start()
    try:
        alone = _alone(eng, prompts, **sampling)
        rides = _watch_rides(eng)
        together = _staggered(eng, prompts, **sampling)
        assert _riders(eng) > 0
        for a, t in zip(alone, together):
            assert t["tokens"] == a["tokens"]
            sampled = len(t["tokens"]) + (t["reason"] == "eos")
            assert t["routing_complete"]
            assert len(t["routing"]) == len(t["prompt"]) + sampled - 1
            assert (t["routing"] == a["routing"]).all()
        assert eng.metrics.counters["routing_incomplete_requests"].value == 0
        # the float32 follower on a record that rode at least twice
        twice = [t for t in together if rides.get(t["rid"], 0) >= 2]
        assert twice
        stack = SimpleNamespace(
            cfg_file=cfg_file, lanes=[SimpleNamespace(params=eng.params)])
        gaps = check.logit_gaps(stack, twice, reference)
        if sampling:
            # a sampled token is not the maximum: the follower runs, on a
            # record whose rows it needs every one of, and reads finite
            assert np.isfinite(gaps).all()
        else:
            assert max(gaps) < check.LOGIT_TOL
    finally:
        eng.stop()


@pytest.mark.parametrize("name,scan", [
    ("tiny-nemotron", False), ("tiny-nemotron", True)],
    ids=["resident", "scan"])
def test_a_rider_at_a_page_end_takes_no_snapshot_and_costs_none(float32,
                                                                 name, scan):
    """A rider's token that ends a page crosses a page end like a prompt's
    last whole page, and its state there goes to the bin: the snapshot
    pool's rows are bit for bit what they were but the ones the round's
    admitted rows took, the host's table counts the admitted rows' alone,
    and a later turn of the conversation that rode resumes from the
    snapshot its own prompt left."""
    lengths = (23, 5, 11, 3, 13)
    eng = _build(name, scan)
    prompts = _prompts(name, lengths)
    at, rounds, faults = [], [], []
    _watch_rides(eng, at)
    waves, take = eng._prefill_ragged_waves, eng._take_snapshots
    pools = lambda: [np.asarray(eng.cache["page_state"][part])
                     for part in ("ssm", "conv")]

    def taking(batch):
        out = take(batch)
        rounds.append(sorted(dst for dst, _end in out.values()))
        return out

    def watched(batch):
        before, live = pools(), eng._prefix.state_slots_live()
        waves(batch)
        taken = rounds[-1]
        kept = [i for i in range(1, eng._snapshots + 1) if i not in taken]
        try:        # on the engine's thread: kept for the test's
            for was, now in zip(before, pools()):
                np.testing.assert_array_equal(now[:, kept], was[:, kept])
            assert eng._prefix.state_slots_live() == live + len(taken)
        except AssertionError as e:
            faults.append(e)

    eng._take_snapshots, eng._prefill_ragged_waves = taking, watched
    eng.start()
    try:
        together = _staggered(eng, prompts)
        assert not faults, faults
        c = eng.metrics.counters
        # every prompt of a page or more took one snapshot, riders none
        assert c["ssm_snapshots_taken"].value == sum(
            n >= PS for n in lengths) == sum(len(t) for t in rounds)
        assert c["ssm_snapshots_evicted"].value == 0
        assert eng._prefix.state_slots_live() == c[
            "ssm_snapshots_taken"].value
        # someone whose prompt left a snapshot rode at a page end
        ended = {rid for rid, pos in at if (pos + 1) % PS == 0}
        turns = [t for t in together
                 if t["rid"] in ended and len(t["prompt"]) >= PS]
        assert turns, at
        turn = turns[0]
        resumed = c["ssm_state_tokens_resumed"].value
        more = turn["prompt"] + turn["tokens"] + prompts[1]
        done, later = _submit(eng, more, 12)
        assert done.wait(180)
        full = len(turn["prompt"]) // PS * PS
        assert c["ssm_state_tokens_resumed"].value - resumed == full
    finally:
        eng.stop()
    cold = _build(name, scan)
    cold.start()
    try:
        assert _alone(cold, [more], (12,))[0]["tokens"] == later["tokens"]
    finally:
        cold.stop()


# -------------------------------------------------------- (c) the rule


@pytest.fixture(scope="module")
def idle():
    """An engine that never runs: its slots are set by hand."""
    return _build()


@pytest.fixture(scope="module", params=["dense", "snapshots"])
def either(request, idle):
    """The same, of either kind: who rides is read from the slots alone,
    whatever state a slot carries beside its pages."""
    if request.param == "dense":
        return idle
    eng = _build("tiny-nemotron")
    assert eng._snapshots
    return eng


def _running(eng, **over):
    """Slot 0 as a row that may ride; ``over`` changes what a case is
    about. Everything else is free."""
    for s in eng.slots:
        s.active = False
    s = eng.slots[0]
    s.active, s.cancelled, s.pending_token = True, False, False
    s.request = GenRequest(prompt=[5] * 12,
                           sampling=SamplingParams(max_new_tokens=10))
    s.generated = [7, 8, 9]
    s.position = s.dispatched_position = 14
    s.table_row = np.arange(MAX_SEQ // PS, dtype=np.int32)
    for k, v in over.items():
        setattr(s, k, v)
    return s


def test_a_running_row_whose_state_is_confirmed_rides(either):
    _running(either)
    assert either._wave_riders() == [0]


@pytest.mark.parametrize("over", [
    {"generated": list(range(9))},            # one token left of ten
    {"cancelled": True},
    {"position": MAX_SEQ - 1, "dispatched_position": MAX_SEQ - 1},
    {"dispatched_position": 14 + K},          # a chunk in flight
    {"pending_token": True},                  # a token already pending
    {"pending_token": True, "generated": []},  # admitted, first not out
], ids=["one_left", "cancelled", "at_max_seq", "chunk_in_flight",
        "pending", "first_pending"])
def test_who_does_not_ride(either, over):
    _running(either, **over)
    assert either._wave_riders() == []


def test_two_left_and_the_last_position_under_max_seq_still_ride(either):
    _running(either, generated=list(range(8)))
    assert either._wave_riders() == [0]
    _running(either, position=MAX_SEQ - 2, dispatched_position=MAX_SEQ - 2)
    assert either._wave_riders() == [0]


def test_a_prefill_lane_takes_no_rider(either):
    _running(either)
    either._role = "prefill"
    try:
        assert either._wave_riders() == []
    finally:
        either._role = None


@pytest.mark.parametrize("ridge,total,width,seats,fit", [
    (0.0, 5, 8, 3, 3),       # the padding under the rung
    (0.0, 5, 8, 2, 2),       # fewer rows than seats
    (0.0, 5, 8, 9, 3),       # a wider rung would cost more
    (0.0, 8, 8, 3, 0),       # no free seat under the plan's price
    (0.0, 16, 16, 1, 0),
    (64.0, 8, 8, 3, 3),      # under the ridge the next rungs cost the same
    (64.0, 30, 32, 40, 34),
    (64.0, 64, 64, 3, 0),    # at the ridge they do not
    (240.0, 20, 32, 40, 40),    # a v5e's ridge: rung 64 at the same price
    (240.0, 100, 128, 40, 28),  # no rung over 128 here; 256 would cost more
    (240.0, 126, 128, 6, 2),
], ids=lambda v: str(v))
def test_riders_sit_in_seats_the_plan_pays_for(idle, ridge, total, width,
                                               seats, fit):
    was = idle._ragged_ridge_tokens
    idle._ragged_ridge_tokens = ridge
    try:
        assert idle._ragged_width_for(total) == width
        assert idle._riders_that_fit(total, width, seats) == fit
        # with them the round is still one wave at no higher a price
        wider = idle._ragged_width_for(total + fit)
        assert wider >= total + fit
        assert max(wider, ridge) <= max(width, ridge)
    finally:
        idle._ragged_ridge_tokens = was


@ENGINES
def test_a_full_wave_leaves_the_running_rows_to_their_decode_step(
        float32, name, scan):
    """The second request's 8 tokens fill its rung: the first, which is
    decoding, does not ride, loses nothing by it, and is counted as the
    one running row that round had no seat for."""
    eng = _build(name, scan)
    prompts = _prompts(name, lengths=(19, 8))
    eng.start()
    try:
        alone = _alone(eng, prompts, (30, 12))
        assert _unseated(eng) == 0     # nobody ran beside an admission
        together = _staggered(eng, prompts, (30, 12))
        assert _riders(eng) == 0 and _unseated(eng) == 1
        assert [t["tokens"] for t in together] == [a["tokens"]
                                                   for a in alone]
    finally:
        eng.stop()


def test_a_stack_whose_ffn_drops_has_no_wave_to_ride():
    eng = _build("tiny-moe")
    assert eng._prefill_ragged_fused is None
    prompts = _prompts("tiny-moe")
    eng.start()
    try:
        together = _staggered(eng, prompts)
        assert all(len(t["tokens"]) == m
                   or t["reason"] == "eos"
                   for t, m in zip(together, BUDGETS))
        assert "wave_rider_tokens" not in eng.metrics.counters
    finally:
        eng.stop()


# ------------------------------------- (d) what a rider does not count


def test_a_rider_is_no_admission_and_its_token_is_no_first_token():
    eng = _build()
    prompts = _prompts()
    n = len(prompts)
    eng.start()
    was = TRACER.enabled
    try:
        alone = _alone(eng, prompts)
        c = eng.metrics.counters
        lat = eng.metrics.latencies
        names = ("prompt_tokens", "prefix_reused_tokens",
                 "prefill_packed_tokens", "tokens_generated",
                 "engine_admitted")
        base = {k: c[k].value for k in names}
        firsts = lat["first_token_s"].count()
        waits = lat["queue_wait_s"].count()
        TRACER.reset()
        TRACER.set_enabled(True)
        together = _staggered(eng, prompts)
        spans = TRACER.snapshot()
        assert _riders(eng) > 0
        moved = {k: c[k].value - base[k] for k in names}
        # what admission counts it counts of the admitted alone: every
        # prompt token is one the cache gave or one a wave packed, and a
        # rider's is neither
        assert moved["prompt_tokens"] == sum(LENGTHS)
        assert (moved["prefill_packed_tokens"]
                + moved["prefix_reused_tokens"]) == sum(LENGTHS)
        assert moved["prefix_reused_tokens"] % PS == 0
        assert moved["engine_admitted"] == n
        assert moved["tokens_generated"] == sum(
            len(t["tokens"]) for t in together)
        assert [len(t["streamed"]) for t in together] == [
            len(a["streamed"]) for a in alone]
        # one first token a request, riders or not
        assert lat["first_token_s"].count() - firsts == n
        assert lat["queue_wait_s"].count() - waits == n
        assert sum(s["name"] == "engine.first_token" for s in spans) == n
        packs = [s["args"] for s in spans
                 if s["name"] == "engine.admission.pack"]
        assert sum(p["riders"] for p in packs) == _riders(eng)
        # a rider's seat is still padding to admission's accounts
        assert all(p["filled"] + p["riders"] <= p["width"] for p in packs)
        # the session after a round with riders carried them
        sessions = [s["args"] for s in spans if s["name"] == "engine.session"]
        assert any(s["carried"] > 0 for s in sessions)
    finally:
        TRACER.set_enabled(was)
        eng.stop()


# ------------------------------------------------- (e) nothing compiles


@pytest.mark.parametrize("name", ["tiny-debug", "tiny-nemotron"],
                         ids=["dense", "snapshots"])
def test_riders_compile_nothing_after_warm_up(name):
    eng = _build(name)
    eng.warmup()
    n0 = eng._compiled_count()
    eng.start()
    try:
        _staggered(eng, _prompts(name))
        _staggered(eng, _prompts(name, lengths=(7, 21, 4, 9, 16)))
        assert _riders(eng) > 0
        assert eng._compiled_count() == n0
    finally:
        eng.stop()
