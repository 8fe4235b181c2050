"""Serving-path fault tolerance (ISSUE 9): lane supervision, retryable
request migration, pool-watermark backpressure, and the serving chaos
harness — all on CPU virtual devices, all deterministic (the only
sleeping is bounded convergence polling against the thresholds under
test).

The acceptance contracts proven here:

- a streamed request survives a mid-decode lane KILL with zero duplicate
  and zero lost chunks (greedy replay is bit-identical to an
  uninterrupted run, checked at every chunk boundary);
- a wedged dispatch (live thread, starved beats) quarantines, migrates,
  and — after heal — re-admits;
- pool squeeze past the hard watermark sheds ONLY the lowest-priority
  queued work, shed requests are retryable, and the client retry
  succeeds once the squeeze heals;
- deadlines bound every wait: an expired queued request fails with the
  final reason "deadline", never a hung stream;
- a retry storm trips the sentinel's new retry_rate SLO with an
  attributed alert.
"""

import threading
import time

import pytest

# an injected LaneKilled IS an unhandled thread exception — the failure
# mode under test, not noise
pytestmark = pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")

from swarmdb_tpu.backend.chaos import ServingChaos, wait_until
from swarmdb_tpu.backend.engine import (GenRequest, RETRYABLE_REASONS,
                                        is_retryable_reason)
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.supervisor import LaneState
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.parallel.lanes import ShardLaneGroup
from swarmdb_tpu.parallel.mesh import make_mesh
from swarmdb_tpu.parallel.serving import build_serving_engine


@pytest.fixture(scope="module")
def stack():
    """2-lane supervised group + chaos harness, shared by the module
    (one compile payment); every test must leave both lanes healthy."""
    g, info = build_serving_engine(
        get_config("tiny-debug"), make_mesh(2, data=2, model=1, expert=1),
        max_batch=4, max_seq=128, paged=True, page_size=8, decode_chunk=4,
    )
    assert isinstance(g, ShardLaneGroup) and info.data_size == 2
    g.start()
    sup = g.attach_supervisor(
        suspect_s=0.25, quarantine_s=0.5, poll_s=0.05,
        probe_clean_n=2, probe_timeout_s=60.0, deadline_s=120.0,
        retries=2)
    chaos = ServingChaos(g)
    yield g, sup, chaos
    chaos.stop()
    sup.stop()
    g.stop()


def _healthy(sup) -> bool:
    return all(l["state"] == "alive" for l in sup.status()["lanes"])


def _gen(group, prompt, max_new, hint=None, priority=1, on_token=None,
         deadline=None, timeout=120.0):
    """Submit one request through the supervised group and wait for it;
    returns (tokens, reason, streamed)."""
    done = threading.Event()
    out = {}
    streamed = []

    def _tok(rid, tok):
        streamed.append(tok)
        if on_token is not None:
            on_token(rid, tok, streamed)

    def _done(rid, toks, reason):
        out["toks"] = toks
        out["reason"] = reason
        done.set()

    req = GenRequest(prompt=list(prompt),
                     sampling=SamplingParams(max_new_tokens=max_new),
                     priority=priority, shard_hint=hint,
                     on_token=_tok, on_done=_done, deadline=deadline)
    group.submit(req)
    assert done.wait(timeout), "request never completed"
    return out["toks"], out["reason"], streamed


def test_idle_lanes_beat_and_read_alive(stack):
    g, sup, _ = stack
    wait_until(lambda: _healthy(sup), 10.0, what="both lanes alive")
    st = sup.status()
    assert [l["state_code"] for l in st["lanes"]] == [0, 0]
    assert all(l["beat_age_s"] < 1.0 for l in st["lanes"])
    # prometheus surface: one swarmdb_lane_state line per lane
    lines = sup.prometheus_lines()
    assert 'swarmdb_lane_state{lane="0"} 0' in lines
    assert 'swarmdb_lane_state{lane="1"} 0' in lines


def test_retryable_reason_contract():
    # the BrokerError.retryable contract, serving-side: engine losses and
    # deliberate returns are retryable; final outcomes are not
    for r in ("engine_error", "engine_restart", "lane_quarantined",
              "shed", "stale_resume"):
        assert is_retryable_reason(r), r
    for r in ("eos", "length", "cancelled", "deadline", "max_seq"):
        assert not is_retryable_reason(r), r
    assert "deadline" not in RETRYABLE_REASONS


def test_kill_mid_stream_migrates_with_zero_loss(stack):
    g, sup, chaos = stack
    wait_until(lambda: _healthy(sup), 30.0, what="lanes healthy")
    prompt = [1, 5, 9, 13]
    ref, reason, _ = _gen(g, prompt, 24, hint=0)
    assert reason == "length" and len(ref) == 24

    migrated_before = g.metrics.counters["requests_migrated"].value
    killed = []

    def kill_at_8(rid, tok, streamed):
        if len(streamed) == 8 and not killed:
            killed.append(True)
            chaos.kill_lane(0)  # lands at the next chunk boundary

    toks, reason, streamed = _gen(g, prompt, 24, hint=0,
                                  on_token=kill_at_8)
    assert killed, "stream finished before the kill armed"
    # zero lost, zero duplicate chunks: the full stream is exactly the
    # uninterrupted greedy reference, and what streamed is what returned
    assert reason == "length"
    assert streamed == toks
    assert toks == ref, "migrated stream diverged from reference"
    assert (g.metrics.counters["requests_migrated"].value
            > migrated_before)
    # evidence trail: quarantine + migration instants in the flight ring
    kinds = {e.get("kind") for e in g.flight.events()}
    assert "lane.quarantined" in kinds
    assert "request.migrated" in kinds
    # recovery: the killed lane restarts, probes clean, and re-admits
    wait_until(lambda: _healthy(sup), 60.0, what="lane 0 readmission")
    st = sup.status()
    assert st["lane_quarantines"] >= 1
    assert st["lane_readmissions"] >= 1
    assert "lane.readmitted" in {e.get("kind") for e in g.flight.events()}
    # post-recovery: the same prompt on the recovered lane still matches
    again, _, _ = _gen(g, prompt, 24, hint=0)
    assert again == ref


def test_replay_bit_identical_at_every_chunk_boundary(stack):
    """Property-style migration-correctness satellite: interrupt the
    stream at every chunk boundary k (emission is block-granular; a kill
    armed at token k, from the stream path, lands at the chunk boundary
    after k's: the resident loop's vote on k's block was taken before
    the block was emitted) and require the replayed total sequence to be
    bit-identical with no duplicate emission (greedy, seeded engine
    weights)."""
    g, sup, chaos = stack
    prompt = [2, 4, 6, 8, 10]
    # decode_chunk=4 -> boundaries at 4, 8, 12, and a chunk past the
    # last of them before the stream ends, so every kill lands mid-stream
    n_tokens = 20
    wait_until(lambda: _healthy(sup), 60.0, what="lanes healthy")
    ref, _, _ = _gen(g, prompt, n_tokens, hint=1)
    assert len(ref) == n_tokens
    for k in (4, 8, 12):
        wait_until(lambda: _healthy(sup), 60.0,
                   what=f"lane recovery before boundary {k}")
        killed = []

        def kill_at_k(rid, tok, streamed, _k=k, _killed=killed):
            if len(streamed) == _k and not _killed:
                _killed.append(True)
                chaos.kill_lane(1)

        toks, reason, streamed = _gen(g, prompt, n_tokens, hint=1,
                                      on_token=kill_at_k)
        assert killed, f"boundary {k}: stream finished before the kill"
        assert reason == "length"
        assert streamed == toks, f"boundary {k}: stream != final tokens"
        assert toks == ref, (
            f"boundary {k}: replay diverged "
            f"(len {len(toks)} vs {len(ref)})")
    wait_until(lambda: _healthy(sup), 60.0, what="final recovery")


def test_wedge_quarantines_migrates_and_heals(stack):
    g, sup, chaos = stack
    wait_until(lambda: _healthy(sup), 60.0, what="lanes healthy")
    q_before = sup.status()["lane_quarantines"]
    chaos.wedge(0)
    wait_until(
        lambda: sup.status()["lanes"][0]["state"] == "quarantined",
        10.0, what="wedged lane quarantined")
    st = sup.status()["lanes"][0]
    assert st["thread_alive"], "wedge must not kill the thread"
    # routing avoids the wedged lane: a hinted request for lane 0 still
    # completes (remapped to the healthy sibling)
    toks, reason, _ = _gen(g, [3, 7, 11], 8, hint=0)
    assert reason == "length" and len(toks) == 8
    chaos.heal(0)
    wait_until(lambda: _healthy(sup), 60.0, what="wedged lane readmitted")
    assert sup.status()["lane_quarantines"] == q_before + 1


def test_supervisor_retries_engine_restart(stack):
    """A single-lane loss with no sibling still resolves: the supervised
    request rides RETRYABLE_REASONS requeue (engine_restart) instead of
    surfacing FAILED — ROADMAP item 5's detector+requeue contract."""
    g, sup, chaos = stack
    wait_until(lambda: _healthy(sup), 60.0, what="lanes healthy")
    retried_before = g.metrics.counters["requests_retried"].value
    # fail the attempt INSIDE the engine: restart fails active+queued
    # with reason engine_restart (retryable) after a couple of tokens
    restarted = []

    def restart_at_4(rid, tok, streamed):
        if len(streamed) == 4 and not restarted:
            restarted.append(True)
            # direct engine restart (not via chaos): exercises the
            # retry path rather than the migration path
            threading.Thread(
                target=g.lanes[1].restart, daemon=True).start()

    toks, reason, streamed = _gen(g, [1, 9, 17], 16, hint=1,
                                  on_token=restart_at_4)
    assert reason == "length" and len(toks) == 16
    assert streamed == toks
    assert (g.metrics.counters["requests_retried"].value
            > retried_before)
    wait_until(lambda: _healthy(sup), 60.0, what="post-restart recovery")


def test_deadline_expires_instead_of_hanging(stack):
    g, sup, chaos = stack
    wait_until(lambda: _healthy(sup), 60.0, what="lanes healthy")
    # wedge BOTH lanes so nothing can serve; a deadlined request must
    # fail with "deadline" instead of hanging to the client timeout
    chaos.wedge(0)
    chaos.wedge(1)
    try:
        toks, reason, _ = _gen(g, [5, 6, 7], 8,
                               deadline=time.time() + 1.0, timeout=30.0)
        assert reason == "deadline"
        assert toks == []
        assert g.metrics.counters["requests_deadline_expired"].value >= 1
    finally:
        chaos.heal(0)
        chaos.heal(1)
    wait_until(lambda: _healthy(sup), 60.0, what="post-wedge recovery")


def _build_single_paged(monkeypatch, high, low, shed):
    from swarmdb_tpu.backend.service import build_backend_engine

    monkeypatch.setenv("SWARMDB_POOL_HIGH", str(high))
    monkeypatch.setenv("SWARMDB_POOL_LOW", str(low))
    monkeypatch.setenv("SWARMDB_POOL_SHED", str(shed))
    eng, _tok = build_backend_engine(
        get_config("tiny-debug"), max_batch=2, max_seq=64, paged=True,
        page_size=8, decode_chunk=4)
    return eng


def test_backpressure_pause_resume_hysteresis(monkeypatch):
    eng = _build_single_paged(monkeypatch, high=0.5, low=0.2, shed=0.9)
    eng.start()
    chaos = ServingChaos(eng)
    try:
        # squeeze past the high watermark -> admission pauses
        chaos.squeeze_pool(0.95)
        done = threading.Event()
        out = {}
        eng.submit(GenRequest(
            prompt=[1, 2, 3], sampling=SamplingParams(max_new_tokens=4),
            on_done=lambda rid, t, r: (out.update(reason=r, toks=t),
                                       done.set())))
        wait_until(
            lambda: eng.metrics.counters["engine_admission_paused"].value
            >= 1, 10.0, what="admission pause")
        assert not done.is_set(), "paused engine admitted anyway"
        assert eng.stats()["admission_paused"] is True
        # heal -> utilization falls under the LOW watermark -> resume,
        # and the parked request completes
        chaos.heal_pool()
        assert done.wait(60), "admission never resumed after heal"
        assert out["reason"] == "length"
        assert (eng.metrics.counters["engine_admission_resumed"].value
                >= 1)
        kinds = {e.get("kind") for e in eng.flight.events()}
        assert "pool.backpressure_paused" in kinds
        assert "pool.backpressure_resumed" in kinds
    finally:
        chaos.stop()
        eng.stop()


def test_pool_squeeze_sheds_only_lowest_priority(monkeypatch):
    eng = _build_single_paged(monkeypatch, high=0.5, low=0.2, shed=0.6)
    eng.start()
    chaos = ServingChaos(eng)
    results = {}
    events = {p: threading.Event() for p in ("low", "high")}

    def mk(name):
        def _done(rid, toks, reason):
            results[name] = (reason, toks)
            events[name].set()
        return _done

    try:
        chaos.squeeze_pool(0.95)  # past the shed watermark
        eng.submit(GenRequest(
            prompt=[1, 2, 3], sampling=SamplingParams(max_new_tokens=4),
            priority=0, on_done=mk("low")))
        eng.submit(GenRequest(
            prompt=[4, 5, 6], sampling=SamplingParams(max_new_tokens=4),
            priority=3, on_done=mk("high")))
        # the LOW-priority request is shed (retryable); the high one
        # stays queued behind the pause
        assert events["low"].wait(20), "low-priority request never shed"
        assert results["low"][0] == "shed"
        assert is_retryable_reason("shed")
        assert not events["high"].is_set(), "shed the wrong priority"
        assert eng.metrics.counters["requests_shed"].value >= 1
        # heal: the high-priority request completes; the client retry of
        # the shed request (resubmit) also succeeds
        chaos.heal_pool()
        assert events["high"].wait(60), "high-priority never admitted"
        assert results["high"][0] == "length"
        events["low"].clear()
        eng.submit(GenRequest(
            prompt=[1, 2, 3], sampling=SamplingParams(max_new_tokens=4),
            priority=0, on_done=mk("low")))
        assert events["low"].wait(60), "shed request's retry hung"
        assert results["low"][0] == "length"
    finally:
        chaos.stop()
        eng.stop()


def test_retry_storm_trips_sentinel_retry_rate_slo():
    """The new retry_rate SLO: a flapping lane's migration requeues show
    up as an attributed sentinel alert (deterministic ingest-level
    drive, same style as test_slo_sentinel)."""
    from swarmdb_tpu.obs.sentinel import SLOConfig, SLOSentinel

    cfg = SLOConfig(window_s=10.0, warmup_windows=1, min_completions=4,
                    ttft_p95_s=1e9, queue_p95_s=1e9, cost_growth_x=1e9,
                    retry_rate=0.5, enabled=True)
    s = SLOSentinel(metrics=None, config=cfg)
    mk = lambda retried: {
        "completed": 10, "admitted": 10, "admission_waves": 5,
        "retried": retried, "retry_rate": retried / 10,
        "p95_ttft_s": 0.1, "p95_queue_wait_s": 0.05,
        "per_completion_ms": {"queue_wait": 1.0, "prefill": 2.0,
                              "decode": 3.0, "host_sync": 0.5},
    }
    assert s.ingest(mk(0)) is None          # baseline window
    assert s.baseline is not None
    assert s.ingest(mk(1)) is None          # 0.1 retries/completion: ok
    alert = s.ingest(mk(9))                 # 0.9 > 0.5: breach
    assert alert is not None
    assert any(b["slo"] == "retry_rate" and b["value"] == 0.9
               for b in alert["breaches"])
    assert alert["dominant"] in ("queue_wait", "prefill", "decode",
                                 "host_sync")
    # the gauge surface carries the window's retry rate
    assert any("swarmdb_slo_retry_rate" in ln
               for ln in s.prometheus_lines())


def test_group_stats_and_admin_surface(stack):
    g, sup, _ = stack
    wait_until(lambda: _healthy(sup), 60.0, what="lanes healthy")
    st = g.stats()
    assert st["lane_states"] == ["alive", "alive"]
    status = sup.status()
    assert status["config"]["retries"] == 2
    assert {l["lane"] for l in status["lanes"]} == {0, 1}
    assert status["lane_quarantines"] >= 1  # earlier tests injected kills
