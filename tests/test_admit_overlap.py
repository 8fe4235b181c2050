"""Per-shard admission lanes + device-resident decode (ISSUE 8).

Covers the tentpole contracts end to end on CPU virtual devices:

- ``build_serving_engine(paged=True)`` on a pure-DP mesh now yields a
  :class:`ShardLaneGroup` (one single-device engine per shard) unless
  ``admit_overlap=False`` / SWARMDB_ADMIT_OVERLAP=0 pins the GSPMD path.
- Routing: shard hints pin conversations to lanes; lanes produce
  identical greedy tokens (params are replicated).
- Overlap: under concurrent load, admission waves dispatch while sibling
  lanes decode (``engine_admission_overlap_steps``).
- Host-sync contract: a completed STREAMED request on the paged path
  spans <= 3 sanctioned host syncs (admit + session drain + final),
  recorded per request in the flight timelines — vs one sync per decode
  chunk on the scan path.
- The BENCH_r05 priority-0 starvation regression check
  (p50-TTFT-monotone under load) extended to the overlapped-admission
  path, per-lane aging included.
"""

import statistics
import threading
import time

import pytest

import jax

from swarmdb_tpu.backend.engine import GenRequest
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.parallel.lanes import ShardLaneGroup
from swarmdb_tpu.parallel.mesh import make_mesh
from swarmdb_tpu.parallel.serving import build_serving_engine


@pytest.fixture(scope="module")
def group():
    g, info = build_serving_engine(
        get_config("tiny-debug"), make_mesh(8, data=8, model=1, expert=1),
        max_batch=16, max_seq=64, paged=True, page_size=8,
    )
    assert isinstance(g, ShardLaneGroup)
    assert info.data_size == 8 and info.cfg.name == "tiny-debug"
    g.start()
    yield g
    g.stop()


def test_group_shape_and_facade(group):
    assert len(group.lanes) == 8
    assert group.max_batch == 16
    assert group.paged.allocator.n_shards == 8
    assert group.paged.allocator.stats()["num_pages"] > 0
    # every lane runs the device-resident session path on its own device
    devs = set()
    for e in group.lanes:
        assert e._resident_variants is not None
        devs.add(next(iter(jax.tree_util.tree_leaves(e.params)[0]
                           .devices())))
    assert len(devs) == 8, "lanes must be pinned to distinct devices"


def test_lanes_generate_identical_greedy_tokens(group):
    """Params are replicated across lanes (the definition of DP), so the
    same prompt routed to different lanes must decode identically."""
    prompt = [1, 5, 9, 13]
    outs = []
    for hint in (0, 3, 7):
        done = threading.Event()
        res = {}

        def on_done(rid, toks, reason, _r=res, _d=done):
            _r["toks"] = toks
            _d.set()

        group.submit(GenRequest(
            prompt=prompt, sampling=SamplingParams(max_new_tokens=6),
            on_done=on_done, shard_hint=hint))
        assert done.wait(120)
        outs.append(res["toks"])
    assert outs[0] == outs[1] == outs[2], outs


def test_lanes_compute_on_their_own_device(group):
    """A lane's programs run on the lane's device, not the process
    default: after traffic, what the programs WROTE (pool, fed-token
    vector) still lives, committed, on the device the weights are on.
    Arrays merely created under ``jax.default_device`` are uncommitted,
    and jit then computes every lane on device 0 — which virtual CPU
    devices tolerate and four real chips refuse (out of memory on the
    first; PR 22's four-chip run)."""
    mesh_devices = list(group.info.mesh.devices.flat)
    for hint, lane in enumerate(group.lanes):
        done = threading.Event()
        group.submit(GenRequest(
            prompt=[1, 5, 9, 13], sampling=SamplingParams(max_new_tokens=6),
            on_done=lambda *_a, _d=done: _d.set(), shard_hint=hint))
        assert done.wait(120)
        for _attempt in range(50):
            state = (lane.params, lane.cache, lane._last_tokens,
                     lane._last_lps)
            try:
                placed = [(leaf.committed, leaf.devices())
                          for leaf in jax.tree_util.tree_leaves(state)]
                break
            except RuntimeError:    # the lane's loop just donated one
                time.sleep(0.05)
        assert placed == [(True, {mesh_devices[hint]})] * len(placed), hint


def test_shard_hint_routes_to_lane(group):
    before = [e.total_requests for e in group.lanes]
    done = threading.Event()
    group.submit(GenRequest(
        prompt=[2, 4], sampling=SamplingParams(max_new_tokens=2),
        on_done=lambda *a: done.set(), shard_hint=5))
    assert done.wait(60)
    after = [e.total_requests for e in group.lanes]
    assert after[5] == before[5] + 1, (before, after)
    assert sum(after) == sum(before) + 1


def test_admission_overlaps_sibling_decode(group):
    """The tentpole property: waves admitted while a SIBLING lane's
    decode session is in flight. A global-wave engine can never count
    one of these."""
    c = group.metrics.counters["engine_admission_overlap_steps"]
    before = c.value
    done = threading.Event()
    lock = threading.Lock()
    n = 32
    left = [n]

    def on_done(rid, toks, reason):
        with lock:
            left[0] -= 1
            if left[0] == 0:
                done.set()

    for i in range(n):
        group.submit(GenRequest(
            prompt=[1, 3 + (i % 40)],
            sampling=SamplingParams(max_new_tokens=8),
            on_done=on_done, shard_hint=i))
    assert done.wait(300), f"{left[0]} of {n} never completed"
    assert c.value > before, "no admission wave overlapped a sibling " \
                             "lane's decode session"


def test_streamed_request_host_syncs_leq_3(group):
    """Acceptance: host syncs per completed STREAMED request <= 3 on the
    paged path (was one per decode chunk), from the flight timeline —
    the operator-visible evidence path."""
    toks = []
    done = threading.Event()
    req = GenRequest(
        prompt=[1, 2, 3],
        sampling=SamplingParams(max_new_tokens=32),  # 4+ chunks at K=8
        on_token=lambda rid, t: toks.append(t),
        on_done=lambda *a: done.set(),
        shard_hint=1,
    )
    rid = group.submit(req)
    assert done.wait(120)
    assert len(toks) >= 16, "not a streamed multi-chunk request"
    rec = next(r for r in reversed(group.flight.requests())
               if r["rid"] == rid)
    assert rec["host_syncs"] <= 3, rec
    assert rec["generated"] == len(toks)


def test_loaded_p50_ttft_monotone_overlapped(group):
    """BENCH_r05 satellite, extended to the overlapped-admission path:
    under a loaded queue spread across per-shard lanes, higher priority
    must show NO WORSE p50 TTFT (per-lane strict priority + aging)."""
    done = threading.Event()
    lock = threading.Lock()
    finished = [0]
    total = 48

    def on_done(rid, toks, reason):
        with lock:
            finished[0] += 1
            if finished[0] == total:
                done.set()

    reqs = []
    for i in range(total):
        reqs.append(GenRequest(
            prompt=[1, 10 + i], sampling=SamplingParams(max_new_tokens=4),
            priority=i % 4, on_done=on_done))
        # conversation-stable hints, all four priorities in every lane
        reqs[-1].shard_hint = i // 4
    for r in reqs:  # constructed first: near-identical submitted_at
        group.submit(r)
    assert done.wait(300), f"only {finished[0]}/{total} completed"

    rid2prio = {r.request_id: r.priority for r in reqs}
    ttfts = {p: [] for p in range(4)}
    for rec in group.flight.requests():
        prio = rid2prio.get(rec["rid"])
        if prio is None:
            continue
        first = rec["first_token_at"] or rec["retired_at"]
        ttfts[prio].append(first - rec["submitted_at"])
    p50 = {p: statistics.median(v) for p, v in ttfts.items() if v}
    assert set(p50) == {0, 1, 2, 3}, p50
    tol = 0.3  # co-admitted waves share one prefill dispatch
    for hi in range(1, 4):
        for lo in range(hi):
            assert p50[hi] <= p50[lo] + tol, (p50, ttfts)


def test_group_restart_revives_only_dead_lanes(group):
    lane = group.lanes[2]
    lane.stop()
    assert not group.alive()
    threads_before = [e._thread for e in group.lanes]
    group.restart()
    assert group.alive()
    # healthy lanes kept their decode threads; lane 2 got a fresh one
    for i, e in enumerate(group.lanes):
        if i != 2:
            assert e._thread is threads_before[i]
    done = threading.Event()
    group.submit(GenRequest(prompt=[5, 6],
                            sampling=SamplingParams(max_new_tokens=2),
                            on_done=lambda *a: done.set(), shard_hint=2))
    assert done.wait(60), "restarted lane does not serve"


def test_leadership_repin_replay_bit_identical(group):
    """ISSUE 14 satellite: a leadership move re-pins a conversation to a
    different lane (backend/locality.py derives the lane from
    (partition, leader)), and greedy decode replayed on the new lane is
    BIT-IDENTICAL to the old one — the lane-group half of the PR 8
    migration proof, applied to leadership-driven re-pinning."""
    from swarmdb_tpu.backend.locality import ConversationLocality
    from swarmdb_tpu.ha import tp_key

    leadership = {"t:0": {"leader": "node-a", "epoch": 1}}
    locality = ConversationLocality(
        topic="t", n_lanes=len(group.lanes),
        leadership=lambda key: leadership.get(key),
        num_partitions=lambda: 1)

    def serve(pin):
        done = threading.Event()
        res = {}

        def on_done(rid, toks, reason, _r=res, _d=done):
            _r["toks"], _r["reason"] = toks, reason
            _d.set()

        group.submit(GenRequest(
            prompt=[2, 7, 11, 3], sampling=SamplingParams(max_new_tokens=8),
            on_done=on_done, shard_hint=pin.lane))
        assert done.wait(120)
        assert res["reason"] in ("length", "eos")
        return res["toks"]

    pin_before = locality.pin("user", "agent-x")
    assert pin_before.leader == "node-a"
    toks_before = serve(pin_before)

    # failover: a new leader seats at a higher epoch; the re-pin is
    # deterministic and (for some leader) lands on a DIFFERENT lane
    new_leader = next(
        f"node-{i}" for i in range(64)
        if locality._lane_for(0, f"node-{i}") != pin_before.lane)
    leadership["t:0"] = {"leader": new_leader, "epoch": 2}
    locality.on_rebalance(tp_key("t", 0), leadership["t:0"])
    pin_after = locality.pin("user", "agent-x")
    assert pin_after.leader == new_leader
    assert pin_after.lane != pin_before.lane
    assert locality.stats()["repins"] == 1

    toks_after = serve(pin_after)
    assert toks_after == toks_before, (
        "greedy replay across a leadership re-pin must be bit-identical")


def test_gspmd_path_still_available():
    """SWARMDB_ADMIT_OVERLAP=0 semantics: admit_overlap=False returns
    the single-program GSPMD engine (the packed-prefill path the
    multichip dry run asserts on)."""
    from swarmdb_tpu.backend.engine import Engine

    engine, sm = build_serving_engine(
        get_config("tiny-debug"), make_mesh(8, data=8, model=1, expert=1),
        max_batch=16, max_seq=64, paged=True, page_size=8,
        admit_overlap=False,
    )
    assert isinstance(engine, Engine)
    assert engine.paged.allocator.n_shards == 8
    assert engine._packed_active()
    # sharded multi-device engines never take the resident-session path
    assert engine._resident_variants is None
