"""Runtime page sanitizer tests (ISSUE 13 dynamic half).

The contract: with ``SWARMDB_PAGECHECK`` unset the factories return
the plain pool classes (zero overhead — type identity pinned here;
the bench echo A/B covers the serving path); with it set, every page
crime the serving stack could commit — double-free, write-after-free
(canary), stale table rows (epoch mismatch), cross-lane aliasing,
pin drift — is detected, named with owners, and dumped to
``pagecheck_<node>.json`` for the CI artifact scan.
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from swarmdb_tpu.ops.paged_kv import (CANARY_VALUE, PageAllocator,
                                      ShardedPageAllocator, canary_check,
                                      canary_fill, make_page_allocator,
                                      make_sharded_page_allocator,
                                      pages_per_slot)
from swarmdb_tpu.ops.prefix_cache import PrefixLRU, make_prefix_lru


@pytest.fixture()
def pagecheck_on(monkeypatch, tmp_path):
    """Enable the sanitizer with a scratch dump dir and a clean
    registry; always reset afterwards so deliberately-provoked
    violations never leak into the session-level zero-violation
    assertion (conftest.pytest_sessionfinish)."""
    monkeypatch.setenv("SWARMDB_PAGECHECK", "1")
    monkeypatch.setenv("SWARMDB_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("SWARMDB_NODE_ID", "testnode")
    from swarmdb_tpu.obs import pagecheck

    pagecheck.registry().reset()
    yield pagecheck
    pagecheck.registry().reset()


def test_factories_return_plain_types_when_off(monkeypatch):
    """The zero-overhead contract: flag off = the exact classes the
    callers constructed before the factories existed."""
    monkeypatch.delenv("SWARMDB_PAGECHECK", raising=False)
    assert type(make_page_allocator(8, 4, 16, 2)) is PageAllocator
    assert type(make_sharded_page_allocator(8, 2, 4, 16, 4)) \
        is ShardedPageAllocator
    assert type(make_prefix_lru(8, 4)) is PrefixLRU


def test_factories_return_checked_types_when_on(pagecheck_on):
    a = make_page_allocator(8, 4, 16, 2)
    assert type(a).__name__ == "CheckedPageAllocator"
    assert isinstance(a, PageAllocator)
    s = make_sharded_page_allocator(8, 2, 4, 16, 4)
    assert type(s).__name__ == "CheckedShardedPageAllocator"
    assert isinstance(s, ShardedPageAllocator)
    p = make_prefix_lru(8, 4, manage_free=False, pool=a)
    assert type(p).__name__ == "CheckedPrefixLRU"
    assert isinstance(p, PrefixLRU)
    # paged mode shares the allocator's pool shadow
    assert p.pagecheck.pool_id == a.pagecheck.pool_id


def test_double_free_detected_and_dumped(pagecheck_on, tmp_path):
    alloc = make_page_allocator(9, 4, 16, 2, label="dfree")
    taken = alloc.reserve(2)
    alloc.add_free(taken)
    alloc.add_free(taken)
    violations = pagecheck_on.registry().violations()
    assert [v["kind"] for v in violations] == ["double-free"]
    assert sorted(violations[0]["pages"]) == sorted(taken)
    # immediate SIGKILL-proof dump, not just atexit
    dump_path = tmp_path / "pagecheck_testnode.json"
    assert dump_path.exists()
    dump = json.loads(dump_path.read_text())
    assert dump["violations"][0]["kind"] == "double-free"
    assert any(p["pool"] == "dfree" for p in dump["pools"])


def test_cross_lane_aliasing_detected(pagecheck_on):
    """A resume-pages list captured on lane A replayed against lane
    B's allocator (the migration-replay hazard): the pages are live in
    A's pool but dead in B's — referencing them must fire."""
    lane_a = make_page_allocator(9, 4, 16, 2, label="laneA")
    lane_b = make_page_allocator(9, 4, 16, 2, label="laneB")
    lane_a.pagecheck.set_lane("lane0")
    lane_b.pagecheck.set_lane("lane1")
    row = lane_a.allocate(0, 2)
    assert row is not None
    pages = lane_a.pages_for(0)
    lane_a.transfer_to_cache(0, pages)      # rolling custody, lane A
    assert pagecheck_on.registry().violations() == []
    lane_b.allocate_with_prefix(0, pages, 1)     # replayed on lane B
    violations = pagecheck_on.registry().violations()
    assert [v["kind"] for v in violations] == ["stale-reference"]
    assert violations[0]["pool"] == "laneB"
    # ...while the same reference on lane A is legitimate
    pagecheck_on.registry().reset()
    lane_a2 = make_page_allocator(9, 4, 16, 2, label="laneA2")
    row = lane_a2.allocate(0, 2)
    pages = lane_a2.pages_for(0)
    lane_a2.transfer_to_cache(0, pages)
    assert lane_a2.allocate_with_prefix(1, pages, 1) is not None
    assert pagecheck_on.registry().violations() == []


def test_epoch_mismatch_on_stale_table_row(pagecheck_on):
    """A row stamped at allocation whose pages were freed and re-
    allocated to another slot before dispatch: validate_row must name
    the epoch move and the new owner."""
    alloc = make_page_allocator(5, 4, 8, 2, label="epoch")
    assert alloc.allocate(0, 2) is not None
    alloc.mark_retired(0)
    alloc.release_taken(alloc.take_pending_frees())
    assert alloc.allocate(1, 2) is not None      # same pages, new epoch
    alloc.pagecheck.set_owner(1, "rid-new")
    alloc.pagecheck.validate_row(0)              # slot 0's stale row
    violations = pagecheck_on.registry().violations()
    assert [v["kind"] for v in violations] == ["epoch-mismatch"]
    assert "rid-new" in violations[0]["message"]


def test_canary_detects_write_after_free(pagecheck_on):
    """The ASan move: freed pages are poisoned; a write landing while
    they are free is caught at re-allocation even though every host-
    side custody transition looked legal."""
    alloc = make_page_allocator(9, 4, 16, 2, label="canary")
    k = jnp.zeros((1, 9, 4, 1, 2), jnp.float32)
    v = jnp.zeros_like(k)
    assert alloc.allocate(0, 2) is not None
    pages = alloc.pages_for(0)
    alloc.mark_retired(0)
    alloc.release_taken(alloc.take_pending_frees())
    k, v = canary_fill(k, v, pages)
    alloc.pagecheck.mark_poisoned(pages)
    assert canary_check(k, v, pages) == []       # intact while untouched
    k = k.at[:, pages[0], 1].set(0.5)            # one rogue element
    bad = canary_check(k, v, alloc.pagecheck.poisoned_pages(pages))
    assert bad == [pages[0]]
    alloc.pagecheck.canary_violation(bad)
    kinds = {vv["kind"] for vv in pagecheck_on.registry().violations()}
    assert kinds == {"canary"}


def test_pin_discipline_violations(pagecheck_on):
    alloc = make_page_allocator(9, 4, 16, 2, label="pins")
    prefix = make_prefix_lru(9, 4, manage_free=False, pool=alloc)
    assert alloc.allocate(0, 2) is not None
    pages = alloc.pages_for(0)
    alloc.transfer_to_cache(0, pages)
    prefix.pin(pages)
    # freeing a pinned page: an active slot still reads it
    alloc.add_free([pages[0]])
    kinds = [v["kind"] for v in pagecheck_on.registry().violations()]
    assert kinds == ["free-pinned"]
    # unpin drift: more unpins than pins
    prefix.unpin([pages[1]])
    prefix.unpin([pages[1]])
    kinds = [v["kind"] for v in pagecheck_on.registry().violations()]
    assert kinds == ["free-pinned", "unpin-unpinned"]


def test_analyzer_lists_pagecheck_dumps_next_to_flight_dumps(
        pagecheck_on, tmp_path):
    """obs/analyze.py: a pagecheck dump sitting beside the analyzed
    trace shows up in the report with its violation count/kinds — a
    detected use-after-free is never invisible in a report."""
    alloc = make_page_allocator(9, 4, 16, 2, label="analyze")
    taken = alloc.reserve(1)
    alloc.add_free(taken)
    alloc.add_free(taken)                        # seeded double-free
    assert (tmp_path / "pagecheck_testnode.json").exists()

    from swarmdb_tpu.obs.analyze import _synthetic_trace, analyze_files

    trace_path = tmp_path / "trace.json"
    trace_path.write_text(json.dumps(
        {"traceEvents": _synthetic_trace(5.0, 10.0, 20.0)}))
    report = analyze_files([str(trace_path)])
    dumps = report.get("pagecheck_dumps")
    assert dumps and dumps[0]["violations"] == 1
    assert dumps[0]["node"] == "testnode"
    assert dumps[0]["violation_kinds"] == ["double-free"]


def test_prometheus_lines_and_report(pagecheck_on):
    alloc = make_page_allocator(9, 4, 16, 2, label="prom")
    alloc.pagecheck.set_lane("lane7")
    assert alloc.allocate(0, 2) is not None
    lines = pagecheck_on.registry().prometheus_lines()
    text = "\n".join(lines)
    assert "swarmdb_page_violations_total 0" in text
    assert 'swarmdb_page_state{state="owned"} 2' in text
    assert 'swarmdb_page_churn_allocated_total{lane="lane7"} 2' in text
    report = pagecheck_on.registry().report()
    assert report["enabled"] is True
    pool = next(p for p in report["pools"] if p["pool"] == "prom")
    assert pool["lane"] == "lane7"
    assert pool["states"]["owned"] == 2


def test_churn_counters_are_flag_independent(monkeypatch):
    """The /metrics page-churn counters read plain allocator stats —
    they must tick with the sanitizer off."""
    monkeypatch.delenv("SWARMDB_PAGECHECK", raising=False)
    alloc = make_page_allocator(9, 4, 16, 2)
    assert type(alloc) is PageAllocator
    assert alloc.allocate(0, 3) is not None
    alloc.mark_retired(0)
    alloc.release_taken(alloc.take_pending_frees())
    s = alloc.stats()
    assert s["pages_allocated_total"] == 3
    assert s["pages_freed_total"] == 3


# ---------------------------------------------------------------------------
# engine end-to-end under the sanitizer


def _tiny_paged_engine(label):
    from paged_engine import paged_engine
    from swarmdb_tpu.models import llama
    from swarmdb_tpu.models.configs import TINY_DEBUG

    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    max_batch, max_seq, ps = 4, 96, 16
    num_pages = 1 + 4 * pages_per_slot(max_seq, ps)
    alloc = make_page_allocator(num_pages, ps, max_seq, max_batch,
                                label=label)
    eng = paged_engine(cfg, params, max_batch=max_batch, max_seq=max_seq,
                       page_size=ps, num_pages=num_pages, allocator=alloc,
                       eos_id=2, seed=0, prefill_buckets=[16, 32, 64])
    eng.start()
    return eng, alloc, num_pages


def test_engine_clean_under_sanitizer(pagecheck_on):
    """The serving loop itself commits no page crimes: generations are
    normal, shadow state stays consistent, the canary verify runs on
    every re-allocation, zero violations."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    eng, alloc, _num_pages = _tiny_paged_engine("engine-clean")
    try:
        assert eng._pagecheck is not None
        sp = SamplingParams(max_new_tokens=8)
        for i in range(3):
            toks, reason = eng.generate_sync([i + 1] * 4, sp)
            assert reason in ("length", "eos")
        time.sleep(0.2)
        assert pagecheck_on.registry().violations() == []
        report = pagecheck_on.registry().report()
        pool = next(p for p in report["pools"]
                    if p["pool"] == "engine-clean")
        assert pool["churn_allocated"] >= 4
        assert pool["churn_freed"] >= 2
        states = pool["states"]
        assert states.get("trash") == 1
        assert states.get("owned", 0) + states.get("free", 0) \
            + states.get("cached", 0) == pool["num_pages"] - 1
    finally:
        eng.stop()


def test_engine_canary_fires_on_rogue_write(pagecheck_on):
    """Seed a real write-after-free INTO the device pool between two
    admission rounds: the next time the page is handed out, the
    sanitizer's canary verify must fire (and dump)."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    eng, alloc, num_pages = _tiny_paged_engine("engine-canary")
    try:
        sp = SamplingParams(max_new_tokens=8)

        def pair(tag):
            ts = [threading.Thread(target=eng.generate_sync,
                                   args=([tag + i] * 4, sp))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()

        pair(1)                      # 4 pages at once
        eng.generate_sync([9] * 4, sp)   # reclaim 4, reuse 2
        time.sleep(0.2)
        pool_shadow = pagecheck_on.registry()._pools[
            alloc.pagecheck.pool_id]
        poisoned = [p for p in range(1, num_pages)
                    if pool_shadow.pages[p].poisoned]
        assert poisoned, "expected lingering poisoned pages"
        rogue = poisoned[0]
        eng.cache["k"] = eng.cache["k"].at[:, rogue].set(3.14159)
        for i in range(6):
            pair(20 + 2 * i)
            if any(v["kind"] == "canary"
                   for v in pagecheck_on.registry().violations()):
                break
        kinds = {v["kind"]
                 for v in pagecheck_on.registry().violations()}
        assert "canary" in kinds
        bad = next(v for v in pagecheck_on.registry().violations()
                   if v["kind"] == "canary")
        assert rogue in bad["pages"]
    finally:
        eng.stop()


def test_flag_off_engine_has_no_sanitizer_hooks(monkeypatch):
    """Flag off: the engine's _pagecheck attr is None (one attr read
    at init is the entire overhead) and the allocator is the plain
    class."""
    monkeypatch.delenv("SWARMDB_PAGECHECK", raising=False)
    eng, alloc, _ = _tiny_paged_engine("off")
    try:
        assert type(alloc) is PageAllocator
        assert eng._pagecheck is None
    finally:
        eng.stop()
