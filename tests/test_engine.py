"""Continuous-batching engine + sampling tests (tiny model, CPU)."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmdb_tpu.backend.engine import Engine, GenRequest
from swarmdb_tpu.backend.sampling import SamplingParams, make_slot_keys, sample_tokens
from swarmdb_tpu.models import llama
from swarmdb_tpu.models.configs import TINY_DEBUG


@pytest.fixture(scope="module")
def engine():
    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(
        lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c),
        lambda b, s: llama.init_kv_cache(cfg, b, s),
        params,
        max_batch=4, max_seq=96, eos_id=2, seed=0,
        prefill_buckets=[16, 32, 64],
    )
    eng.start()
    yield eng
    eng.stop()


def test_greedy_generation_deterministic(engine):
    toks1, r1 = engine.generate_sync([1, 5, 9], SamplingParams(max_new_tokens=8))
    toks2, r2 = engine.generate_sync([1, 5, 9], SamplingParams(max_new_tokens=8))
    assert toks1 == toks2
    assert r1 in ("length", "eos") and len(toks1) <= 8


def test_streaming_callbacks_and_order(engine):
    got = []
    done = threading.Event()
    req = GenRequest(
        prompt=[1, 7],
        sampling=SamplingParams(max_new_tokens=5),
        on_token=lambda rid, t: got.append(t),
        on_done=lambda rid, toks, reason: done.set(),
    )
    engine.submit(req)
    assert done.wait(60)
    # tokens streamed == tokens a greedy rerun of the same prompt returns
    toks, _ = engine.generate_sync([1, 7], SamplingParams(max_new_tokens=5))
    assert got == toks


def test_concurrent_requests_fill_slots(engine):
    """More requests than slots: all must complete via continuous batching."""
    results = {}
    done = threading.Event()
    lock = threading.Lock()
    n = 10  # > max_batch=4

    def mk(i):
        def on_done(rid, toks, reason):
            with lock:
                results[i] = (toks, reason)
                if len(results) == n:
                    done.set()
        return on_done

    for i in range(n):
        engine.submit(GenRequest(
            prompt=[1, 3 + i], sampling=SamplingParams(max_new_tokens=6),
            on_done=mk(i)))
    assert done.wait(120), f"only {len(results)}/{n} completed"
    assert all(len(t) <= 6 for t, _ in results.values())
    # batched results must equal solo runs (slot isolation)
    solo, _ = engine.generate_sync([1, 3], SamplingParams(max_new_tokens=6))
    assert results[0][0] == solo


def test_priority_admission(engine):
    """When the queue is backed up, CRITICAL requests are admitted first."""
    order = []
    lock = threading.Lock()
    all_done = threading.Event()
    total = 8

    def mk(tag):
        def on_done(rid, toks, reason):
            with lock:
                order.append(tag)
                if len(order) == total:
                    all_done.set()
        return on_done

    # fill all 4 slots with long generations, then queue low+high
    for i in range(4):
        engine.submit(GenRequest(prompt=[1, 50 + i],
                                 sampling=SamplingParams(max_new_tokens=30),
                                 priority=1, on_done=mk(f"fill{i}")))
    time.sleep(0.2)  # let fills occupy slots
    for i in range(2):
        engine.submit(GenRequest(prompt=[1, 80 + i],
                                 sampling=SamplingParams(max_new_tokens=2),
                                 priority=0, on_done=mk(f"low{i}")))
    for i in range(2):
        engine.submit(GenRequest(prompt=[1, 90 + i],
                                 sampling=SamplingParams(max_new_tokens=2),
                                 priority=3, on_done=mk(f"crit{i}")))
    assert all_done.wait(180)
    crit_pos = [order.index(f"crit{i}") for i in range(2)]
    low_pos = [order.index(f"low{i}") for i in range(2)]
    assert max(crit_pos) < max(low_pos), order


def test_loaded_p50_ttft_monotone_with_priority(engine):
    """ISSUE 2 satellite (BENCH_r05 p50_ttft_by_priority): under a loaded
    queue, higher priority must show NO WORSE p50 TTFT. Measured from the
    flight recorder's request timelines — the same evidence path an
    operator reads — not ad-hoc callback bookkeeping."""
    import statistics

    done = threading.Event()
    lock = threading.Lock()
    finished = [0]
    total = 32

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
    for r in reqs:  # all constructed first: near-identical submitted_at
        engine.submit(r)
    assert done.wait(240), f"only {finished[0]}/{total} completed"

    rid2prio = {r.request_id: r.priority for r in reqs}
    ttfts = {p: [] for p in range(4)}
    for rec in engine.flight.requests():
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


def test_age_queue_promotes_starved_low_priority():
    """Priority aging (the BENCH_r05 starvation fix): a LOW request that
    has waited >= 2 * aging_s competes two classes higher — outranking a
    younger NORMAL — while its own priority field never mutates.
    Deterministic heap-level check; no decode needed."""
    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(
        lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c),
        lambda b, s: llama.init_kv_cache(cfg, b, s),
        params, max_batch=2, max_seq=64, seed=0,
        prefill_buckets=[16], aging_s=5.0)
    old_low = GenRequest(prompt=[1, 2], priority=0)
    old_low.submitted_at = time.time() - 11.0  # two class bumps earned
    fresh_normal = GenRequest(prompt=[1, 3], priority=1)
    eng.submit(old_low)
    eng.submit(fresh_normal)
    with eng._cv:
        assert eng._queue[0][3] is fresh_normal  # strict priority order
    eng._age_queue()
    with eng._cv:
        assert eng._queue[0][3] is old_low  # aged to class 2 > NORMAL
    assert old_low.priority == 0  # original priority untouched
    assert eng.metrics.counters["engine_priority_aged"].value == 1
    # idempotent: a second pass with no further wait changes nothing
    eng._age_queue()
    with eng._cv:
        assert eng._queue[0][3] is old_low
    # aging disabled => strict priority preserved
    eng2 = Engine(
        lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c),
        lambda b, s: llama.init_kv_cache(cfg, b, s),
        params, max_batch=2, max_seq=64, seed=0,
        prefill_buckets=[16], aging_s=0)
    old2 = GenRequest(prompt=[1, 2], priority=0)
    old2.submitted_at = time.time() - 100.0
    new2 = GenRequest(prompt=[1, 3], priority=1)
    eng2.submit(old2)
    eng2.submit(new2)
    eng2._age_queue()
    with eng2._cv:
        assert eng2._queue[0][3] is new2


def test_prompt_too_long_rejected(engine):
    with pytest.raises(ValueError):
        engine.submit(GenRequest(prompt=list(range(96))))


def test_stats_shape(engine):
    s = engine.stats()
    assert {"active_slots", "queued", "total_requests",
            "tokens_per_sec_60s"} <= set(s)


def test_sample_tokens_greedy_vs_temperature():
    logits = jnp.asarray(np.array([[0.0, 5.0, 1.0], [9.0, 0.0, 0.0]], np.float32))
    keys = make_slot_keys(0, 2)
    pos = jnp.array([3, 4], jnp.int32)
    greedy = sample_tokens(logits, keys, pos,
                           jnp.zeros(2), jnp.zeros(2, jnp.int32), jnp.ones(2))
    assert list(np.asarray(greedy)) == [1, 0]
    # temperature sampling is deterministic given (key, position)
    t = jnp.full(2, 1.0)
    s1 = sample_tokens(logits, keys, pos, t, jnp.zeros(2, jnp.int32), jnp.ones(2))
    s2 = sample_tokens(logits, keys, pos, t, jnp.zeros(2, jnp.int32), jnp.ones(2))
    assert list(np.asarray(s1)) == list(np.asarray(s2))


def test_sample_tokens_topk1_is_greedy():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 50)).astype(np.float32))
    keys = make_slot_keys(7, 4)
    pos = jnp.arange(4, dtype=jnp.int32)
    out = sample_tokens(logits, keys, pos,
                        jnp.full(4, 2.0), jnp.full(4, 1, jnp.int32), jnp.ones(4))
    assert list(np.asarray(out)) == list(np.asarray(jnp.argmax(logits, -1)))


def test_sample_tokens_top_p_restricts():
    # one dominant logit, top_p tiny -> always that token
    logits = jnp.asarray(np.array([[10.0] + [0.0] * 9], np.float32))
    keys = make_slot_keys(3, 1)
    out = sample_tokens(logits, keys, jnp.array([0], jnp.int32),
                        jnp.ones(1), jnp.zeros(1, jnp.int32),
                        jnp.full(1, 0.01))
    assert int(out[0]) == 0


def test_chunked_engine_matches_stepwise():
    """Two-segment chunked decode (frozen cache + per-chunk K/V buffer,
    Engine chunked_fns) must produce token-identical greedy output to the
    per-step cache-threading path."""
    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    fwd = lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c)
    init_cache = lambda b, s: llama.init_kv_cache(cfg, b, s)
    chunked = (
        lambda p, t, pos, c, hkv, s: llama.forward_chunked(
            p, cfg, t, pos, c, hkv, s),
        lambda b, k: llama.init_chunk_kv(cfg, b, k),
        llama.merge_chunk,
    )
    outs = {}
    for name, fns in (("plain", None), ("chunked", chunked)):
        eng = Engine(fwd, init_cache, params, max_batch=4, max_seq=96,
                     eos_id=2, seed=0, prefill_buckets=[16, 32],
                     decode_chunk=4, chunked_fns=fns)
        eng.start()
        try:
            # long enough to span several chunks; two prompts so slots
            # decode at different positions (exercises per-row masking)
            outs[name] = [
                eng.generate_sync([1, 5, 9], SamplingParams(max_new_tokens=13)),
                eng.generate_sync([3, 2, 8, 4, 6], SamplingParams(max_new_tokens=9)),
            ]
        finally:
            eng.stop()
    assert outs["plain"] == outs["chunked"]


def test_chunked_engine_sampling_variants():
    """Filtered / fast / greedy chunk variants must agree where semantics
    overlap: greedy requests produce identical tokens whichever compiled
    variant serves the population."""
    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(4))
    eng = Engine(
        lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c),
        lambda b, s: llama.init_kv_cache(cfg, b, s),
        params, max_batch=4, max_seq=96, eos_id=2, seed=0,
        prefill_buckets=[16], decode_chunk=4,
    )
    eng.start()
    try:
        # all-greedy population -> _decode_greedy variant
        greedy_only, _ = eng.generate_sync([1, 2, 3],
                                           SamplingParams(max_new_tokens=8))
        # mixed population: a top-k request forces the filtered variant
        # while the greedy request is in flight
        done = threading.Event()
        res = {}
        eng.submit(GenRequest(
            prompt=[4, 4, 4],
            sampling=SamplingParams(temperature=0.9, top_k=5,
                                    max_new_tokens=8),
            on_done=lambda rid, t, r: done.set(),
        ))
        mixed, _ = eng.generate_sync([1, 2, 3],
                                     SamplingParams(max_new_tokens=8))
        assert done.wait(60)
        assert mixed == greedy_only
    finally:
        eng.stop()


def test_pipeline_depths_token_identical():
    """Dispatch-ahead pipelining (depth 2) must not change any sampled
    token vs lockstep (depth 1): dispatch order and device state are
    identical, only host read timing moves. Exercises slot reuse across
    in-flight chunks (more requests than slots, short replies)."""
    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(6))
    outs = {}
    for depth in (1, 2):
        eng = Engine(
            lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c),
            lambda b, s: llama.init_kv_cache(cfg, b, s),
            params, max_batch=2, max_seq=96, eos_id=-1, seed=0,
            prefill_buckets=[16], decode_chunk=4, pipeline_depth=depth,
        )
        eng.start()
        try:
            results = {}
            done = threading.Event()
            n = 6  # 3x the slot count -> forced mid-flight reuse

            def mk(i):
                def on_done(rid, toks, reason):
                    results[i] = toks
                    if len(results) == n:
                        done.set()
                return on_done

            for i in range(n):
                eng.submit(GenRequest(
                    prompt=[1 + i, 5, 9],
                    sampling=SamplingParams(max_new_tokens=7),
                    on_done=mk(i),
                ))
            assert done.wait(120)
            outs[depth] = [results[i] for i in range(n)]
        finally:
            eng.stop()
    assert outs[1] == outs[2]


def test_warmup_covers_all_variants():
    """After Engine.warmup(), serving traffic must hit ZERO new compiles —
    round 3's bench collapse was prompts graduating into uncompiled
    buckets mid-window. Asserted via the jit caches' entry counts."""
    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    eng = Engine(
        lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c),
        lambda b, s: llama.init_kv_cache(cfg, b, s),
        params, max_batch=4, max_seq=96, eos_id=-1, seed=0,
        prefill_buckets=[16, 32, 64], decode_chunk=4,
    )
    eng.warmup()
    pre_prefill = eng._prefill_fused._cache_size()
    pre_decode = sum(d._cache_size() for d in eng._decode_variants)
    # one variant per bucket (incl. the auto-appended max_seq-1 bucket)
    assert pre_prefill == len(eng.prefill_buckets)
    eng.start()
    try:
        # traffic across every bucket (length 10 -> 16, 30 -> 32, 60 -> 64)
        # and both greedy + sampled populations
        for n, temp in ((10, 0.0), (30, 0.7), (60, 0.0), (90, 0.0)):
            toks, reason = eng.generate_sync(
                list(range(1, n + 1)),
                SamplingParams(max_new_tokens=3, temperature=temp),
            )
            assert reason in ("length", "eos")
    finally:
        eng.stop()
    assert eng._prefill_fused._cache_size() == pre_prefill
    assert sum(d._cache_size() for d in eng._decode_variants) == pre_decode


def test_default_bucket_ladder_scales_with_max_seq():
    """Long-context engines use the x4 ladder: every bucket is a compiled
    XLA variant (a quarter of a minute or more each at 8B widths), and
    the x2 ladder at S=1024 put enough compiles in warmup to exceed the bench
    watchdog. Short-context engines keep the fine x2 ladder."""
    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(0))

    def make(max_seq):
        return Engine(
            lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c),
            lambda b, s: llama.init_kv_cache(cfg, b, s),
            params, max_batch=2, max_seq=max_seq, eos_id=2,
        )

    assert make(256).prefill_buckets == [16, 32, 64, 128, 256]
    assert make(1024).prefill_buckets == [64, 256, 1024]
    # the largest admissible prompt (max_seq - 1) must always fit, and the
    # auto-appended top bucket is max_seq itself (stays tile/page aligned)
    assert make(96).prefill_buckets == [16, 32, 64, 96]
    assert make(600).prefill_buckets == [64, 256, 600]


def test_precompile_plan_matches_warmup():
    """warmup_call_plan() must cover exactly the variants warmup()
    executes (3 decode samplers + one prefill per bucket + one prefix
    prefill per bucket x PP width) and every entry must AOT-lower:
    precompile() races these through .lower().compile() threads to fill
    the persistent XLA cache ahead of sequential warmup."""
    from swarmdb_tpu.models.configs import TINY_DEBUG as cfg

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    fwd = lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c)
    init_cache = lambda b, s: llama.init_kv_cache(cfg, b, s)

    # dense, no prefix: 3 decode + |buckets|
    eng = Engine(fwd, init_cache, params, max_batch=2, max_seq=64,
                 eos_id=2, prefill_buckets=[8, 16])
    plan = eng.warmup_call_plan()
    assert len(plan) == 3 + len(eng.prefill_buckets)
    assert eng.precompile(parallel=2) >= 0.0
    eng.warmup()  # state untouched by precompile: executes cleanly

    # dense + prefix cache: adds |buckets| x |PP widths|
    peng = Engine(
        fwd, init_cache, params, max_batch=2, max_seq=64, eos_id=2,
        prefill_buckets=[8, 16],
        prefix_fns=(
            lambda p, t, tab, pl, pk, pv, lp, logits_at=None:
                llama.forward_prefix_lane(p, cfg, t, tab, pl, pk, pv,
                                          lp, logits_at=logits_at),
            lambda n, ps: llama.init_prefix_pool(cfg, n, ps),
        ),
        prefix_pages=4, prefix_page_size=8,
    )
    pplan = peng.warmup_call_plan()
    expect = (3 + len(peng.prefill_buckets)
              + len(peng.prefill_buckets) * len(peng._prefix_pp_buckets))
    assert len(pplan) == expect
    for fn, specs in pplan:
        fn.lower(*specs)  # type-checks every prefix variant


def test_precompile_cache_covers_warmup(compile_cache_dir):
    """End-to-end drift guard for warmup_call_plan(): with the persistent
    XLA cache on, precompile() must leave warmup() with ZERO new cache
    entries — any spec/shape/dtype/arg-order/donation mismatch between
    the plan and warmup's real calls shows up as a fresh compile here.
    Covers the paged branches the inline-lowering test cannot."""
    from paged_engine import paged_engine
    import swarmdb_tpu.utils.xla_cache as xla_cache

    cfg = TINY_DEBUG
    cache_dir = compile_cache_dir
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    fwd = lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c)
    init_cache = lambda b, s: llama.init_kv_cache(cfg, b, s)

    dense = Engine(
        fwd, init_cache, params, max_batch=2, max_seq=64, eos_id=2,
        prefill_buckets=[8],
        prefix_fns=(
            lambda p, t, tab, pl, pk, pv, lp, logits_at=None:
                llama.forward_prefix_lane(p, cfg, t, tab, pl, pk, pv,
                                          lp, logits_at=logits_at),
            lambda n, ps: llama.init_prefix_pool(cfg, n, ps),
        ),
        prefix_pages=4, prefix_page_size=8,
    )
    ps, num_pages = 8, 17  # 2 rows x 8 pages/row + trash
    paged = paged_engine(
        cfg, params, max_batch=2, max_seq=64, page_size=ps,
        num_pages=num_pages, prefix=True, eos_id=2, prefill_buckets=[8])
    for eng in (dense, paged):
        eng.precompile(parallel=2)
    before = xla_cache.persistent_cache_programs(str(cache_dir))
    assert before, "precompile wrote nothing to the persistent cache"
    for eng in (dense, paged):
        eng.warmup()
    after = xla_cache.persistent_cache_programs(str(cache_dir))
    assert after == before, (
        f"warmup compiled {len(after - before)} programs precompile "
        f"missed — warmup_call_plan() drifted from warmup()")


def test_a_page_pool_brings_its_chunk_triple():
    """The pairing of a pool with the forward it is decoded with is
    PagedKV's: a spec cannot be built without the triple, the engine
    decodes with it, and an engine handed a second triple beside a page
    pool refuses by name (``chunked_fns`` is the dense slab's)."""
    from paged_engine import paged_chunk_fns, paged_engine
    from swarmdb_tpu.backend.engine import PagedKV
    from swarmdb_tpu.ops.paged_kv import PageAllocator

    cfg = TINY_DEBUG
    with pytest.raises(TypeError, match="chunked_fns"):
        PagedKV(init_pool=lambda: None, page_size=8, num_pages=17,
                allocator=PageAllocator(17, 8, 64, 2))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(max_batch=2, max_seq=64, page_size=8, num_pages=17, eos_id=2,
              prefill_buckets=[8])
    eng = paged_engine(cfg, params, **kw)
    assert eng._chunked_fns is eng.paged.chunked_fns
    with pytest.raises(ValueError, match=r"PagedKV\.chunked_fns"):
        paged_engine(cfg, params, chunked_fns=paged_chunk_fns(cfg), **kw)


def test_warmup_parallel_env_is_forgiving(monkeypatch):
    """A malformed SWARMDB_WARMUP_PARALLEL falls back to sequential, and
    parallel>1 without a persistent cache is refused (not run twice)."""
    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(
        lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c),
        lambda b, s: llama.init_kv_cache(cfg, b, s),
        params, max_batch=2, max_seq=32, eos_id=2, prefill_buckets=[8])
    monkeypatch.setenv("SWARMDB_WARMUP_PARALLEL", "definitely-not-an-int")
    assert eng.warmup() >= 0.0
    # without a persistent cache the parallel path must log-and-skip
    # rather than compile everything twice (earlier suite tests may have
    # enabled a cache process-wide — force the condition, then restore)
    monkeypatch.setenv("SWARMDB_WARMUP_PARALLEL", "4")
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert eng.warmup() >= 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# -------------------------------------------------- device-resident decode


def _paged_tiny_engine(**kw):
    """Single-chip paged engine (the device-resident session path is the
    DEFAULT for single-shard paged engines; SWARMDB_EMIT_RING=0 pins the
    per-chunk scan+pipeline path)."""
    from paged_engine import paged_engine

    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return paged_engine(
        cfg, params, max_batch=2, max_seq=96, page_size=8, num_pages=41,
        eos_id=2, seed=0, prefill_buckets=[16, 32], **kw)


def test_resident_matches_scan_path_tokens(monkeypatch):
    """The emission-ring while_loop must be a pure restructuring: greedy
    tokens identical to the per-chunk scan path, chunk math unchanged."""
    resident = _paged_tiny_engine()
    assert resident._resident_variants is not None
    monkeypatch.setenv("SWARMDB_EMIT_RING", "0")
    scan = _paged_tiny_engine()
    assert scan._resident_variants is None
    monkeypatch.delenv("SWARMDB_EMIT_RING")
    prompts = [[1, 5, 9], list(range(3, 30)), [7, 7]]
    try:
        resident.start()
        scan.start()
        for p in prompts:
            a, _ = resident.generate_sync(
                p, SamplingParams(max_new_tokens=12))
            b, _ = scan.generate_sync(
                p, SamplingParams(max_new_tokens=12))
            assert a == b, (p, a, b)
    finally:
        resident.stop()
        scan.stop()


def test_resident_host_syncs_per_request(monkeypatch):
    """The tentpole host-sync contract on ONE engine: a streamed
    multi-chunk request spans <= 3 sanctioned syncs on the resident
    path, while the scan path pays ~one per chunk (flight timelines)."""
    resident = _paged_tiny_engine()
    monkeypatch.setenv("SWARMDB_EMIT_RING", "0")
    scan = _paged_tiny_engine()
    monkeypatch.delenv("SWARMDB_EMIT_RING")

    def stream_one(eng):
        toks = []
        done = threading.Event()
        req = GenRequest(
            prompt=[1, 2, 3],
            sampling=SamplingParams(max_new_tokens=40),  # ~5 chunks, K=8
            on_token=lambda rid, t: toks.append(t),
            on_done=lambda *a: done.set())
        rid = eng.submit(req)
        assert done.wait(120)
        rec = next(r for r in reversed(eng.flight.requests())
                   if r["rid"] == rid)
        assert len(toks) >= 24
        return rec["host_syncs"]

    try:
        resident.start()
        scan.start()
        assert stream_one(resident) <= 3
        assert stream_one(scan) >= 4  # one drain per chunk, ~5 chunks
    finally:
        resident.stop()
        scan.stop()


def test_resident_session_counters_and_flight():
    """Sessions are counted, chunks accumulate, and the one drain per
    session is the only engine host sync while a request runs."""
    eng = _paged_tiny_engine()
    c = eng.metrics.counters
    try:
        eng.start()
        toks, reason = eng.generate_sync(
            [4, 5, 6], SamplingParams(max_new_tokens=24))
        assert reason in ("length", "eos")
        # on_done fires from the emission callback DURING the session;
        # the drain (and its counters) land right after — poll briefly
        deadline = time.time() + 10
        while (time.time() < deadline
               and (c["engine_resident_sessions"].value < 1
                    or c["engine_host_syncs"].value
                    != c["engine_resident_sessions"].value)):
            time.sleep(0.05)
        assert c["engine_resident_sessions"].value >= 1
        assert (c["engine_resident_chunks"].value
                >= c["engine_resident_sessions"].value)
        assert (c["engine_host_syncs"].value
                == c["engine_resident_sessions"].value)
    finally:
        eng.stop()


def test_row_bucketed_waves():
    """Lane-geometry paged engines pad admission waves to the smallest
    covering ROW bucket instead of prefill_batch (78% measured grid
    padding at dp8 otherwise); dense engines keep the fixed shape."""
    eng = _paged_tiny_engine()
    assert eng._row_buckets == [1, 2]
    assert eng._rows_for(1) == 1 and eng._rows_for(2) == 2
    dense = Engine(
        lambda p, t, pos, c: llama.forward(p, TINY_DEBUG, t, pos, c),
        lambda b, s: llama.init_kv_cache(TINY_DEBUG, b, s),
        llama.init_params(TINY_DEBUG, jax.random.PRNGKey(0)),
        max_batch=2, max_seq=32, eos_id=2, prefill_buckets=[8])
    assert dense._row_buckets == [dense.prefill_batch]
    # a single admission must dispatch a 1-row wave: padding delta for
    # the wave is bucket - prompt, not prefill_batch * bucket - prompt
    c = eng.metrics.counters
    try:
        eng.start()
        before = c["prefill_padding_tokens"].value
        eng.generate_sync([1] * 10, SamplingParams(max_new_tokens=2))
        added = c["prefill_padding_tokens"].value - before
        assert added <= 16 - 10, added  # one row, bucket 16
    finally:
        eng.stop()
