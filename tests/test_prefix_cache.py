"""Prefix caching: op/model parity, LRU behavior, engine token-exactness.

The feature (ops/prefix_cache.py + ops/layers.gqa_attention_prefix +
models/*.forward_prefix_lane + the engine's fused prefix admission) reuses
page-aligned prompt KV across requests. These tests pin the invariant that
matters: a prefix-cache engine produces EXACTLY the tokens of a plain
engine, because the reused K/V bytes are the bytes prefill would have
written. No reference counterpart (reference has no model code).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmdb_tpu.models import llama
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.ops.prefix_cache import PrefixLRU, page_chains

TINY = get_config("tiny-debug")


# ------------------------------------------------------------------ op parity


def test_forward_prefix_lane_matches_full_forward():
    """Suffix logits + lane image == full-prompt forward's logits + cache."""
    cfg = TINY
    ps = 8
    rng = np.random.default_rng(0)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))

    prompt = rng.integers(1, cfg.vocab_size, size=21).tolist()
    PP = 2                      # reuse 2 pages = 16 tokens
    P0 = PP * ps
    suffix = prompt[P0:]
    T = 8                       # suffix bucket (5 real + padding)
    lane_pages = PP + 1

    # full forward over the whole prompt (the ground truth)
    B = 1
    full_T = len(prompt)
    cache = llama.init_kv_cache(cfg, B, full_T)
    toks = jnp.asarray([prompt], jnp.int32)
    pos = jnp.arange(full_T, dtype=jnp.int32)[None]
    logits_full, (ck, cv) = llama.forward(params, cfg, toks, pos, cache)

    # build a pool whose pages 1..PP hold the prompt's first P0 tokens' KV
    pool_k, pool_v = llama.init_prefix_pool(cfg, 4, ps)
    for p in range(PP):
        pool_k = pool_k.at[:, p + 1].set(ck[:, 0, p * ps:(p + 1) * ps])
        pool_v = pool_v.at[:, p + 1].set(cv[:, 0, p * ps:(p + 1) * ps])

    suffix_pad = suffix + [0] * (T - len(suffix))
    table = jnp.asarray([[1, 2]], jnp.int32)
    plens = jnp.asarray([P0], jnp.int32)
    logits_sfx, lane_k, lane_v = llama.forward_prefix_lane(
        params, cfg, jnp.asarray([suffix_pad], jnp.int32), table, plens,
        pool_k, pool_v, lane_pages,
    )

    n = len(suffix)
    np.testing.assert_allclose(
        np.asarray(logits_sfx[0, :n]),
        np.asarray(logits_full[0, P0:P0 + n]), rtol=2e-3, atol=2e-3,
    )
    # the lane image must hold the prompt's exact cache bytes
    np.testing.assert_array_equal(
        np.asarray(lane_k[:, 0, :len(prompt)]),
        np.asarray(ck[:, 0, :len(prompt)]),
    )
    np.testing.assert_array_equal(
        np.asarray(lane_v[:, 0, :len(prompt)]),
        np.asarray(cv[:, 0, :len(prompt)]),
    )
    # beyond the prompt the lane holds pad-token garbage — unreachable
    # under the engine's write-before-read invariant (decode overwrites
    # position p in the step that first attends it)


def test_forward_prefix_lane_ragged_rows():
    """Rows with DIFFERENT prefix lengths in one call each match their own
    full forward."""
    cfg = TINY
    ps = 8
    rng = np.random.default_rng(1)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))

    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (20, 11)]
    hits = [2, 1]               # pages reused per row
    PP, T, lane_pages = 2, 8, 3

    pool_k, pool_v = llama.init_prefix_pool(cfg, 8, ps)
    refs = []
    tables = np.zeros((2, PP), np.int32)
    next_page = 1
    for b, prompt in enumerate(prompts):
        B, full_T = 1, len(prompt)
        cache = llama.init_kv_cache(cfg, B, full_T)
        logits, (ck, cv) = llama.forward(
            params, cfg, jnp.asarray([prompt], jnp.int32),
            jnp.arange(full_T, dtype=jnp.int32)[None], cache)
        refs.append((logits, ck, cv))
        for p in range(hits[b]):
            pool_k = pool_k.at[:, next_page].set(ck[:, 0, p * ps:(p + 1) * ps])
            pool_v = pool_v.at[:, next_page].set(cv[:, 0, p * ps:(p + 1) * ps])
            tables[b, p] = next_page
            next_page += 1

    plens = np.asarray([h * ps for h in hits], np.int32)
    sfx = np.zeros((2, T), np.int32)
    for b, prompt in enumerate(prompts):
        s = prompt[plens[b]:]
        sfx[b, :len(s)] = s
    logits_sfx, lane_k, lane_v = llama.forward_prefix_lane(
        params, cfg, jnp.asarray(sfx), jnp.asarray(tables),
        jnp.asarray(plens), pool_k, pool_v, lane_pages,
    )
    for b, prompt in enumerate(prompts):
        n = len(prompt) - plens[b]
        logits_full, ck, cv = refs[b]
        np.testing.assert_allclose(
            np.asarray(logits_sfx[b, :n]),
            np.asarray(logits_full[0, plens[b]:len(prompt)]),
            rtol=2e-3, atol=2e-3,
        )
        np.testing.assert_array_equal(
            np.asarray(lane_k[:, b, :len(prompt)]),
            np.asarray(ck[:, 0, :len(prompt)]),
        )


# ------------------------------------------------------------------ LRU table


def test_page_chains_prefix_property():
    ps = 4
    a = page_chains([1, 2, 3, 4, 5, 6, 7, 8, 9], ps)
    b = page_chains([1, 2, 3, 4, 5, 6, 7, 8, 100, 200], ps)
    assert len(a) == 2 and len(b) == 2
    assert a[0] == b[0] and a[1] == b[1]          # same full pages
    c = page_chains([1, 2, 3, 99, 5, 6, 7, 8], ps)
    assert c[0] != a[0] and c[1] != a[1]          # chain diverges at page 0


def test_prefix_lru_match_register_evict():
    lru = PrefixLRU(4, 4)                         # 3 usable pages
    toks = list(range(1, 13))                     # 3 full pages
    chains = page_chains(toks, 4)
    assert lru.match(chains, toks) == []

    pages = lru.acquire(3)
    assert sorted(pages) == [1, 2, 3]
    for i, (c, p) in enumerate(zip(chains, pages)):
        lru.register(c, tuple(toks[i * 4:(i + 1) * 4]), p)
    assert lru.match(chains, toks) == pages

    # different tokens with (forced) same chain run would stop the match
    other = [9, 9, 9, 9]
    assert lru.match([chains[0]], other) == []

    # eviction: acquiring 2 more pages evicts the LRU entries
    more = lru.acquire(2)
    assert more is not None and len(more) == 2
    # at most one original entry can still match (page 0's chain may be gone)
    assert len(lru.match(chains, toks)) <= 1


def test_prefix_lru_pinned_pages_not_evicted():
    lru = PrefixLRU(3, 4)                         # 2 usable pages
    toks = list(range(1, 9))
    chains = page_chains(toks, 4)
    pages = lru.acquire(2)
    for i, (c, p) in enumerate(zip(chains, pages)):
        lru.register(c, tuple(toks[i * 4:(i + 1) * 4]), p)
    lru.pin(pages)
    assert lru.acquire(1) == []                   # nothing evictable
    lru.unpin(pages)
    assert len(lru.acquire(1)) == 1


def _mk_engine(prefix: bool, pool_pages: int = 64):
    from swarmdb_tpu.backend.engine import Engine

    cfg = TINY
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    fwd = lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c)
    init_cache = lambda b, s: llama.init_kv_cache(cfg, b, s)
    chunked = (
        lambda p, t, pos, c, hkv, s: llama.forward_chunked(
            p, cfg, t, pos, c, hkv, s),
        lambda b, k: llama.init_chunk_kv(cfg, b, k),
        llama.merge_chunk,
    )
    kw = {}
    if prefix:
        kw = dict(
            prefix_fns=(
                lambda p, t, tab, pl, pk, pv, lp, logits_at=None:
                    llama.forward_prefix_lane(p, cfg, t, tab, pl, pk, pv,
                                              lp, logits_at=logits_at),
                lambda n, ps: llama.init_prefix_pool(cfg, n, ps),
            ),
            prefix_pages=pool_pages,
            prefix_page_size=8,
        )
    eng = Engine(fwd, init_cache, params, max_batch=4, max_seq=64,
                 eos_id=2, seed=0, prefill_buckets=[8, 16, 32, 63],
                 decode_chunk=4, chunked_fns=chunked, **kw)
    eng.start()
    return eng


@pytest.fixture(scope="module")
def plain_engine():
    eng = _mk_engine(prefix=False)
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def prefix_engine():
    eng = _mk_engine(prefix=True)
    yield eng
    eng.stop()


def test_engine_prefix_matches_plain_multiturn(plain_engine, prefix_engine):
    """Simulated multi-turn conversations: growing shared-prefix prompts
    must generate EXACTLY the plain engine's tokens, and later turns must
    actually hit the cache."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    rng = np.random.default_rng(7)
    history = rng.integers(3, TINY.vocab_size, size=9).tolist()
    for turn in range(4):
        prompt = list(history)
        for eng_label, eng in (("plain", plain_engine),
                               ("prefix", prefix_engine)):
            toks, reason = eng.generate_sync(
                list(prompt), SamplingParams(max_new_tokens=6))
            if eng_label == "plain":
                expect = (toks, reason)
        assert (toks, reason) == expect, f"turn {turn}"
        # the conversation grows: reply + a new user message
        history.extend(toks)
        history.extend(rng.integers(3, TINY.vocab_size, size=5).tolist())

    st = prefix_engine.stats()["prefix_cache"]
    assert st["hit_tokens"] > 0, st
    assert st["cached_pages"] > 0, st


def test_engine_prefix_matches_plain_sampled(plain_engine, prefix_engine):
    """Sampled generation also matches: the PRNG fold uses ABSOLUTE
    positions, so suffix-only prefill draws the same randomness."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    rng = np.random.default_rng(11)
    base = rng.integers(3, TINY.vocab_size, size=17).tolist()
    sp = SamplingParams(max_new_tokens=5, temperature=0.7, top_k=8)
    a1, _ = plain_engine.generate_sync(list(base), sp)
    b1, _ = prefix_engine.generate_sync(list(base), sp)    # miss + register
    b2, _ = prefix_engine.generate_sync(list(base), sp)    # hit
    assert a1 == b1 == b2


def test_engine_prefix_cross_request_sharing(prefix_engine):
    """Two different requests sharing a long page-aligned prefix: the
    second reuses the first's pages (hit counter advances)."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    rng = np.random.default_rng(13)
    shared = rng.integers(3, TINY.vocab_size, size=24).tolist()
    before = prefix_engine.stats()["prefix_cache"]["hit_tokens"]
    prefix_engine.generate_sync(shared + [5, 6],
                                SamplingParams(max_new_tokens=3))
    prefix_engine.generate_sync(shared + [9, 10, 11],
                                SamplingParams(max_new_tokens=3))
    after = prefix_engine.stats()["prefix_cache"]["hit_tokens"]
    assert after > before


def test_mixtral_forward_prefix_lane_matches_full():
    """MoE variant: suffix logits and lane image match the full forward."""
    from swarmdb_tpu.models import mixtral

    cfg = get_config("tiny-moe")
    ps = 8
    rng = np.random.default_rng(3)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))

    prompt = rng.integers(1, cfg.vocab_size, size=19).tolist()
    PP, P0 = 2, 16
    T, lane_pages = 8, 3
    cache = mixtral.init_kv_cache(cfg, 1, len(prompt))
    logits_full, (ck, cv) = mixtral.forward(
        params, cfg, jnp.asarray([prompt], jnp.int32),
        jnp.arange(len(prompt), dtype=jnp.int32)[None], cache)

    pool_k, pool_v = llama.init_prefix_pool(cfg, 4, ps)
    for p in range(PP):
        pool_k = pool_k.at[:, p + 1].set(ck[:, 0, p * ps:(p + 1) * ps])
        pool_v = pool_v.at[:, p + 1].set(cv[:, 0, p * ps:(p + 1) * ps])

    suffix = prompt[P0:]
    sfx = np.zeros((1, T), np.int32)
    sfx[0, :len(suffix)] = suffix
    # (a routed family's forwards return their routing last)
    logits_sfx, lane_k, _lane_v, _routing = llama.forward_prefix_lane(
        params, cfg, jnp.asarray(sfx), jnp.asarray([[1, 2]], jnp.int32),
        jnp.asarray([P0], jnp.int32), pool_k, pool_v, lane_pages,
    )
    n = len(suffix)
    np.testing.assert_allclose(
        np.asarray(logits_sfx[0, :n]),
        np.asarray(logits_full[0, P0:P0 + n]), rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_array_equal(
        np.asarray(lane_k[:, 0, :len(prompt)]),
        np.asarray(ck[:, 0, :len(prompt)]),
    )


@pytest.mark.parametrize("manage_free", [False, True])
def test_evictable_count_and_lru_victims_without_walking_the_cache(
        manage_free):
    """``evictable_count`` walks the pins and ``evict_lru`` stops at its
    last victim (both stand at a session boundary, PR 45): over a random
    run of registrations, matches, pins, unpins and evictions the count
    is the brute-force one and the victims are the oldest unpinned
    entries, in LRU order."""
    rng = np.random.default_rng(45)
    ps, pages = 4, 64
    lru = PrefixLRU(pages, ps, manage_free=manage_free)
    free = list(range(pages - 1, 0, -1))
    pinned = []

    def brute():
        return sum(1 for p, _t, _r in lru._entries.values()
                   if not lru._pins.get(p))

    for step in range(600):
        op = rng.integers(0, 5)
        if op == 0 and free:
            toks = tuple(rng.integers(3, 9, size=ps).tolist())
            page = free.pop()
            if not lru.register(page_chains(list(toks), ps)[0], toks, page):
                free.append(page)
        elif op == 1 and lru._entries:
            chain = list(lru._entries)[rng.integers(len(lru._entries))]
            page, toks, _ = lru._entries[chain]
            assert lru.match_and_pin([chain], list(toks)) == [page]
            pinned.append(page)
        elif op == 2 and pinned:
            lru.unpin([pinned.pop(rng.integers(len(pinned)))])
        elif op == 3:
            n = int(rng.integers(1, 4))
            want = [p for p, _t, _r in lru._entries.values()
                    if not lru._pins.get(p)][:n]
            got = lru.evict_lru(n)
            assert got == want
            free.extend(got)
        elif op == 4:
            odd = lambda p: p % 2 == 1      # noqa: E731
            want = [p for p, _t, _r in lru._entries.values()
                    if not lru._pins.get(p) and odd(p)][:2]
            got = lru.evict_lru(2, want=odd)
            assert got == want
            free.extend(got)
        assert lru.evictable_count() == brute(), step
        assert lru._entry_pages == {p for p, _t, _r in lru._entries.values()}
    assert lru.evictable_count() < len(lru._entries)    # pins were live
    lru.reset()
    assert lru.evictable_count() == 0 and not lru._entry_pages


def test_prefix_lru_duplicate_registration_recycles():
    lru = PrefixLRU(4, 4)
    toks = list(range(1, 5))
    (chain,) = page_chains(toks, 4)
    p1 = lru.acquire(1)[0]
    assert lru.register(chain, tuple(toks), p1)
    p2 = lru.acquire(1)[0]
    assert not lru.register(chain, tuple(toks), p2)  # duplicate
    assert lru.match(page_chains(toks, 4), toks) == [p1]
    assert lru.stats()["free_pages"] == 2         # p2 went back


# --------------------------------------------------------------- paged engine


def _mk_paged_prefix_engine(pool_pages: int = 64):
    """Paged engine with IN-PLACE prefix caching over the main pool."""
    from paged_engine import paged_engine

    cfg = TINY
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = paged_engine(cfg, params, max_batch=4, max_seq=64, page_size=8,
                       num_pages=1 + pool_pages, prefix=True, eos_id=2,
                       seed=0, prefill_buckets=[8, 16, 32, 63],
                       decode_chunk=4)
    eng.start()
    return eng


@pytest.fixture(scope="module")
def paged_prefix_engine():
    eng = _mk_paged_prefix_engine()
    yield eng
    eng.stop()


def test_paged_prefix_matches_plain_multiturn(plain_engine,
                                              paged_prefix_engine):
    """Paged in-place prefix reuse: growing conversations generate exactly
    the plain dense engine's tokens, with real cache hits."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    rng = np.random.default_rng(23)
    history = rng.integers(3, TINY.vocab_size, size=11).tolist()
    for turn in range(4):
        a, ra = plain_engine.generate_sync(
            list(history), SamplingParams(max_new_tokens=6))
        b, rb = paged_prefix_engine.generate_sync(
            list(history), SamplingParams(max_new_tokens=6))
        assert (a, ra) == (b, rb), f"turn {turn}"
        history.extend(a)
        history.extend(rng.integers(3, TINY.vocab_size, size=5).tolist())

    st = paged_prefix_engine.stats()["prefix_cache"]
    assert st["hit_tokens"] > 0, st
    assert st["pinned_pages"] == 0, st        # all retired -> all unpinned


def test_paged_prefix_under_pool_pressure(plain_engine):
    """A pool barely larger than one request's footprint: eviction must
    free cached pages for new admissions, and tokens stay exact."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    eng = _mk_paged_prefix_engine(pool_pages=20)  # tight: maxp=8 per slot
    try:
        rng = np.random.default_rng(29)
        for i in range(6):
            prompt = rng.integers(3, TINY.vocab_size, size=30 + i).tolist()
            a, _ = plain_engine.generate_sync(
                list(prompt), SamplingParams(max_new_tokens=5))
            b, _ = eng.generate_sync(
                list(prompt), SamplingParams(max_new_tokens=5))
            assert a == b, f"request {i}"
        al = eng.paged.allocator.stats()
        assert al["live_slots"] <= 1
    finally:
        eng.stop()


def test_engine_recovery_resets_prefix_cache(plain_engine):
    """An in-loop engine error rebuilds the pool; the prefix table must be
    forgotten (stale entries would point at zeroed/reused pages) and
    generation must stay token-correct afterwards."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    eng = _mk_engine(prefix=True, pool_pages=64)
    try:
        rng = np.random.default_rng(31)
        prompt = rng.integers(3, TINY.vocab_size, size=20).tolist()
        ref, _ = plain_engine.generate_sync(
            list(prompt), SamplingParams(max_new_tokens=6))
        out1, _ = eng.generate_sync(list(prompt),
                                    SamplingParams(max_new_tokens=6))
        assert out1 == ref
        assert eng.stats()["prefix_cache"]["cached_pages"] > 0

        # force one engine-loop failure: next dispatch raises
        original = eng._dispatch_decode
        calls = {"n": 0}

        def boom(*a, **kw):
            calls["n"] += 1
            eng._dispatch_decode = original  # fail exactly once
            raise RuntimeError("injected device error")

        eng._dispatch_decode = boom
        toks, reason = eng.generate_sync(list(prompt),
                                         SamplingParams(max_new_tokens=6),
                                         timeout=60)
        assert reason in ("engine_error", "length")
        assert calls["n"] == 1

        # the recovery path must forget every cached page; on_done fires
        # from _fail_all BEFORE the engine thread reaches the reset, so
        # poll briefly instead of racing it
        import time as _t
        deadline = _t.time() + 10
        while _t.time() < deadline:
            st = eng.stats()["prefix_cache"]
            if st["cached_pages"] == 0 and st["pinned_pages"] == 0:
                break
            _t.sleep(0.05)
        assert st["cached_pages"] == 0, st
        assert st["pinned_pages"] == 0, st

        # and serving continues, token-correct, re-warming the cache
        out2, _ = eng.generate_sync(list(prompt),
                                    SamplingParams(max_new_tokens=6))
        assert out2 == ref
        out3, _ = eng.generate_sync(list(prompt),
                                    SamplingParams(max_new_tokens=6))
        assert out3 == ref  # served from the re-registered cache
        assert eng.stats()["prefix_cache"]["hit_tokens"] > 0
    finally:
        eng.stop()


def test_prefix_attention_respects_sliding_window():
    """gqa_attention_prefix with a window smaller than the prefix must
    match the full forward's windowed attention (windowed models reuse
    prefixes too)."""
    import dataclasses

    cfg = dataclasses.replace(TINY, sliding_window=12)
    ps = 8
    rng = np.random.default_rng(17)
    params = llama.init_params(cfg, jax.random.PRNGKey(2))

    prompt = rng.integers(1, cfg.vocab_size, size=22).tolist()
    PP, P0 = 2, 16
    T, lane_pages = 8, 3
    cache = llama.init_kv_cache(cfg, 1, len(prompt))
    logits_full, (ck, cv) = llama.forward(
        params, cfg, jnp.asarray([prompt], jnp.int32),
        jnp.arange(len(prompt), dtype=jnp.int32)[None], cache)

    pool_k, pool_v = llama.init_prefix_pool(cfg, 4, ps)
    for p in range(PP):
        pool_k = pool_k.at[:, p + 1].set(ck[:, 0, p * ps:(p + 1) * ps])
        pool_v = pool_v.at[:, p + 1].set(cv[:, 0, p * ps:(p + 1) * ps])

    suffix = prompt[P0:]
    sfx = np.zeros((1, T), np.int32)
    sfx[0, :len(suffix)] = suffix
    logits_sfx, _sk, _sv = llama.forward_prefix_pages(
        params, cfg, jnp.asarray(sfx), jnp.asarray([[1, 2]], jnp.int32),
        jnp.asarray([P0], jnp.int32), pool_k, pool_v,
    )
    n = len(suffix)
    np.testing.assert_allclose(
        np.asarray(logits_sfx[0, :n]),
        np.asarray(logits_full[0, P0:P0 + n]), rtol=2e-3, atol=2e-3,
    )
