"""Quantized KV pages (SWARMDB_KV_DTYPE=int8): quantize-on-write /
dequantize-in-kernel parity, canary regressions, dtype-pin guarantees,
and end-to-end engine greedy-decode drift bounds.

Tolerance notes (the bounded-error contract int8 pools trade the
bit-identical one for):
- per-element dequant error <= scale/2, scale = page-head amax / 127
  -> relative error ~0.4% of the page's dynamic range;
- attention outputs are softmax-weighted averages of V, so output
  error stays the same order (we assert 5e-2 on unit-scale data);
- greedy decode drift: logit gaps near argmax occasionally flip a
  token; the floor below is set from observed behavior (>= 90% of
  tokens match the full-precision run on TINY_DEBUG) with slack.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmdb_tpu.models import llama
from swarmdb_tpu.models.configs import TINY_DEBUG
from swarmdb_tpu.ops.paged_kv import (
    INT8_CANARY_VALUE,
    SCALE_CANARY_VALUE,
    QuantPool,
    _dequantize_pages,
    _quantize_pages,
    canary_check,
    canary_fill,
    init_paged_kv_cache,
    is_quantized,
    kv_dtype_name,
    kv_quantized,
    paged_gather_kv,
    paged_write_chunk,
    paged_write_ragged,
    pages_per_slot,
    pool_dtype,
    pool_insert_pages,
    pool_layer,
    pool_page_bytes,
)


# ---------------------------------------------------------------------------
# dtype resolution + bit-identity pins


def test_env_unset_is_bf16(monkeypatch):
    monkeypatch.delenv("SWARMDB_KV_DTYPE", raising=False)
    assert kv_dtype_name() == "bf16"
    assert not kv_quantized()
    cache = init_paged_kv_cache(2, 4, 4, 2, 8, 1, 16)
    assert cache["k"].dtype == jnp.bfloat16
    assert not is_quantized(cache["k"])


def test_unknown_dtype_rejected(monkeypatch):
    monkeypatch.setenv("SWARMDB_KV_DTYPE", "int4")
    with pytest.raises(ValueError):
        kv_dtype_name()


@pytest.mark.parametrize("name,dt", [("bf16", jnp.bfloat16),
                                     ("f32", jnp.float32)])
def test_plain_dtypes_bit_identical_to_explicit(monkeypatch, name, dt):
    """SWARMDB_KV_DTYPE=f32/bf16 must produce byte-identical pools and
    write results to passing the dtype explicitly (the zero-risk pin:
    unquantized configs cannot drift)."""
    rng = np.random.default_rng(0)
    L, P, ps, Hkv, D = 2, 5, 4, 2, 8
    k = jnp.asarray(rng.standard_normal((L, 1, 1, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, 1, 1, Hkv, D)), jnp.float32)
    table = jnp.asarray([[1, 2, 3]], jnp.int32)

    def run(dtype_arg):
        cache = init_paged_kv_cache(L, P, ps, Hkv, D, 1, 12, dtype_arg)
        # a one-step chunk written at position 5
        return paged_write_chunk(
            cache["k"], cache["v"], k.astype(cache["k"].dtype),
            v.astype(cache["v"].dtype), jnp.asarray([5], jnp.int32), table)

    monkeypatch.setenv("SWARMDB_KV_DTYPE", name)
    got_k, got_v = run(None)
    want_k, want_v = run(dt)
    assert got_k.dtype == dt
    # ... which put the token where a numpy scatter puts it
    assert np.array_equal(np.asarray(got_k, np.float32)[:, 2, 1],
                          np.asarray(k.astype(dt), np.float32)[:, 0, 0])
    assert np.array_equal(np.asarray(got_k, np.float32),
                          np.asarray(want_k, np.float32))
    assert np.array_equal(np.asarray(got_v, np.float32),
                          np.asarray(want_v, np.float32))


def test_int8_pool_structure(monkeypatch):
    monkeypatch.setenv("SWARMDB_KV_DTYPE", "int8")
    assert kv_quantized()
    L, P, ps, Hkv, D = 2, 5, 4, 2, 8
    cache = init_paged_kv_cache(L, P, ps, Hkv, D, 1, 12)
    pool = cache["k"]
    assert is_quantized(pool)
    assert pool.data.shape == (L, P, ps, Hkv, D)
    assert pool.data.dtype == jnp.int8
    assert pool.scale.shape == (L, P, Hkv)
    assert pool.scale.dtype == jnp.float32
    assert pool_dtype(pool) == jnp.bfloat16  # logical dtype
    # per-page price covers payload + scale planes
    per_page = pool_page_bytes(pool)
    assert per_page == (ps * Hkv * D * 1 * L + Hkv * 4 * L)
    # pool_layer slices BOTH leaves (QuantPool[i] is tuple indexing!)
    lay = pool_layer(pool, 1)
    assert lay.data.shape == (P, ps, Hkv, D)
    assert lay.scale.shape == (P, Hkv)


# ---------------------------------------------------------------------------
# quantization math


def test_quant_roundtrip_bound():
    rng = np.random.default_rng(1)
    pages = rng.standard_normal((6, 8, 2, 16)).astype(np.float32)
    q, s = _quantize_pages(jnp.asarray(pages))
    deq = np.asarray(_dequantize_pages(q, s))
    # error <= scale/2 per element, scale per (page, head)
    bound = 0.5 * np.asarray(s)[:, None, :, None] + 1e-6
    assert (np.abs(deq - pages) <= bound).all()
    # payload never uses -128 (reserved for the canary)
    assert int(np.asarray(q).min()) >= -127


def test_requant_idempotent_on_full_pages():
    """Re-quantizing an untouched full page must not walk: the amax
    slot re-rounds to +/-127 exactly, so survivors are stable across
    any number of incremental writes to OTHER slots."""
    rng = np.random.default_rng(2)
    pages = rng.standard_normal((3, 8, 2, 16)).astype(np.float32)
    q1, s1 = _quantize_pages(jnp.asarray(pages))
    q2, s2 = _quantize_pages(_dequantize_pages(q1, s1))
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_int8_write_gather_roundtrip(monkeypatch):
    monkeypatch.setenv("SWARMDB_KV_DTYPE", "int8")
    rng = np.random.default_rng(3)
    L, ps, Hkv, D, maxp, B = 2, 4, 2, 8, 3, 2
    P = 1 + B * maxp
    cache = init_paged_kv_cache(L, P, ps, Hkv, D, B, maxp * ps)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    dense = rng.standard_normal((L, B, maxp * ps, Hkv, D)).astype(np.float32)
    kc = jnp.asarray(dense.reshape(L, B * maxp, ps, Hkv, D))
    flat = table.reshape(-1)
    pk = pool_insert_pages(cache["k"], flat, kc)
    pv = pool_insert_pages(cache["v"], flat, kc)
    scl = np.asarray(pk.scale)  # [L, P, Hkv]
    for l in range(L):
        gk, gv = paged_gather_kv(pool_layer(pk, l), pool_layer(pv, l),
                                 table)
        # quantized pools dequantize to f32 on the gather (fallback) path
        assert gk.dtype == jnp.float32
        per_slot_scale = scl[l][np.asarray(table)]   # [B, maxp, Hkv]
        bound = 0.5 * np.repeat(per_slot_scale, ps, axis=1) + 1e-6
        err = np.abs(np.asarray(gk) - dense[l])      # gk [B, S, Hkv, D]
        assert (err <= bound[..., None]).all()


# ---------------------------------------------------------------------------
# canary: int8 payload slot + scale slot (satellite: pagecheck)


def test_int8_canary_roundtrip(monkeypatch):
    monkeypatch.setenv("SWARMDB_KV_DTYPE", "int8")
    L, P, ps, Hkv, D = 2, 6, 4, 2, 8
    cache = init_paged_kv_cache(L, P, ps, Hkv, D, 1, 16)
    pages = np.array([2, 4], np.int32)
    pk, pv = canary_fill(cache["k"], cache["v"], jnp.asarray(pages))
    assert (np.asarray(pk.data[:, pages]) == INT8_CANARY_VALUE).all()
    assert (np.asarray(pk.scale[:, pages]) == SCALE_CANARY_VALUE).all()
    assert len(canary_check(pk, pv, jnp.asarray(pages))) == 0

    # payload crime: one int8 cell overwritten
    bad = QuantPool(pk.data.at[0, 2, 0, 0, 0].set(5), pk.scale)
    assert 2 in canary_check(bad, pv, jnp.asarray(pages))

    # scale crime: a write-after-free that only touched the scale plane
    # (real scales are strictly positive; the canary is -1.0)
    bad2 = QuantPool(pk.data, pk.scale.at[1, 4, 1].set(0.25))
    assert 4 in canary_check(bad2, pv, jnp.asarray(pages))


# ---------------------------------------------------------------------------
# interpreter parity: quant kernels vs quantized XLA reference
# (GQA ratios, page crossings, prefix+suffix spans)


def _quant_pool_fixture(seed, B, Hkv, D, ps, maxp, lengths):
    rng = np.random.default_rng(seed)
    P = 1 + B * maxp
    kp = np.zeros((P, ps, Hkv, D), np.float32)
    vp = np.zeros((P, ps, Hkv, D), np.float32)
    table = np.zeros((B, maxp), np.int32)
    nxt = 1
    for b in range(B):
        n = int(lengths[b])
        kv = rng.standard_normal((n, Hkv, D)).astype(np.float32)
        vv = rng.standard_normal((n, Hkv, D)).astype(np.float32)
        for j in range(-(-n // ps)):
            table[b, j] = nxt
            kp[nxt, : len(kv[j * ps:(j + 1) * ps])] = kv[j * ps:(j + 1) * ps]
            vp[nxt, : len(vv[j * ps:(j + 1) * ps])] = vv[j * ps:(j + 1) * ps]
            nxt += 1
    kq, ks = _quantize_pages(jnp.asarray(kp))
    vq, vs = _quantize_pages(jnp.asarray(vp))
    return kq, ks, vq, vs, table, rng


@pytest.mark.parametrize("G", [1, 4])
def test_ragged_quant_kernel_parity_prefix_suffix(G):
    """Ragged prefill with BOTH pool-resident (quantized) prefix pages
    and a full-precision suffix stream: quant kernel vs quantized
    reference (tight) and vs full-precision reference (quant bound)."""
    from swarmdb_tpu.ops.attention_pallas import (
        ragged_paged_prefill_attention_quant)
    from swarmdb_tpu.ops.layers import ragged_prefill_attention_reference

    rng = np.random.default_rng(30 + G)
    Hkv, D, ps, maxp, R = 2, 16, 4, 4, 3
    Hq = Hkv * G
    # rows: fresh (no prefix), page-aligned prefix, mid-page split
    plens = np.asarray([0, ps, ps + 1], np.int32)
    lens = np.asarray([3, 5, 4], np.int32)
    starts = np.asarray([0, 3, 8], np.int32)
    W = 16
    P = 1 + R * maxp
    kp = np.zeros((P, ps, Hkv, D), np.float32)
    vp = np.zeros((P, ps, Hkv, D), np.float32)
    table = np.zeros((R, maxp), np.int32)
    nxt = 1
    for r in range(R):
        need = max(1, -(-int(plens[r] + lens[r]) // ps))
        for c in range(need):
            table[r, c] = nxt
            nxt += 1
        # prefix contents (slots past plens are masked by both sides,
        # so filling whole pages is fine — same pool on both paths)
        npref = max(1, -(-int(plens[r]) // ps))
        kp[table[r, :npref]] = rng.standard_normal(
            (npref, ps, Hkv, D)).astype(np.float32)
        vp[table[r, :npref]] = rng.standard_normal(
            (npref, ps, Hkv, D)).astype(np.float32)
    tok_row = np.full(W, R, np.int32)
    for r in range(R):
        tok_row[starts[r]:starts[r] + lens[r]] = r
    q = jnp.asarray(rng.standard_normal((W, Hq, D)), jnp.float32)
    sk = jnp.asarray(rng.standard_normal((W, Hkv, D)), jnp.float32)
    sv = jnp.asarray(rng.standard_normal((W, Hkv, D)), jnp.float32)
    kq, ks = _quantize_pages(jnp.asarray(kp))
    vq, vs = _quantize_pages(jnp.asarray(vp))

    got = np.asarray(ragged_paged_prefill_attention_quant(
        q, sk, sv, kq, ks, vq, vs, jnp.asarray(table),
        jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(plens),
        interpret=True))
    want_q = np.asarray(ragged_prefill_attention_reference(
        q, sk, sv, QuantPool(kq, ks), QuantPool(vq, vs),
        jnp.asarray(table), jnp.asarray(starts), jnp.asarray(lens),
        jnp.asarray(plens), jnp.asarray(tok_row)))
    want_f = np.asarray(ragged_prefill_attention_reference(
        q, sk, sv, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(plens),
        jnp.asarray(tok_row)))
    live = tok_row < R
    # tight: same dequantized values on both sides
    assert np.max(np.abs(got[live] - want_q[live])) < 2e-5
    # bounded: quantization error vs the full-precision pool
    assert np.max(np.abs(got[live] - want_f[live])) < 5e-2


@pytest.mark.parametrize("G", [1, 2, 4])
def test_chunked_decode_quant_kernel_parity(G):
    """In-kernel dequant == boundary dequant: the quant chunked decode
    kernel (pool pages quantized, chunk buffer full precision) must match
    its XLA fallback, the quantized gather path, to fp rounding, across
    GQA ratios and page-crossing prefix lengths (incl. an empty slot,
    which attends its chunk buffer alone)."""
    from swarmdb_tpu.ops.layers import (paged_attention_dispatch_chunked,
                                        pallas_disabled)

    rng = np.random.default_rng(7)
    B, Hkv, D, ps, maxp = 4, 2, 16, 8, 3
    Hq = Hkv * G
    lengths = np.asarray([5, ps, 2 * ps + 3, 0], np.int32)
    kq, ks, vq, vs, table, _ = _quant_pool_fixture(
        40 + G, B, Hkv, D, ps, maxp, lengths)
    pool_k, pool_v = QuantPool(kq, ks), QuantPool(vq, vs)
    Kc = 4
    step = 2
    ck = jnp.asarray(rng.standard_normal((B, Kc, Hkv, D)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((B, Kc, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    qpos = jnp.asarray(lengths + step, jnp.int32)[:, None]
    starts = jnp.asarray(lengths, jnp.int32)

    with pallas_disabled():
        want = np.asarray(paged_attention_dispatch_chunked(
            q, pool_k, pool_v, jnp.asarray(table), ck, cv, qpos,
            jnp.asarray(step, jnp.int32)))
    from swarmdb_tpu.ops.attention_pallas import (
        paged_decode_gqa_attention_chunked_quant)
    got = np.asarray(paged_decode_gqa_attention_chunked_quant(
        q[:, 0], kq, ks, vq, vs, jnp.asarray(table), ck, cv, starts,
        jnp.asarray(step, jnp.int32), interpret=True))
    assert np.max(np.abs(got - want[:, 0])) < 2e-5


# ---------------------------------------------------------------------------
# incremental writes: decode / chunk / ragged under int8


def test_int8_decode_write_survivors_bounded(monkeypatch):
    """One-step chunk writes on a QuantPool, a token at a time: the new
    token lands within the rounding budget and survivors drift at most
    one requant step."""
    monkeypatch.setenv("SWARMDB_KV_DTYPE", "int8")
    rng = np.random.default_rng(11)
    ps, Hkv, D, maxp, B = 4, 2, 8, 3, 1
    P = 1 + maxp
    cache = init_paged_kv_cache(1, P, ps, Hkv, D, B, maxp * ps)
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    pk, pv = cache["k"], cache["v"]
    history = []
    for pos in range(6):
        k = rng.standard_normal((1, B, 1, Hkv, D)).astype(np.float32)
        v = rng.standard_normal((1, B, 1, Hkv, D)).astype(np.float32)
        history.append(k[0])
        pk, pv = paged_write_chunk(
            pk, pv, jnp.asarray(k), jnp.asarray(v),
            jnp.asarray([pos], jnp.int32), table)
    want = np.concatenate([h[:, 0] for h in history], axis=0)  # [6,Hkv,D]
    scl = np.asarray(pk.scale)[0]  # [P, Hkv]
    for pos in range(6):
        page = int(np.asarray(table)[0, pos // ps])
        got = np.asarray(pk.data)[0, page, pos % ps].astype(np.float32) \
            * scl[page][:, None]
        assert np.max(np.abs(got - want[pos])) < \
            np.max(scl[page]) * 0.75 + 1e-5


def test_int8_chunk_write_matches_dense(monkeypatch):
    monkeypatch.setenv("SWARMDB_KV_DTYPE", "int8")
    rng = np.random.default_rng(12)
    L, ps, Hkv, D, maxp, B = 2, 4, 2, 8, 3, 2
    P = 1 + B * maxp
    cache = init_paged_kv_cache(L, P, ps, Hkv, D, B, maxp * ps)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    starts = jnp.asarray([2, ps], jnp.int32)  # mid-page + page-aligned
    Kc = 4
    ck = rng.standard_normal((L, B, Kc, Hkv, D)).astype(np.float32)
    cv = rng.standard_normal((L, B, Kc, Hkv, D)).astype(np.float32)
    pk, pv = paged_write_chunk(cache["k"], cache["v"], jnp.asarray(ck),
                               jnp.asarray(cv), starts, table)
    scl = np.asarray(pk.scale)
    for b in range(B):
        for t in range(Kc):
            pos = int(np.asarray(starts)[b]) + t
            page = int(np.asarray(table)[b, pos // ps])
            got = np.asarray(pk.data)[:, page, pos % ps].astype(
                np.float32) * scl[:, page][:, :, None]
            assert np.max(np.abs(got - ck[:, b, t])) < \
                np.max(scl[:, page]) * 0.75 + 1e-5


def test_int8_ragged_write_positions(monkeypatch):
    monkeypatch.setenv("SWARMDB_KV_DTYPE", "int8")
    rng = np.random.default_rng(13)
    L, ps, Hkv, D, maxp, R = 2, 4, 2, 8, 3, 2
    P = 1 + R * maxp
    cache = init_paged_kv_cache(L, P, ps, Hkv, D, R, maxp * ps)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    # row 0: fresh from 0; row 1: resume mid-page at pos 5
    tok_row = np.array([0, 0, 0, 1, 1, 2, 2, 2], np.int32)
    tok_pos = np.array([0, 1, 2, 5, 6, 0, 0, 0], np.int32)
    W = tok_row.shape[0]
    sk = rng.standard_normal((L, W, Hkv, D)).astype(np.float32)
    sv = rng.standard_normal((L, W, Hkv, D)).astype(np.float32)
    pk, pv = paged_write_ragged(
        cache["k"], cache["v"], jnp.asarray(sk), jnp.asarray(sv),
        jnp.asarray(tok_row), jnp.asarray(tok_pos), tables)
    scl = np.asarray(pk.scale)
    for t in range(W):
        if tok_row[t] >= R:
            continue
        page = int(np.asarray(tables)[tok_row[t], tok_pos[t] // ps])
        got = np.asarray(pk.data)[:, page, tok_pos[t] % ps].astype(
            np.float32) * scl[:, page][:, :, None]
        assert np.max(np.abs(got - sk[:, t])) < \
            np.max(scl[:, page]) * 0.75 + 1e-5
    # trash page absorbed the dead tokens; live pages untouched elsewhere
    assert len(canary_check(pk, pv, jnp.asarray([], jnp.int32))) == 0


# ---------------------------------------------------------------------------
# engine end-to-end: greedy drift floor + logit divergence


@pytest.fixture(scope="module")
def int8_engines():
    """Dense engine + int8-paged engine over identical params."""
    import os

    from paged_engine import paged_engine
    from swarmdb_tpu.backend.engine import Engine

    prev = os.environ.get("SWARMDB_KV_DTYPE")
    os.environ["SWARMDB_KV_DTYPE"] = "int8"
    try:
        cfg = TINY_DEBUG
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        fwd = lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c)
        init_cache = lambda b, s: llama.init_kv_cache(cfg, b, s)
        max_batch, max_seq, ps = 2, 64, 16
        maxp = pages_per_slot(max_seq, ps)
        num_pages = 1 + max_batch * maxp

        dense = Engine(fwd, init_cache, params, max_batch=max_batch,
                       max_seq=max_seq, eos_id=2, seed=0,
                       prefill_buckets=[16, 32])
        dense.start()
        paged = paged_engine(cfg, params, max_batch=max_batch,
                             max_seq=max_seq, page_size=ps,
                             num_pages=num_pages, eos_id=2, seed=0,
                             prefill_buckets=[16, 32])
        paged.start()
        yield dense, paged
        dense.stop()
        paged.stop()
    finally:
        if prev is None:
            os.environ.pop("SWARMDB_KV_DTYPE", None)
        else:
            os.environ["SWARMDB_KV_DTYPE"] = prev


def test_engine_int8_pool_is_quantized(int8_engines):
    _, paged = int8_engines
    assert is_quantized(paged.cache["k"])


def test_engine_int8_greedy_drift_floor(int8_engines):
    """Greedy decode on the int8 pool vs the dense engine: tokens may
    drift where logit gaps are inside the quantization budget, but the
    match rate must clear the documented floor (0.7 over 30 tokens on
    TINY_DEBUG; observed ~1.0)."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    dense, paged = int8_engines
    prompts = [[1, 5, 9], [4, 4, 4, 4, 4, 4, 4], [7, 3, 2, 11]]
    match = total = 0
    for prompt in prompts:
        td, _ = dense.generate_sync(prompt, SamplingParams(max_new_tokens=10))
        tp, _ = paged.generate_sync(prompt, SamplingParams(max_new_tokens=10))
        n = min(len(td), len(tp))
        match += sum(int(a == b) for a, b in zip(td[:n], tp[:n]))
        total += max(len(td), len(tp))
    assert total > 0
    assert match / total >= 0.7, (match, total)


def test_forward_paged_int8_logit_divergence(monkeypatch):
    """Per-step logit divergence bound: paged int8 decode (the chunked
    forward over the quantized pool) vs the dense forward, same prefix. The bound is the parity contract obs/analyze
    roofline A/Bs rely on (quantization is the only error source)."""
    monkeypatch.setenv("SWARMDB_KV_DTYPE", "int8")
    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B, max_seq, ps = 2, 32, 8
    maxp = pages_per_slot(max_seq, ps)
    prompt = jnp.asarray([[1, 5, 9, 2], [3, 3, 0, 0]], jnp.int32)
    plen = np.asarray([4, 2])
    pos = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32)[None], (B, 4))
    dense_cache = llama.init_kv_cache(cfg, B, max_seq)
    _, dense_cache = llama.forward(params, cfg, prompt, pos, dense_cache)

    pool = llama.init_paged_cache(cfg, B, max_seq, 1 + B * maxp, ps)
    assert is_quantized(pool["k"])
    table = np.zeros((B, maxp), np.int32)
    table[0, :] = [1, 2, 3, 4][:maxp]
    table[1, :] = [5, 6, 7, 8][:maxp]
    dk, dv = dense_cache
    padk = jnp.pad(dk[:, :, :4], [(0, 0), (0, 0), (0, 4), (0, 0), (0, 0)])
    padv = jnp.pad(dv[:, :, :4], [(0, 0), (0, 0), (0, 4), (0, 0), (0, 0)])
    pk = pool_insert_pages(
        pool["k"], jnp.asarray([1, 5], jnp.int32),
        padk.reshape(cfg.n_layers, B * 1, ps, cfg.n_kv_heads,
                     cfg.head_dim))
    pv = pool_insert_pages(
        pool["v"], jnp.asarray([1, 5], jnp.int32),
        padv.reshape(cfg.n_layers, B * 1, ps, cfg.n_kv_heads,
                     cfg.head_dim))
    cache_paged = {"k": pk, "v": pv, "page_table": jnp.asarray(table)}

    tok = jnp.asarray([[7], [11]], jnp.int32)
    worst = 0.0
    chunk = llama.init_chunk_kv(cfg, B, 3)
    for step in range(3):
        dpos = jnp.asarray([[int(plen[0]) + step], [int(plen[1]) + step]],
                           jnp.int32)
        ld, dense_cache = llama.forward(params, cfg, tok, dpos, dense_cache)
        lp, chunk = llama.forward_paged_chunked(
            params, cfg, tok, dpos, cache_paged, chunk,
            jnp.asarray(step, jnp.int32))
        worst = max(worst, float(np.max(np.abs(
            np.asarray(ld) - np.asarray(lp)))))
        tok = jnp.argmax(ld[:, -1], axis=-1).astype(jnp.int32)[:, None]
    # bucket-tail garbage note: the insert quantized whole pages whose
    # tails are zeros here, so amax comes from real tokens; bound is
    # pure quantization error through one attention + MLP stack
    assert worst < 0.35, worst
