"""Pallas decode-attention kernel vs the XLA einsum reference path.

Runs in interpreter mode on CPU (pallas_guide: `interpret=True`); the same
kernel compiles to Mosaic on a real TPU.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmdb_tpu.ops.attention_pallas import decode_gqa_attention
from swarmdb_tpu.ops.layers import gqa_attention


def _rand_case(B=4, S=64, Hq=8, Hkv=2, D=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)).astype(np.float32))
    lengths = jnp.asarray(rng.integers(1, S + 1, size=B).astype(np.int32))
    return q, k, v, lengths


def test_matches_einsum_reference():
    q, k, v, lengths = _rand_case()
    out = decode_gqa_attention(q, k, v, lengths, interpret=True)
    ref = gqa_attention(q[:, None], k, v, (lengths - 1)[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_respects_lengths():
    """Entries beyond a slot's length must not influence its output."""
    q, k, v, lengths = _rand_case(seed=1)
    lengths = jnp.full_like(lengths, 3)
    out1 = decode_gqa_attention(q, k, v, lengths, interpret=True)
    # poison everything past position 3
    k2 = k.at[:, 3:].set(1e6)
    v2 = v.at[:, 3:].set(-1e6)
    out2 = decode_gqa_attention(q, k2, v2, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)


def test_bfloat16_cache():
    q, k, v, lengths = _rand_case(seed=2)
    out = decode_gqa_attention(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16), lengths, interpret=True,
    )
    ref = gqa_attention(
        q.astype(jnp.bfloat16)[:, None], k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16), (lengths - 1)[:, None],
    )[:, 0]
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_gqa_attention_dispatch_env(monkeypatch):
    """SWARMDB_PALLAS=1 routes T==1 through the kernel with identical
    results to the einsum path."""
    q, k, v, lengths = _rand_case(seed=3)
    pos = (lengths - 1)[:, None]
    ref = gqa_attention(q[:, None], k, v, pos)
    monkeypatch.setenv("SWARMDB_PALLAS", "1")
    out = gqa_attention(q[:, None], k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_full_model_decode_with_pallas(monkeypatch):
    """End-to-end: tiny Llama forward with the Pallas decode path on."""
    from swarmdb_tpu.models import llama
    from swarmdb_tpu.models.configs import get_config

    cfg = get_config("tiny-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    cache = llama.init_kv_cache(cfg, 2, 32)
    tokens = jnp.asarray([[5], [9]], jnp.int32)
    positions = jnp.asarray([[0], [0]], jnp.int32)

    ref_logits, _ = llama.forward(params, cfg, tokens, positions, cache)
    monkeypatch.setenv("SWARMDB_PALLAS", "1")
    out_logits, _ = llama.forward(params, cfg, tokens, positions, cache)
    np.testing.assert_allclose(np.asarray(out_logits), np.asarray(ref_logits),
                               rtol=2e-2, atol=2e-2)


# ---- dense two-segment (chunked) kernel -----------------------------------


def _chunk_case(B=4, S=64, Kc=8, Hq=8, Hkv=2, D=16, seed=3):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)).astype(np.float32))
    ck = jnp.asarray(rng.normal(size=(B, Kc, Hkv, D)).astype(np.float32))
    cv = jnp.asarray(rng.normal(size=(B, Kc, Hkv, D)).astype(np.float32))
    starts = jnp.asarray(rng.integers(0, S - Kc, size=B).astype(np.int32))
    return q, k, v, ck, cv, starts


@pytest.mark.parametrize("step_val", [0, 3, 7])
def test_chunked_matches_einsum_reference(step_val, monkeypatch):
    from swarmdb_tpu.ops.attention_pallas import decode_gqa_attention_chunked
    from swarmdb_tpu.ops.layers import gqa_attention_chunked

    # the reference must be the EINSUM path even if the environment
    # exports SWARMDB_PALLAS=1 (kernel-vs-itself would be vacuous)
    monkeypatch.setenv("SWARMDB_PALLAS", "0")
    q, k, v, ck, cv, starts = _chunk_case()
    step = jnp.int32(step_val)
    out = decode_gqa_attention_chunked(
        q, k, v, ck, cv, starts, step, tile=32, interpret=True)
    ref = gqa_attention_chunked(
        q[:, None], k, v, ck, cv, (starts + step_val)[:, None], step)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_chunked_ignores_dead_cache_and_future_chunk():
    """Cache entries >= start (previous occupant's garbage) and chunk
    entries > step must not influence the output."""
    from swarmdb_tpu.ops.attention_pallas import decode_gqa_attention_chunked

    q, k, v, ck, cv, starts = _chunk_case(seed=4)
    starts = jnp.full_like(starts, 5)
    step = jnp.int32(2)
    out1 = decode_gqa_attention_chunked(
        q, k, v, ck, cv, starts, step, tile=32, interpret=True)
    k2 = k.at[:, 5:].set(1e6)
    v2 = v.at[:, 5:].set(-1e6)
    ck2 = ck.at[:, 3:].set(1e6)
    cv2 = cv.at[:, 3:].set(-1e6)
    out2 = decode_gqa_attention_chunked(
        q, k2, v2, ck2, cv2, starts, step, tile=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)


def test_chunked_sliding_window_parity(monkeypatch):
    from swarmdb_tpu.ops.attention_pallas import decode_gqa_attention_chunked
    from swarmdb_tpu.ops.layers import gqa_attention_chunked

    monkeypatch.setenv("SWARMDB_PALLAS", "0")
    q, k, v, ck, cv, starts = _chunk_case(seed=5)
    step = jnp.int32(4)
    out = decode_gqa_attention_chunked(
        q, k, v, ck, cv, starts, step, window=16, tile=32, interpret=True)
    ref = gqa_attention_chunked(
        q[:, None], k, v, ck, cv, (starts + 4)[:, None], step,
        window=16)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_chunked_dispatch_env(monkeypatch):
    """SWARMDB_PALLAS=1 routes gqa_attention_chunked through the kernel
    (interpret off-TPU) and matches the einsum path exactly enough."""
    from swarmdb_tpu.ops import layers

    q, k, v, ck, cv, starts = _chunk_case(seed=6)
    step = jnp.int32(1)
    qpos = (starts + 1)[:, None]
    monkeypatch.setenv("SWARMDB_PALLAS", "0")
    ref = layers.gqa_attention_chunked(q[:, None], k, v, ck, cv, qpos, step)
    monkeypatch.setenv("SWARMDB_PALLAS", "1")
    out = layers.gqa_attention_chunked(q[:, None], k, v, ck, cv, qpos, step)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---- ragged paged PREFILL kernel (ISSUE 11) --------------------------------


def _ragged_case(rows, ps=8, maxp=6, Hq=8, Hkv=2, D=16, seed=0,
                 dtype=np.float32):
    """Build a packed ragged wave from ``rows`` = [(prefix_len,
    suffix_len)]: page pool + per-row tables covering each prefix,
    packed q / suffix K/V streams, and the descriptor arrays."""
    rng = np.random.default_rng(seed)
    R = len(rows)
    W = sum(s for _, s in rows)
    P = 1 + sum(-(-p // ps) for p, _ in rows) + 2
    kp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)).astype(dtype))
    vp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)).astype(dtype))
    tables = np.zeros((R, maxp), np.int32)
    starts = np.zeros(R, np.int32)
    lens = np.zeros(R, np.int32)
    plens = np.zeros(R, np.int32)
    tok_row = np.zeros(W, np.int32)
    nxt, off = 1, 0
    for r, (p, s) in enumerate(rows):
        n = -(-p // ps)
        assert n <= maxp
        tables[r, :n] = range(nxt, nxt + n)
        nxt += n
        starts[r], lens[r], plens[r] = off, s, p
        tok_row[off:off + s] = r
        off += s
    q = jnp.asarray(rng.normal(size=(W, Hq, D)).astype(dtype))
    sk = jnp.asarray(rng.normal(size=(W, Hkv, D)).astype(dtype))
    sv = jnp.asarray(rng.normal(size=(W, Hkv, D)).astype(dtype))
    return (q, sk, sv, kp, vp, jnp.asarray(tables), jnp.asarray(starts),
            jnp.asarray(lens), jnp.asarray(plens)), jnp.asarray(tok_row)


_MIXED_ROWS = [(0, 5), (13, 9), (7, 1), (20, 16)]  # page-crossing prefixes


def _ragged_kernel_vs_reference(rows, tol=2e-5, window=None, **kw):
    from swarmdb_tpu.ops.attention_pallas import (
        ragged_paged_prefill_attention)
    from swarmdb_tpu.ops.layers import ragged_prefill_attention_reference

    args, tok_row = _ragged_case(rows, **kw)
    ref = ragged_prefill_attention_reference(*args, tok_row, window=window)
    out = ragged_paged_prefill_attention(*args, window=window, tile=16,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_ragged_mixed_rows_cross_page_boundaries():
    """Mixed suffix lengths with prefixes that cross page boundaries —
    the full acceptance grid shape — within 2e-5 of the dense XLA
    reference."""
    _ragged_kernel_vs_reference(_MIXED_ROWS)


@pytest.mark.parametrize("Hq,Hkv", [(8, 8), (8, 2), (8, 1), (4, 4)])
def test_ragged_gqa_head_ratios(Hq, Hkv):
    _ragged_kernel_vs_reference([(0, 7), (9, 12), (16, 3)], Hq=Hq,
                                Hkv=Hkv, seed=Hq * 10 + Hkv)


def test_ragged_single_token_rows():
    """Every row contributes exactly one query token (the wave shape a
    burst of cache-hit turns produces)."""
    _ragged_kernel_vs_reference([(8, 1), (0, 1), (23, 1), (16, 1)], seed=3)


def test_ragged_empty_row_is_inert():
    """A dead descriptor row (len 0) must not perturb its neighbors and
    must not produce NaNs."""
    from swarmdb_tpu.ops.attention_pallas import (
        ragged_paged_prefill_attention)

    rows = [(0, 5), (13, 9), (0, 0), (20, 16)]
    args, _ = _ragged_case(rows, seed=4)
    out = ragged_paged_prefill_attention(*args, tile=16, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    # and the surviving rows still match the reference exactly
    _ragged_kernel_vs_reference(rows, seed=4)


def test_ragged_sliding_window_parity():
    _ragged_kernel_vs_reference(_MIXED_ROWS, window=7, seed=5)


def test_ragged_bfloat16():
    from swarmdb_tpu.ops.attention_pallas import (
        ragged_paged_prefill_attention)
    from swarmdb_tpu.ops.layers import ragged_prefill_attention_reference

    args, tok_row = _ragged_case(_MIXED_ROWS, seed=6)
    bf = [a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a
          for a in args]
    out = ragged_paged_prefill_attention(*bf, tile=16, interpret=True)
    ref = ragged_prefill_attention_reference(*bf, tok_row)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_ragged_reference_anchored_on_prefix_attention():
    """The ragged reference itself must agree with the TRUSTED two-
    segment prefill attention (gqa_attention_prefix) row by row — so the
    kernel parity above is anchored to the path serving already uses,
    not to a second implementation of the same bug."""
    from swarmdb_tpu.ops.layers import (gqa_attention_prefix,
                                        ragged_prefill_attention_reference)

    args, tok_row = _ragged_case(_MIXED_ROWS, seed=7)
    (q, sk, sv, kp, vp, tables, starts, lens, plens) = args
    out = ragged_prefill_attention_reference(*args, tok_row)
    ps, maxp = kp.shape[1], tables.shape[1]
    Pt = maxp * ps
    for r, (p, s) in enumerate(_MIXED_ROWS):
        s0 = int(starts[r])
        kp_r = kp[tables[r]].reshape(1, Pt, *kp.shape[2:])
        vp_r = vp[tables[r]].reshape(1, Pt, *vp.shape[2:])
        ref_r = gqa_attention_prefix(
            q[None, s0:s0 + s], kp_r, vp_r, sk[None, s0:s0 + s],
            sv[None, s0:s0 + s], jnp.asarray([p], jnp.int32))[0]
        np.testing.assert_allclose(
            np.asarray(out[s0:s0 + s]), np.asarray(ref_r),
            rtol=2e-5, atol=2e-5)


def test_ragged_dispatch_env(monkeypatch):
    """SWARMDB_PALLAS=1 routes ragged_prefill_dispatch through the
    kernel (interpret off-TPU, incl. the sublane pad for tiny waves) and
    matches the reference."""
    from swarmdb_tpu.ops import layers

    rows = [(8, 3), (0, 2)]  # W=5: exercises the %8 sublane pad
    args, tok_row = _ragged_case(rows, seed=8)
    monkeypatch.setenv("SWARMDB_PALLAS", "0")
    ref = layers.ragged_prefill_dispatch(*args, tok_row)
    monkeypatch.setenv("SWARMDB_PALLAS", "1")
    out = layers.ragged_prefill_dispatch(*args, tok_row)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---- the ragged kernel's walk (ISSUE 32): rows that meet a query block,
# ---- their live pages a block a trip, their suffix tiles up to the block's

_WPS, _WMAXP, _WTILE = 8, 40, 16
_WBLOCK = 128            # tokens a prefix trip: `_pages_per_block` * ps
_WFULL = _WPS * _WMAXP   # the table's full width, 320 tokens


def _walk_case(rows, *, Hq=8, Hkv=2, D=16, R=None, pad=0, shuffle=False,
               layer=None, dtype=np.float32, window=None, seed=0):
    """Kernel (interpret mode) against the dense reference on one wave.
    ``rows`` = [(prefix_len, suffix_len)], (0, 0) a dead row wherever it
    stands; ``R`` pads the descriptors with dead rows and ``pad`` the
    stream with tokens of no row (their output is zero). ``shuffle`` deals
    the page ids out of order; ``layer`` = (l, L) puts the pool at slice
    l of a flat [L*P, ...] pool, the kernel's table offset by l * P."""
    from swarmdb_tpu.ops.attention_pallas import (
        _pages_per_block, ragged_paged_prefill_attention)
    from swarmdb_tpu.ops.layers import ragged_prefill_attention_reference

    ps, maxp = _WPS, _WMAXP
    assert _pages_per_block(ps, Hkv, D, np.dtype(dtype).itemsize,
                            maxp) * ps == _WBLOCK
    rng = np.random.default_rng(seed)
    rows = list(rows) + [(0, 0)] * ((R or len(rows)) - len(rows))
    R = len(rows)
    W = sum(s for _, s in rows) + pad
    P = 1 + sum(-(-p // ps) for p, _ in rows) + 2
    ids = np.arange(1, P)
    if shuffle:
        rng.shuffle(ids)
    tables = np.zeros((R, maxp), np.int32)
    starts, lens, plens = (np.zeros(R, np.int32) for _ in range(3))
    tok_row = np.full(W, R, np.int32)
    nxt = off = 0
    for r, (p, s) in enumerate(rows):
        n = -(-p // ps)
        assert n <= maxp
        tables[r, :n] = ids[nxt:nxt + n]
        nxt += n
        if s:
            starts[r] = off
        lens[r], plens[r] = s, p
        tok_row[off:off + s] = r
        off += s

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    q, sk, sv = arr(W, Hq, D), arr(W, Hkv, D), arr(W, Hkv, D)
    kp, vp = arr(P, ps, Hkv, D), arr(P, ps, Hkv, D)
    desc = tuple(jnp.asarray(a) for a in (starts, lens, plens))
    ref = ragged_prefill_attention_reference(
        q, sk, sv, kp, vp, jnp.asarray(tables), *desc,
        jnp.asarray(tok_row), window=window)
    pool_k, pool_v, tbl = kp, vp, tables
    if layer is not None:
        l, L = layer

        def flat(own):   # other layers' pages hold other numbers
            return jnp.concatenate(
                [own if i == l else arr(*own.shape) for i in range(L)])

        pool_k, pool_v, tbl = flat(kp), flat(vp), tables + l * P
    out = ragged_paged_prefill_attention(
        q, sk, sv, pool_k, pool_v, jnp.asarray(tbl), *desc,
        window=window, tile=_WTILE, interpret=True)
    assert out.dtype == q.dtype
    out, live = np.asarray(out, np.float32), tok_row < R
    assert (out[~live] == 0).all()
    tol = 2e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(out[live], np.asarray(ref, np.float32)[live],
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("kw", [
    # one prefix length a case, beside a fresh row and a mid-block one
    *[pytest.param(dict(rows=[(n, 5), (0, 9), (77, 3)]), id=f"prefix-{n}")
      for n in (0, 1, _WPS, _WBLOCK - 1, _WBLOCK, _WBLOCK + 1,
                2 * _WBLOCK, _WFULL - 1, _WFULL)],
    # a row over two and three query blocks, its head and tail sharing
    # blocks with its neighbours
    pytest.param(dict(rows=[(13, 7), (40, 20), (0, 5)]),
                 id="row-spans-two-blocks"),
    pytest.param(dict(rows=[(0, 3), (_WBLOCK + 9, 40), (8, 5)]),
                 id="row-spans-three-blocks"),
    pytest.param(dict(rows=[(0, 64)]), id="fresh-row-of-four-blocks"),
    pytest.param(dict(rows=[(0, 0), (21, 6), (0, 0), (0, 0), (130, 11),
                            (0, 0)]),
                 id="dead-rows-before-between-after"),
    pytest.param(dict(rows=[(150, 30)], R=16, pad=2),
                 id="R16-one-live-row"),
    pytest.param(dict(rows=[(0, 0)] * 4, pad=24), id="all-dead"),
    pytest.param(dict(rows=[(200, 17), (31, 8), (0, 4)], shuffle=True),
                 id="page-ids-out-of-order"),
    *[pytest.param(dict(rows=[(200, 17), (0, 4), (129, 20)], shuffle=True,
                        layer=(l, 3)), id=f"flat-pool-layer-{l}")
      for l in (0, 2)],
    *[pytest.param(dict(rows=[(140, 19), (0, 6), (9, 12)], Hq=2 * g,
                        Hkv=2), id=f"G{g}")
      for g in (1, 4, 8)],
    *[pytest.param(dict(rows=[(300, 21), (131, 30), (0, 40)], window=w),
                   id=f"window-{w}")
      for w in (5, _WBLOCK, 200)],
    pytest.param(dict(rows=[(200, 21), (0, 12), (129, 3)],
                      dtype=jnp.bfloat16), id="bf16"),
])
def test_ragged_kernel_walks_live_rows_and_pages(kw):
    """The ragged prefill kernel (grid (query block, row); prefix pages
    and suffix tiles copied by the kernel itself, a 128-token block a
    trip) agrees with the dense reference wherever a row's prefix ends
    (before, at and after a block's edge, at the table's full width),
    however a row lies across query blocks, and whichever rows are dead."""
    _walk_case(**kw)


@pytest.mark.parametrize("cut", (5, _WTILE, 37))
def test_ragged_row_split_over_two_waves_reads_back_its_pages(cut):
    """A row longer than its wave rides two: the head's K/V land in the
    row's pages and the tail's wave finds them there as prefix
    (``prefix_len`` advanced). Both waves together give what one wave
    gives."""
    from swarmdb_tpu.ops.attention_pallas import (
        ragged_paged_prefill_attention)

    ps, maxp, Hq, Hkv, D = _WPS, _WMAXP, 8, 2, 16
    rng = np.random.default_rng(cut)
    p0, n = 123, 60          # cached prefix, new tokens of the split row
    other = (19, 7)          # a neighbour in both waves
    P = 1 + 2 * maxp

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    kp, vp = np.array(arr(P, ps, Hkv, D)), np.array(arr(P, ps, Hkv, D))
    tables = np.zeros((2, maxp), np.int32)
    tables[0] = rng.permutation(np.arange(1, 1 + maxp))
    tables[1] = rng.permutation(np.arange(1 + maxp, 1 + 2 * maxp))
    q, sk, sv = arr(n, Hq, D), arr(n, Hkv, D), arr(n, Hkv, D)
    oq, ok, ov = (arr(other[1], h, D) for h in (Hq, Hkv, Hkv))

    def wave(lo, hi, plen):
        """Tokens [lo, hi) of the split row, then the neighbour."""
        args = [jnp.concatenate([a[lo:hi], b])
                for a, b in ((q, oq), (sk, ok), (sv, ov))]
        desc = [jnp.asarray(a, jnp.int32) for a in
                ([0, hi - lo], [hi - lo, other[1]], [plen, other[0]])]
        return ragged_paged_prefill_attention(
            *args, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            *desc, tile=_WTILE, interpret=True)[:hi - lo]

    whole = wave(0, n, p0)
    head = wave(0, cut, p0)
    for i in range(cut):     # the engine's paged_write_ragged
        pos = p0 + i
        kp[tables[0, pos // ps], pos % ps] = np.asarray(sk[i])
        vp[tables[0, pos // ps], pos % ps] = np.asarray(sv[i])
    tail = wave(cut, n, p0 + cut)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(head), np.asarray(tail)]),
        np.asarray(whole), rtol=2e-5, atol=2e-5)
