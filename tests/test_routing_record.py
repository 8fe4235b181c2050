"""The served path says which experts it chose (ISSUE 33): ``moe_block``
reports its routing, every forward hands it on, the engine carries a
position's row to the request that owns it (``GenRequest.routing``), and
the prefix cache keeps a page's rows beside its tokens. A dense
configuration's programs, callbacks and records are as they were."""

import inspect
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from swarmdb_tpu.backend.engine import GenRequest, _unpack_resident_block
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.service import build_backend_engine
from swarmdb_tpu.models import llama, mixtral
from swarmdb_tpu.models.configs import TINY_DEBUG, TINY_MOE, get_config

PS = 8
L_ROUTED, TOP_K, N_EXPERTS = mixtral.routing_shape(TINY_MOE)


# ---------------------------------------------------------------- the model


def _expected_routing(logits, top_k, capacity):
    """``top_idx`` and ``within_cap`` as ``moe_block`` reckons them, in
    plain numpy: the k largest router logits a token, and a choice's place
    in its expert's queue over the flattened (token, choice) order."""
    top_idx = np.argsort(-logits, axis=-1, kind="stable")[:, :top_k]
    seen = np.zeros(logits.shape[-1], np.int64)
    within = np.zeros(top_idx.shape, bool)
    for n in range(top_idx.shape[0]):
        for j in range(top_k):
            within[n, j] = seen[top_idx[n, j]] < capacity
            seen[top_idx[n, j]] += 1
    return top_idx, within


@pytest.mark.parametrize("capacity_factor", [2.0, 0.5])
@pytest.mark.parametrize("dispatch", ["scatter", "einsum"])
def test_moe_block_reports_its_choices_and_its_drops(dispatch,
                                                     capacity_factor):
    E, D, F, k, B, T = 8, 16, 24, 2, 2, 12
    ks = jax.random.split(jax.random.PRNGKey(33), 5)
    # every third token is the same vector, so its two experts overflow
    x = jax.random.normal(ks[0], (B * T, D), jnp.float32)
    x = x.at[::3].set(x[0]).reshape(B, T, D)
    router = jax.random.normal(ks[1], (D, E), jnp.float32)
    wg = jax.random.normal(ks[2], (E, D, F), jnp.float32) * 0.1
    wu = jax.random.normal(ks[3], (E, D, F), jnp.float32) * 0.1
    wd = jax.random.normal(ks[4], (E, F, D), jnp.float32) * 0.1
    y, load, routing = mixtral.moe_block(
        x, router, wg, wu, wd, top_k=k, capacity_factor=capacity_factor,
        dispatch=dispatch)
    assert routing.shape == (B, T, k) and routing.dtype == jnp.int16
    assert load.shape == (E,)

    logits = np.asarray(x.reshape(-1, D) @ router)
    capacity = max(1, int(B * T * k * capacity_factor / E))
    top_idx, within = _expected_routing(logits, k, capacity)
    got = np.asarray(routing).reshape(-1, k)
    np.testing.assert_array_equal(mixtral.routing_experts(got), top_idx)
    np.testing.assert_array_equal(mixtral.routing_dropped(got), ~within)
    assert (~within).any() and within.any()

    # a follower that leaves out what the routing says was dropped gives
    # the block's output: the report is what the program computed
    sel = np.take_along_axis(logits, top_idx, axis=-1)
    gates = np.exp(sel - sel.max(-1, keepdims=True))
    gates = gates / gates.sum(-1, keepdims=True) * within
    xf = np.asarray(x.reshape(-1, D))
    want = np.zeros_like(xf)
    for n in range(xf.shape[0]):
        for j in range(k):
            e = top_idx[n, j]
            h = xf[n] @ np.asarray(wg[e])
            h = h / (1 + np.exp(-h)) * (xf[n] @ np.asarray(wu[e]))
            want[n] += gates[n, j] * (h @ np.asarray(wd[e]))
    np.testing.assert_allclose(np.asarray(y).reshape(-1, D), want,
                               rtol=2e-4, atol=2e-5)


def test_routing_code_round_trips():
    idx = jnp.asarray([[0, 3], [255, 7], [32767, 1]], jnp.int32)
    kept = jnp.asarray([[True, False], [False, True], [False, False]])
    code = np.asarray(mixtral.encode_routing(idx, kept))
    assert code.dtype == np.int16
    np.testing.assert_array_equal(mixtral.routing_experts(code), idx)
    np.testing.assert_array_equal(mixtral.routing_dropped(code), ~kept)
    assert mixtral.routing_shape(TINY_DEBUG) is None
    assert mixtral.routing_shape(TINY_MOE) == (2, 2, 4)


def _moe_setup():
    """``tests/test_llama.py``'s family set-up for the routed family, with
    ``forward``'s routing beside its logits."""
    cfg = TINY_MOE
    params = mixtral.init_params(cfg, jax.random.PRNGKey(4),
                                 dtype=jnp.float32)
    B, T, S = 2, 12, 16
    tokens = jnp.asarray(
        np.random.default_rng(9).integers(1, cfg.vocab_size, size=(B, T)),
        jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    _logits, cache, routing = llama.forward(
        params, cfg, tokens, positions,
        llama.init_kv_cache(cfg, B, S, dtype=jnp.float32))
    return cfg, params, tokens, cache, np.asarray(routing)


def _pages_of(cache, n_tokens, ps):
    """A page pool holding each row's first ``n_tokens`` of ``cache`` and
    the rows' page table (page 0 is the trash page)."""
    L, B, S, H, D = cache[0].shape
    maxp = S // ps
    table = jnp.arange(1, 1 + B * maxp, dtype=jnp.int32).reshape(B, maxp)

    def pool(c):
        live = jnp.where(jnp.arange(S)[None, :, None, None] < n_tokens,
                         c, 0.0)
        pages = live.reshape(L, B * maxp, ps, H, D)
        return jnp.concatenate([jnp.zeros_like(pages[:, :1]), pages], axis=1)

    return pool(cache[0]), pool(cache[1]), table


@pytest.mark.parametrize("name", [
    "forward_prefix_pages", "forward_prefix_lane", "forward_ragged_prefill",
    "forward_chunked", "forward_paged_chunked"])
def test_every_routed_forward_reports_forwards_routing(name):
    """The same tokens, the same numerics (float32, no call drops at
    these sizes): each forward reports, for the positions it computes, the
    rows ``forward`` reports for them, ``[.., T, L_routed, k]``."""
    cfg, params, tokens, cache, want = _moe_setup()
    B, T = tokens.shape
    assert want.shape == (B, T, L_ROUTED, TOP_K) and want.dtype == np.int16
    assert (want >= 0).all() and (want < N_EXPERTS).all()
    ps, P0 = 4, 8
    pool_k, pool_v, table = _pages_of(cache, P0, ps)

    if name == "forward_ragged_prefill":
        pool_k, pool_v, table = _pages_of(cache, 0, ps)
        *_, got = llama.forward_ragged_prefill(
            params, cfg, tokens[0], jnp.zeros((T,), jnp.int32),
            jnp.arange(T, dtype=jnp.int32), table[:1],
            jnp.asarray([0], jnp.int32), jnp.asarray([T], jnp.int32),
            jnp.asarray([0], jnp.int32), pool_k, pool_v)
        alone = llama.forward(
            params, cfg, tokens[:1], jnp.arange(T, dtype=jnp.int32)[None],
            llama.init_kv_cache(cfg, 1, T, dtype=jnp.float32))[-1]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone)[0])
        return
    if name.startswith("forward_prefix"):
        args = (params, cfg, tokens[:, P0:], table[:, :P0 // ps],
                jnp.full((B,), P0, jnp.int32), pool_k, pool_v)
        if name == "forward_prefix_lane":
            *_, got = llama.forward_prefix_lane(*args, T // ps)
        else:
            *_, got = llama.forward_prefix_pages(*args)
        np.testing.assert_array_equal(np.asarray(got), want[:, P0:])
        return

    history = tuple(jnp.where(
        jnp.arange(c.shape[2])[None, None, :, None, None] < P0, c, 0.0)
        for c in cache)
    paged = {"k": pool_k, "v": pool_v, "page_table": table}
    chunk = llama.init_chunk_kv(cfg, B, T - P0, dtype=jnp.float32)
    for step in range(T - P0):
        tok = tokens[:, P0 + step:P0 + step + 1]
        pos = jnp.full((B, 1), P0 + step, jnp.int32)
        at = jnp.asarray(step, jnp.int32)
        if name == "forward_chunked":
            _, chunk, got = llama.forward_chunked(
                params, cfg, tok, pos, history, chunk, at)
        else:
            _, chunk, got = llama.forward_paged_chunked(
                params, cfg, tok, pos, paged, chunk, at)
        assert got.shape == (B, 1, L_ROUTED, TOP_K)
        np.testing.assert_array_equal(np.asarray(got)[:, 0],
                                      want[:, P0 + step])


def test_a_routed_forward_always_reports_and_a_dense_one_never():
    cfg, params, tokens, _cache, want = _moe_setup()
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    # no switch: the configuration decides. The family's own ``forward``
    # is the same call without the routing, for logit comparisons
    plain = mixtral.forward(params, cfg, tokens, positions,
                            llama.init_kv_cache(cfg, B, 16,
                                                dtype=jnp.float32))
    assert len(plain) == 2
    stacked = jnp.moveaxis(jnp.asarray(want), 2, 0)    # as the scan stacks
    out, (routing,) = llama.take_routing(cfg, (("k", "v"), stacked))
    assert out == ("k", "v")
    np.testing.assert_array_equal(np.asarray(routing), want)
    dense = llama.init_params(TINY_DEBUG, jax.random.PRNGKey(4),
                              dtype=jnp.float32)
    out = llama.forward(dense, TINY_DEBUG, tokens, positions,
                        llama.init_kv_cache(TINY_DEBUG, B, 16,
                                            dtype=jnp.float32))
    assert len(out) == 2
    # the layer scan of a dense configuration stacks the cache alone
    assert llama.take_routing(TINY_DEBUG, ("k", "v")) == (("k", "v"), ())


# --------------------------------------------------------------- the engine


def _submit(eng, prompt, max_new, **kw):
    """One request and the event that fires when it is done; the record is
    read from the REQUEST inside ``on_done``, as a wrapper round it would."""
    done, seen = threading.Event(), {}
    req = GenRequest(prompt=list(prompt),
                     sampling=SamplingParams(max_new_tokens=max_new), **kw)

    def on_done(_rid, toks, reason):
        seen.update(tokens=list(toks), reason=reason, routing=req.routing,
                    complete=req.routing_complete)
        done.set()

    req.on_done = on_done
    eng.submit(req)
    return req, done, seen


def _run(eng, prompt, max_new, **kw):
    _req, done, seen = _submit(eng, prompt, max_new, **kw)
    assert done.wait(180)
    assert seen["reason"] in ("length", "eos")
    return seen


def _counters(eng):
    return {k: eng.metrics.counters[k].value for k in (
        "moe_assignments", "moe_dropped_assignments",
        "routing_incomplete_requests")}


@pytest.fixture(scope="module", params=["resident", "scan"])
def moe_engine(request):
    """A tiny routed paged engine on the resident loop, and on the scan
    path (``SWARMDB_EMIT_RING=0``, read when the engine is built)."""
    was = os.environ.get("SWARMDB_EMIT_RING")
    if request.param == "scan":
        os.environ["SWARMDB_EMIT_RING"] = "0"
    try:
        eng, _tok = build_backend_engine(
            TINY_MOE, max_batch=4, max_seq=128, paged=True, page_size=PS,
            decode_chunk=4)
    finally:
        if request.param == "scan":
            if was is None:
                os.environ.pop("SWARMDB_EMIT_RING")
            else:
                os.environ["SWARMDB_EMIT_RING"] = was
    assert eng._use_resident() == (request.param == "resident")
    eng.start()
    yield eng
    eng.stop()


def _rows(seen, prompt):
    """Positions that went through the stack: all but the last sampled
    token, which on "eos" is the eos itself (not among the tokens)."""
    return len(prompt) + len(seen["tokens"]) - (seen["reason"] != "eos")


def _check_record(seen, prompt):
    rows = _rows(seen, prompt)
    routing = seen["routing"]
    assert isinstance(routing, np.ndarray) and routing.dtype == np.int16
    assert routing.shape == (rows, L_ROUTED, TOP_K)
    assert seen["complete"] is True
    experts = mixtral.routing_experts(routing)
    assert (experts >= 0).all() and (experts < N_EXPERTS).all()
    # a token's k choices are k experts
    assert (experts[..., 0] != experts[..., 1]).all()


def test_engine_hands_every_request_its_routing(moe_engine):
    """Three requests at once, of other lengths: each record has one row
    for every position of prompt + generated but the last, and the rows
    are those positions' (a full-sequence ``forward`` over the engine's
    own weights chooses the same experts nearly everywhere: bf16 decode
    and prefill differ at a near tie, a record out of step would agree
    at chance, a sixth)."""
    eng = moe_engine
    before = _counters(eng)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, TINY_MOE.vocab_size, size=n).tolist()
               for n in (19, 37, 8)]
    pending = [_submit(eng, p, m) for p, m in zip(prompts, (11, 6, 14))]
    assigned = 0
    for (_req, done, seen), prompt in zip(pending, prompts):
        assert done.wait(180)
        _check_record(seen, prompt)
        seq = (prompt + seen["tokens"])[:_rows(seen, prompt)]
        want = llama.forward(
            eng.params, TINY_MOE, jnp.asarray([seq], jnp.int32),
            jnp.arange(len(seq), dtype=jnp.int32)[None],
            llama.init_kv_cache(TINY_MOE, 1, len(seq)))[-1]
        same = (np.sort(mixtral.routing_experts(seen["routing"]), -1)
                == np.sort(np.asarray(want)[0], -1)).all(-1)
        assert same.mean() > 0.9, same.mean()
        assigned += seen["routing"].size
    after = _counters(eng)
    assert after["moe_assignments"] - before["moe_assignments"] == assigned
    # 4 experts, top-2: the capacity is the token count, nothing drops
    assert after["moe_dropped_assignments"] == 0
    assert after["routing_incomplete_requests"] == 0
    balance = eng.metrics.latencies["moe_load_max_over_mean"].summary()
    assert balance["count"] >= L_ROUTED
    assert 1.0 <= balance["p50"] <= N_EXPERTS / TOP_K


def test_a_record_short_of_its_positions_reads_incomplete(moe_engine,
                                                          monkeypatch):
    """Whatever its path said: a wave whose routing never reached the
    slot (here the engine is made to drop one prefill's) leaves a record
    with fewer rows than positions, and that is marked and counted."""
    eng = moe_engine
    prompt = [11, 12, 13] * 7
    whole = _run(eng, prompt, 5)
    _check_record(whole, prompt)
    before = _counters(eng)

    def lose_the_wave():
        eng._wave_routing = []
        return None

    monkeypatch.setattr(eng, "_take_wave", lose_the_wave)
    other = [p + 1 for p in prompt]
    seen = _run(eng, other, 5)
    assert seen["complete"] is False
    assert len(seen["routing"]) < _rows(seen, other)
    after = _counters(eng)
    assert after["routing_incomplete_requests"] \
        - before["routing_incomplete_requests"] == 1


def test_load_balance_reads_even_and_skewed():
    """``moe_load_max_over_mean`` of known chunks, a layer an
    observation: 1.0 where the rows spread evenly, E / k where every row
    chose the same experts; a dropped choice (``~e``) counts for ``e``."""
    from types import SimpleNamespace

    from swarmdb_tpu.backend.engine import Engine
    from swarmdb_tpu.utils.metrics import MetricsRegistry

    me = SimpleNamespace(_routed=(2, 2, 4), metrics=MetricsRegistry())
    even = np.asarray([[0, 1], [2, 3]], np.int16)
    same = np.asarray([[1, ~2], [~1, 2]], np.int16)
    # [n=2 rows, L=2, k=2]: layer 0 even, layer 1 all on experts 1 and 2
    # (a slot's rows a list entry: both rows here are one slot's steps)
    Engine._observe_load(me, [np.stack([even, same], axis=1)])
    seen = me.metrics.latencies["moe_load_max_over_mean"].values()
    assert seen == [1.0, 2.0]


def _entries(eng):
    """The prefix cache's entries, a chain each: (page, tokens, rows)."""
    with eng._prefix._lock:
        return dict(eng._prefix._entries)


def test_a_cached_prefix_brings_the_rows_its_pages_were_registered_with(
        moe_engine):
    """A second turn starts its record with what the prefix cache holds
    for its hit pages, not with a recomputation: the cached rows are
    marked here (a dropped choice that this shape never produces) and the
    second turn's record carries the marks. After an eviction the pages
    are recomputed and registered with the rows of that recomputation."""
    eng = moe_engine
    rng = np.random.default_rng(77)
    p1 = rng.integers(3, TINY_MOE.vocab_size, size=3 * PS + 5).tolist()
    first = _run(eng, p1, 6)
    _check_record(first, p1)
    mine = {c: e for c, e in _entries(eng).items()
            if e[1] == tuple(p1[:PS]) or e[1] == tuple(p1[PS:2 * PS])
            or e[1] == tuple(p1[2 * PS:3 * PS])}
    assert len(mine) == 3
    with eng._prefix._lock:
        for chain, (page, toks, rows) in mine.items():
            rows = rows if isinstance(rows, np.ndarray) else rows.get()
            assert rows.shape == (PS, L_ROUTED, TOP_K)
            at = p1.index(toks[0])
            np.testing.assert_array_equal(rows,
                                          first["routing"][at:at + PS])
            eng._prefix._entries[chain] = (page, toks, ~rows)

    p2 = p1 + first["tokens"] + [11, 12, 13]
    second = _run(eng, p2, 5)
    _check_record(second, p2)
    np.testing.assert_array_equal(second["routing"][:3 * PS],
                                  ~first["routing"][:3 * PS])
    assert (second["routing"][3 * PS:] >= 0).all()

    # eviction, as Engine._paged_allocate does it, then the same prompt:
    # recomputed, registered anew, and a later turn starts with THOSE rows
    reclaimed = eng._prefix.evict_lru(10 ** 6)
    eng.paged.allocator.add_free(reclaimed)
    assert not _entries(eng)
    third = _run(eng, p2, 5)
    _check_record(third, p2)
    assert (third["routing"] >= 0).all()
    held = [e for e in _entries(eng).values() if e[1] == tuple(p2[:PS])]
    assert len(held) == 1
    rows = held[0][2] if isinstance(held[0][2], np.ndarray) \
        else held[0][2].get()
    np.testing.assert_array_equal(rows, third["routing"][:PS])
    fourth = _run(eng, p2 + third["tokens"] + [5], 4)
    n_full = len(p2) // PS * PS
    np.testing.assert_array_equal(fourth["routing"][:n_full],
                                  third["routing"][:n_full])
    assert fourth["complete"] is True


def test_a_rolling_resume_is_marked_incomplete_and_counted(moe_engine):
    """Kept pages come back without their routing (their custody is the
    caller's): the record holds the rows this request computed and says
    it is not complete."""
    eng = moe_engine
    assert eng.supports_rolling()
    kept = {}

    def on_pages(_rid, pages, written, tail):
        kept.update(pages=pages, written=written, tail=tail)

    p1 = np.random.default_rng(3).integers(
        3, TINY_MOE.vocab_size, size=21).tolist()
    first = _run(eng, p1, 7, keep_pages=True, on_pages=on_pages)
    assert first["complete"] is True
    before = _counters(eng)["routing_incomplete_requests"]
    new = kept["tail"] + [21, 22, 23, 24]
    second = _run(eng, new, 6, keep_pages=True, on_pages=on_pages,
                  resume_pages=list(kept["pages"]),
                  resume_len=kept["written"])
    assert second["complete"] is False
    assert second["routing"].shape == (_rows(second, new), L_ROUTED, TOP_K)
    assert _counters(eng)["routing_incomplete_requests"] == before + 1
    eng.rolling_free(kept["pages"])


# ------------------------------------------------------ packed ragged waves


def test_a_routed_ragged_engine_carries_the_streams_rows(monkeypatch):
    """``backend/service.py`` wires the packed ragged prefill for dense
    configurations only (a packed stream and a row-bucketed wave reckon
    other capacities), but the engine's side of it is general: with the
    routed forward wired in (TINY_MOE never drops, so the two agree), a
    prompt split over several waves gets one row a position in stream
    order, and the pages it registers keep their slice of it."""
    import swarmdb_tpu.backend.service as service

    real = service.PagedKV

    def paged_kv(**kw):
        spec = real(**kw)
        spec.prefill_ragged = (
            lambda p, toks, trow, tpos, tables, st, ln, pl, pk, pv:
                llama.forward_ragged_prefill(
                    p, TINY_MOE, toks, trow, tpos, tables, st, ln, pl, pk,
                    pv))
        return spec

    monkeypatch.setattr(service, "PagedKV", paged_kv)
    monkeypatch.setenv("SWARMDB_RAGGED_PREFILL", "1")
    eng, _tok = build_backend_engine(TINY_MOE, max_batch=4, max_seq=128,
                                     paged=True, page_size=PS,
                                     decode_chunk=4)
    assert eng._ragged_active() and eng._routed == (2, 2, 4)
    eng.start()
    try:
        rng = np.random.default_rng(61)
        # 61 tokens split as 32 + 16 + 8 + 4 + 1 over the ladder's waves
        p1 = rng.integers(3, TINY_MOE.vocab_size, size=61).tolist()
        first = _run(eng, p1, 7)
        _check_record(first, p1)
        seq = (p1 + first["tokens"])[:_rows(first, p1)]
        want = llama.forward(
            eng.params, TINY_MOE, jnp.asarray([seq], jnp.int32),
            jnp.arange(len(seq), dtype=jnp.int32)[None],
            llama.init_kv_cache(TINY_MOE, 1, len(seq)))[-1]
        same = (np.sort(mixtral.routing_experts(first["routing"]), -1)
                == np.sort(np.asarray(want)[0], -1)).all(-1)
        assert same.mean() > 0.9, same.mean()
        # every full prompt page was registered with its own rows
        for _page, toks, rows in _entries(eng).values():
            at = next(i for i in range(0, len(p1), PS)
                      if tuple(p1[i:i + PS]) == toks)
            rows = rows if isinstance(rows, np.ndarray) else rows.get()
            np.testing.assert_array_equal(rows,
                                          first["routing"][at:at + PS])
        p2 = p1 + first["tokens"] + [9, 9]
        second = _run(eng, p2, 4)
        _check_record(second, p2)
        n_full = len(p1) // PS * PS
        np.testing.assert_array_equal(second["routing"][:n_full],
                                      first["routing"][:n_full])
    finally:
        eng.stop()
    assert eng.metrics.counters["routing_incomplete_requests"].value == 0


# ------------------------------------------------------------------- drops


def test_dropped_choices_reach_the_record_and_the_counter():
    """8 experts, top-2: a call's capacity is half its tokens, and a
    prefill wave's padding queues for its two experts ahead of the later
    rows' tokens, so the served path drops. What a request's own rows say
    is what the counters say."""
    cfg = get_config("tiny-moe", n_experts=8)
    eng, _tok = build_backend_engine(cfg, max_batch=4, max_seq=128,
                                     paged=True, page_size=PS,
                                     decode_chunk=4)
    eng.start()
    try:
        rng = np.random.default_rng(8)
        prompts = [rng.integers(3, cfg.vocab_size, size=n).tolist()
                   for n in (9, 30, 17, 25)]
        pending = [_submit(eng, p, 9) for p in prompts]
        dropped = assigned = 0
        for (_req, done, seen), prompt in zip(pending, prompts):
            assert done.wait(180)
            assert seen["routing"].shape == (_rows(seen, prompt), 2, 2)
            assert (mixtral.routing_experts(seen["routing"]) < 8).all()
            dropped += int(mixtral.routing_dropped(seen["routing"]).sum())
            assigned += seen["routing"].size
    finally:
        eng.stop()
    got = _counters(eng)
    assert got["moe_assignments"] == assigned
    assert got["moe_dropped_assignments"] == dropped > 0


# ------------------------------------------------------------ fleet handoff


def test_a_fleet_handoff_is_marked_incomplete_and_counted():
    """The transit store carries a staged request's pages to the decode
    pool without their routing: the caller's request holds the rows its
    decode stage computed, ``routing_complete`` False, and the decode
    lane counted it."""
    from swarmdb_tpu.parallel.mesh import make_mesh
    from swarmdb_tpu.parallel.serving import build_serving_engine

    was = os.environ.get("SWARMDB_FLEET")
    os.environ["SWARMDB_FLEET"] = "prefill:1,decode:1"
    try:
        group, _info = build_serving_engine(
            TINY_MOE, make_mesh(2, data=2, model=1, expert=1), max_batch=4,
            max_seq=128, paged=True, page_size=PS, decode_chunk=4)
    finally:
        if was is None:
            os.environ.pop("SWARMDB_FLEET")
        else:
            os.environ["SWARMDB_FLEET"] = was
    assert group.fleet is not None
    group.start()
    try:
        prompt = list(range(5, 5 + 21))
        seen = _run(group, prompt, 8)
        c = group.lanes[0].metrics.counters
        assert c["fleet_handoffs"].value == 1
        assert seen["complete"] is False
        assert seen["routing"].shape[1:] == (L_ROUTED, TOP_K)
        assert 0 < len(seen["routing"]) < len(prompt) + len(seen["tokens"])
        assert sum(lane.metrics.counters["routing_incomplete_requests"].value
                   for lane in group.lanes) >= 1
    finally:
        group.stop()


# ------------------------------------------------------- a dense engine


@pytest.fixture(scope="module")
def dense_engine():
    eng, _tok = build_backend_engine(TINY_DEBUG, max_batch=4, max_seq=96,
                                     paged=True, page_size=PS,
                                     decode_chunk=4)
    eng.start()
    yield eng
    eng.stop()


def test_a_dense_engine_reports_nothing(dense_engine):
    eng = dense_engine
    emitted = []
    emit = eng._resident_emit

    def spy(*operands):
        emitted.append(len(operands))
        return emit(*operands)

    eng._resident_emit = spy
    try:
        seen = _run(eng, [4, 5, 6] * 7, 9)
    finally:
        eng._resident_emit = emit
    assert seen["routing"] is None and seen["complete"] is False
    # the fixture's engine traced its resident program under the spy: the
    # callback took the chunk's one packed operand (tokens, logprobs, the
    # chunk's index and the loop's done row; no routing part)
    assert emitted and set(emitted) == {1}
    assert all(v == 0 for v in _counters(eng).values())
    assert eng.metrics.latencies["moe_load_max_over_mean"].summary()[
        "count"] == 0
    assert eng._routed is None
    # the emission callback's signature is that one operand, for a dense
    # and a routed program alike: whether the buffer has a routing part is
    # a fact of the configuration, not an argument
    params = list(inspect.signature(emit).parameters.values())
    assert [p.name for p in params] == ["packed"]
    K1, B = eng.decode_chunk + 1, eng.max_batch
    assert _unpack_resident_block(
        np.zeros(2 * K1 * B + 1 + B, np.int32), K1, B, None)[4] is None


@pytest.mark.parametrize("routed", [False, True])
def test_compiled_programs_outputs(routed, dense_engine):
    """Every program of the warm-up plan: a dense engine's decode programs
    return the five outputs they returned and its prefill programs their
    four (pools, fed tokens, fed logprobs); a routed engine's return the
    routing after them, ``[K, B, L_routed, k]`` a chunk and
    ``[rows, T, L_routed, k]`` a wave."""
    if routed:
        eng, _tok = build_backend_engine(TINY_MOE, max_batch=4, max_seq=96,
                                         paged=True, page_size=PS,
                                         decode_chunk=4)
    else:
        eng = dense_engine
    seen = set()
    for fn, specs in eng.warmup_call_plan():
        name = getattr(fn, "__name__", None) or getattr(
            getattr(fn, "__wrapped__", None), "__name__", "program")
        if name in seen:
            continue
        seen.add(name)
        out = jax.eval_shape(fn, *specs)
        if "resident" in name:
            continue       # a while_loop's carry: the callback takes the rest
        if "decode" in name:
            assert len(out) == 5 + routed, name
            if routed:
                assert out[5].shape == (4, 4, L_ROUTED, TOP_K)
                assert out[5].dtype == jnp.int16
        elif "prefill" in name:
            assert len(out) == 4 + routed, name
            if routed:
                assert out[4].shape[-2:] == (L_ROUTED, TOP_K)
                assert out[4].dtype == jnp.int16
    assert any("decode" in n for n in seen)
    assert any("prefill" in n for n in seen)
