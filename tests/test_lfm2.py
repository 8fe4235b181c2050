"""The family with a mixer a layer (models/lfm2.py) at tiny widths: every
served forward against the plain whole-sequence ``forward``; conv state
beside pages through the engine (a prefix hit gives what the cold request
gives, a page without its state is forgone, not resumed behind); the
sigmoid router's bias chooses and never gates; a token's result does not
depend on what shares its call; the paths that cannot carry state refuse
by name."""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmdb_tpu.backend.engine import Engine, GenRequest
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.service import build_backend_engine
from swarmdb_tpu.models import lfm2, llama
from swarmdb_tpu.models.configs import TINY_LFM2 as CFG
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.ops.paged_kv import paged_write_ragged

PS, SLOTS, PAGES, MAX_SEQ = 16, 4, 64, 256
F32 = jnp.float32


@pytest.fixture(scope="module")
def params():
    return lfm2.init_params(CFG, jax.random.PRNGKey(11), F32)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(5), (3, 96), 3,
                                         CFG.vocab_size))


def _highest(fn):
    """``fn`` of (params, CFG, ...) jitted once a shape, float32 matmuls
    at ``highest``: the cases share programs, not eager dispatches."""
    def run(params, *args):
        with jax.default_matmul_precision("highest"):
            return fn(params, CFG, *args)
    return jax.jit(run)


_FORWARD = _highest(llama.forward)
_RAGGED = _highest(llama.forward_ragged_prefill)
_CHUNKED = _highest(llama.forward_paged_chunked)


def whole(params, toks):
    """Logits [T, V] of one whole sequence by the plain forward."""
    T = len(toks)
    logits, _cache, _routing = _FORWARD(
        params, jnp.asarray(toks)[None], jnp.arange(T)[None],
        llama.init_kv_cache(CFG, 1, T, F32))
    return np.asarray(logits[0])


class Served:
    """The served path's model calls around a float32 pool, with the seed
    and scatter rules of ``Engine._prefill_ragged_insert``: a wave of rows
    ``(slot, tokens, first position, table row, src)``, ``src`` a page id
    to resume behind, 0 for a cold row, -1 for the slot's own state."""

    def __init__(self, params):
        self.params = params
        self.cache = llama.init_paged_cache(CFG, SLOTS, MAX_SEQ, PAGES, PS,
                                            F32)
        assert self.cache["state"].dtype == F32

    def wave(self, rows, width):
        R, maxp = SLOTS, MAX_SEQ // PS
        toks = np.zeros(width, np.int32)
        tok_row = np.full(width, R, np.int32)
        tok_pos = np.full(width, maxp * PS, np.int32)
        starts, lens, plens = (np.zeros(R, np.int32) for _ in range(3))
        tables = np.zeros((R, maxp), np.int32)
        src = np.zeros(R, np.int32)
        slots = np.full(R, SLOTS, np.int32)
        at = 0
        for r, (slot, row_toks, p0, table, s) in enumerate(rows):
            n = len(row_toks)
            toks[at:at + n] = row_toks
            tok_row[at:at + n] = r
            tok_pos[at:at + n] = np.arange(p0, p0 + n)
            starts[r], lens[r], plens[r] = at, n, p0
            tables[r, :len(table)] = table
            src[r], slots[r] = s, slot
            at += n
        c = self.cache
        seed = lfm2.seed_state(jnp.asarray(src), jnp.asarray(slots),
                               c["state"], c["page_state"])
        (logits, sk, sv, row_state, ends_state, end_pages,
         routing) = _RAGGED(
            self.params, jnp.asarray(toks), jnp.asarray(tok_row),
            jnp.asarray(tok_pos), jnp.asarray(tables),
            jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(plens),
            c["k"], c["v"], seed)
        c["k"], c["v"] = paged_write_ragged(
            c["k"], c["v"], sk, sv, jnp.asarray(tok_row),
            jnp.asarray(tok_pos), jnp.asarray(tables))
        c["state"] = c["state"].at[:, slots].set(row_state, mode="drop")
        c["page_state"] = c["page_state"].at[:, end_pages].set(ends_state)
        for r, (slot, _t, _p, table, _s) in enumerate(rows):
            c["page_table"] = c["page_table"].at[slot, :len(table)].set(
                jnp.asarray(table, jnp.int32))
        assert routing.shape == (width, CFG.n_routed_layers,
                                 CFG.experts_per_token)
        assert int(routing.min()) >= 0          # nothing is dropped
        return np.asarray(logits)

    def chunk(self, feed, pos0, K=8):
        """One decode chunk, teacher-forced: ``feed`` [K, SLOTS] tokens,
        ``pos0`` [SLOTS] first positions. Returns logits [K, SLOTS, V]."""
        chunk_kv = llama.init_chunk_kv(CFG, SLOTS, K, F32)
        out = []
        for s in range(K):
            logits, chunk_kv, _routing = _CHUNKED(
                self.params, jnp.asarray(feed[s])[:, None],
                jnp.asarray(pos0 + s)[:, None], self.cache, chunk_kv,
                jnp.int32(s))
            out.append(np.asarray(logits[:, 0]))
        self.cache = llama.merge_paged_chunk(self.cache, chunk_kv,
                                             jnp.asarray(pos0))
        return np.stack(out)


TOL = dict(atol=2e-4, rtol=0)


def test_layer_plan_of_the_published_cut_is_a_period_and_a_scan():
    lt = ("conv", "conv", "full_attention", "conv") * 4
    cfg = get_config("tiny-lfm2", n_layers=16, layer_types=lt)
    plan = lfm2.layer_plan(cfg)
    assert [n for _p, n in plan] == [1, 3]
    assert [len(p) for p, _n in plan] == [4, 4]
    assert [f for _m, f in plan[0][0]] == ["dense", "dense", "moe", "moe"]
    assert lfm2.routing_shape(cfg) == (14, 2, 8)
    # a configuration whose layers all attend has no plan of its own
    assert get_config("tiny-debug").layer_types is None


def test_cold_rows_packed_in_one_wave_match_the_whole_forward(params,
                                                              tokens):
    """Two rows back to back in a padded stream: neither row's convolution
    reads its neighbour, and padding takes no part."""
    s = Served(params)
    a, b = tokens[0][:45], tokens[1][:23]
    got = s.wave([(0, a, 0, [1, 2, 3], 0), (1, b, 0, [4, 5], 0)], 128)
    np.testing.assert_allclose(got[0], whole(params, a)[-1], **TOL)
    np.testing.assert_allclose(got[1], whole(params, b)[-1], **TOL)
    # and the same row alone in the smallest wave that holds it
    alone = Served(params).wave([(2, b, 0, [9, 8], 0)], 32)
    np.testing.assert_allclose(alone[0], got[1], **TOL)


@pytest.mark.parametrize("boundary", [16, 32, 48, 64])
def test_a_prefix_hit_at_each_page_boundary_matches_cold(params, tokens,
                                                         boundary):
    """A row that resumes behind a cached page is seeded with the state
    the wave that wrote the page left under its id."""
    s = Served(params)
    first = tokens[0][:70]
    s.wave([(0, first, 0, [1, 2, 3, 4, 5], 0)], 128)
    # another sequence shares the first `boundary` tokens
    later = np.concatenate([first[:boundary], tokens[2][:21]])
    hits = list(range(1, boundary // PS + 1))
    got = s.wave([(1, later[boundary:], boundary, hits + [20, 21], hits[-1])],
                 32)
    np.testing.assert_allclose(got[0], whole(params, later)[-1], **TOL)
    # seeded with zeros instead, the same row reads otherwise
    wrong = s.wave([(2, later[boundary:], boundary, hits + [22, 23], 0)], 32)
    assert np.abs(wrong[0] - got[0]).max() > 1e-3


def test_a_split_prompt_continues_from_its_slots_state(params, tokens):
    """A row spread over two waves, cut in the middle of a page."""
    s = Served(params)
    seq = tokens[1][:61]
    table = [3, 4, 5, 6]
    s.wave([(2, seq[:27], 0, table, 0)], 32)
    got = s.wave([(2, seq[27:], 27, table, -1)], 64)
    np.testing.assert_allclose(got[0], whole(params, seq)[-1], **TOL)
    # page 4 ends at position 31, inside the second wave: its state is
    # what a row that resumes there needs
    resumed = s.wave([(0, seq[32:], 32, [3, 4, 30, 31], 4)], 32)
    np.testing.assert_allclose(resumed[0], got[0], **TOL)


def test_chunked_decode_carries_the_state_across_chunks(params, tokens):
    """Two chunks of 8 teacher-forced steps behind a prefill: a step reads
    the slot's state and the chunk's own z, and the merge hands the next
    chunk what it needs. A lane with no pages decodes garbage beside it
    and changes nothing."""
    s = Served(params)
    seq = tokens[2][:45 + 16]
    s.wave([(1, seq[:45], 0, [7, 8, 9, 10], 0)], 64)
    want = whole(params, seq)
    pos0 = np.zeros(SLOTS, np.int32)
    for c in range(2):
        lo = 45 + 8 * c
        feed = np.zeros((8, SLOTS), np.int32)
        feed[:, 1] = seq[lo:lo + 8]
        pos0[1] = lo
        got = s.chunk(feed, pos0)
        np.testing.assert_allclose(got[:, 1], want[lo:lo + 8], **TOL)


def test_a_pool_kept_at_lane_width_reads_the_same(monkeypatch, params,
                                                  tokens):
    """On the chip a pool of narrow heads is kept 128 lanes wide
    (``llama.kv_head_dim``): q, k, v are padded to it, q takes the
    kernels' scale back, and the output is cut to the head size."""
    monkeypatch.setattr(llama, "kv_head_dim", lambda cfg: 128)
    s = Served(params)
    assert s.cache["k"].shape[-1] == 128
    seq = tokens[0][:61]
    s.wave([(0, seq[:40], 0, [1, 2, 3, 4], 0)], 64)
    got = s.wave([(1, seq[32:45], 32, [1, 2, 11], 2)], 16)
    want = whole(params, seq)
    np.testing.assert_allclose(got[0], want[44], **TOL)
    feed = np.zeros((8, SLOTS), np.int32)
    feed[:, 1] = seq[45:53]
    got = s.chunk(feed, np.asarray([0, 45, 0, 0], np.int32))
    np.testing.assert_allclose(got[:, 1], want[45:53], **TOL)


def test_the_bias_chooses_and_never_gates():
    lp = lfm2.init_params(CFG, jax.random.PRNGKey(3), F32)["segments"][1][0]
    lp = jax.tree.map(lambda a: a[0], lp)
    h = jax.random.normal(jax.random.PRNGKey(4), (256, CFG.dim), F32)
    k = CFG.experts_per_token
    with_b, g_b = lfm2.route(h, lp["router"], lp["expert_bias"], k)
    without, g_0 = lfm2.route(h, lp["router"], jnp.zeros_like(
        lp["expert_bias"]), k)
    differs = np.asarray(jnp.any(jnp.sort(with_b) != jnp.sort(without), -1))
    assert 0 < differs.sum() < len(differs)
    # the gates are the chosen experts' sigmoid scores renormalised: the
    # bias is not in them, whichever experts it chose
    s = np.asarray(jax.nn.sigmoid(h @ lp["router"]))
    picked = np.take_along_axis(s, np.asarray(with_b), -1)
    np.testing.assert_allclose(
        np.asarray(g_b), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    same = ~differs
    order_b, order_0 = np.argsort(with_b[same]), np.argsort(without[same])
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(g_b)[same], order_b, -1),
        np.take_along_axis(np.asarray(g_0)[same], order_0, -1), rtol=1e-6)


def test_a_tokens_result_does_not_depend_on_what_shares_its_call(params,
                                                                 tokens):
    """The same row alone, and among 31 other rows and padding: no
    capacity, so nothing another token does reaches it."""
    lp = jax.tree.map(lambda a: a[0], params["segments"][1][0])
    x = jax.random.normal(jax.random.PRNGKey(8), (32, 1, CFG.dim), F32)
    live = jnp.arange(32)[:, None] < 20          # 12 rows are padding
    many, routing = lfm2.moe_block(x, lp, CFG.experts_per_token, live)
    alone, r1 = lfm2.moe_block(x[7:8], lp, CFG.experts_per_token)
    np.testing.assert_allclose(np.asarray(many[7]), np.asarray(alone[0]),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(routing[7]), np.asarray(r1[0]))
    assert float(jnp.abs(many[20:]).max()) == 0.0     # padding adds nothing


# ------------------------------------------------------------- the engine


def _engine(**kw):
    eng, _tok = build_backend_engine(
        CFG, max_batch=SLOTS, max_seq=MAX_SEQ, seed=3, decode_chunk=8,
        paged=True, page_size=PS, kv_pool_tokens=2048, **kw)
    return eng


@pytest.fixture(scope="module", params=["resident", "scan"])
def engine(request):
    mp = pytest.MonkeyPatch()
    if request.param == "scan":
        mp.setenv("SWARMDB_EMIT_RING", "0")
    eng = _engine()
    assert eng._stateful and eng._ragged_active()
    assert eng._use_resident() == (request.param == "resident")
    eng.start()
    yield eng
    eng.stop()
    mp.undo()


def _run(eng, prompt, max_new=20):
    done, seen = threading.Event(), {}
    req = GenRequest(prompt=[int(t) for t in prompt],
                     sampling=SamplingParams(temperature=0.0,
                                             max_new_tokens=max_new))

    def on_done(_rid, toks, reason):
        seen.update(tokens=list(toks), reason=reason, req=req)
        done.set()

    req.on_done = on_done
    eng.submit(req)
    assert done.wait(300), "request did not finish"
    return seen


def _counter(eng, name):
    return eng.metrics.snapshot()["counters"].get(name, 0)


def test_a_hit_gives_what_the_cold_request_gives(engine, tokens):
    """The same prompt cold, then on a prefix hit of four pages in a slot
    another request has used since: the same tokens and logprobs, and the
    hit's pages were reused, not forgone."""
    prompt = tokens[0][:70]
    reused0 = _counter(engine, "prefix_reused_tokens")
    cold = _run(engine, prompt)
    assert _counter(engine, "prefix_reused_tokens") == reused0
    _run(engine, tokens[1][:40], 9)            # someone else's state
    hit = _run(engine, prompt)
    assert _counter(engine, "prefix_reused_tokens") == reused0 + 64
    assert _counter(engine, "prefix_state_forgone_tokens") == 0
    assert hit["tokens"] == cold["tokens"]
    np.testing.assert_allclose(hit["req"].metadata["logprobs"],
                               cold["req"].metadata["logprobs"], atol=0.05)
    # the record: a row a position, 6 layers that route, none negative
    for seen in (cold, hit):
        rows = seen["req"].routing
        assert seen["req"].routing_complete
        assert rows.shape[1:] == (6, 2) and rows.min() >= 0
    np.testing.assert_array_equal(hit["req"].routing[:64],
                                  cold["req"].routing[:64])
    # and by the plain forward the engine's first token is the best one,
    # as far as a bf16 tie allows
    logits, _c, _r = llama.forward(
        engine.params, CFG, jnp.asarray(prompt)[None], jnp.arange(70)[None],
        llama.init_kv_cache(CFG, 1, 70))
    assert float(logits[0, -1].max() - logits[0, -1, cold["tokens"][0]]) \
        < 0.05
    assert _counter(engine, "moe_dropped_assignments") == 0
    assert 0 < _counter(engine, "moe_expert_hits") <= _counter(
        engine, "moe_expert_step_slots")


def test_a_page_without_its_state_is_forgone_not_resumed_behind(engine,
                                                                tokens):
    """The match is cut back to the deepest page that has its state; what
    lies behind is computed again and counted."""
    prompt = tokens[2][:60]
    cold = _run(engine, prompt)
    lru = engine._prefix
    with lru._lock:                 # the third page loses its handle
        chain = [c for c, e in lru._entries.items()
                 if e[1] == tuple(int(t) for t in prompt[32:48])][0]
        assert lru._states.pop(chain) is True
    reused0 = _counter(engine, "prefix_reused_tokens")
    hit = _run(engine, prompt)
    assert _counter(engine, "prefix_state_forgone_tokens") == 16
    assert _counter(engine, "prefix_reused_tokens") == reused0 + 32
    assert hit["tokens"] == cold["tokens"]


def test_the_plan_phase_counts_the_rows_seeded_from_a_page(engine, tokens):
    from swarmdb_tpu.obs.tracer import TRACER

    prompt = tokens[1][:50]
    was = TRACER.enabled
    TRACER.set_enabled(True)
    try:
        _run(engine, prompt, 4)
        _run(engine, prompt, 4)
    finally:
        TRACER.set_enabled(was)
    plans = [e for e in TRACER.snapshot()
             if e["name"] == "engine.admission.plan" and e["args"]["rows"]]
    assert plans and all("state_rows" in e["args"] for e in plans)
    assert plans[-1]["args"]["state_rows"] == 1
    assert plans[-1]["args"]["cached_tokens"] == 48


def test_expert_reach_counts_distinct_experts_a_step_and_a_layer():
    from types import SimpleNamespace

    from swarmdb_tpu.utils.metrics import MetricsRegistry

    me = SimpleNamespace(_routed=(2, 2, 4), metrics=MetricsRegistry())
    # two slots; the first read two steps, the second one. [steps, L, k]
    a = np.asarray([[[0, 1], [2, 3]], [[0, 1], [0, 1]]], np.int16)
    b = np.asarray([[[0, 2], [2, 3]]], np.int16)
    Engine._observe_load(me, [a, b])
    c = me.metrics.snapshot()["counters"]
    # step 0: layer 0 {0, 1, 2}, layer 1 {2, 3}; step 1: {0, 1}, {0, 1}
    assert c["moe_expert_hits"] == 3 + 2 + 2 + 2
    assert c["moe_expert_step_slots"] == 2 * 2 * 4


# --------------------------------------------------------------- refusals


def test_paths_that_cannot_carry_the_state_refuse_by_name(monkeypatch):
    with pytest.raises(NotImplementedError, match="dense slab engine"):
        build_backend_engine(CFG, max_batch=2, max_seq=64, paged=False)
    monkeypatch.setenv("SWARMDB_RAGGED_PREFILL", "0")
    with pytest.raises(NotImplementedError, match="conv state"):
        _engine()


@pytest.mark.parametrize("path", [
    "forward_chunked", "forward_prefix_pages",
    "forward_pipelined", "forward_seq_parallel", "build_sharded_model",
    "rolling resume"])
def test_a_forward_without_state_refuses(path, engine):
    if path == "rolling resume":
        assert not engine.supports_rolling()
        with pytest.raises(NotImplementedError, match="kept pages"):
            engine.submit(GenRequest(prompt=[5, 6], resume_pages=[1],
                                     resume_len=16))
        return
    if path == "build_sharded_model":
        from swarmdb_tpu.parallel.serving import build_sharded_model

        with pytest.raises(NotImplementedError, match="conv state"):
            build_sharded_model(CFG)
        return
    import inspect

    fn = getattr(llama, path)
    need = [p for p in inspect.signature(fn).parameters.values()
            if p.default is p.empty and p.kind is p.POSITIONAL_OR_KEYWORD]
    with pytest.raises(NotImplementedError, match="conv state"):
        # refused before an argument is read
        fn(None, CFG, *([None] * (len(need) - 2)))
