"""The family with a sublayer a layer (models/nemotron_h.py) at tiny widths:
the three forms of the Mamba-2 recurrence against each other; every served
forward against the plain whole-sequence ``forward``; snapshots rarer than
a page through the engine (a prefix hit that resumes from a snapshot gives
what the cold request gives, and so does one whose snapshot was evicted,
forgone and counted); a layer with no FFN and one with no mixer; the
shares of an expert layer add up to the whole; the paths that cannot carry
the state refuse by name."""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmdb_tpu.backend.engine import GenRequest
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.service import build_backend_engine
from swarmdb_tpu.models import lfm2, llama, nemotron_h
from swarmdb_tpu.models.configs import TINY_NEMOTRON as CFG
from swarmdb_tpu.models.configs import ModelConfig, get_config
from swarmdb_tpu.ops.paged_kv import paged_write_ragged
from swarmdb_tpu.ops.prefix_cache import PrefixLRU

PS, SLOTS, PAGES, MAX_SEQ, SNAPS = 16, 4, 64, 256, 5
F32 = jnp.float32
H, P, G, N = CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_groups, CFG.ssm_state


@pytest.fixture(autouse=True)
def short_segments(monkeypatch):
    # rows of tens of tokens must span several segments of the wave's scan
    monkeypatch.setattr(nemotron_h, "SCAN_CHUNK", 8)


@pytest.fixture(scope="module")
def params():
    return nemotron_h.init_params(CFG, jax.random.PRNGKey(11), F32)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(5), (3, 96), 3,
                                         CFG.vocab_size))


def _highest(fn):
    def run(params, *args):
        with jax.default_matmul_precision("highest"):
            return fn(params, CFG, *args)
    return jax.jit(run)


_FORWARD = _highest(llama.forward)
_RAGGED = _highest(llama.forward_ragged_prefill)
_CHUNKED = _highest(llama.forward_paged_chunked)
TOL = dict(atol=3e-4, rtol=0)


def whole(params, toks):
    T = len(toks)
    logits, _cache, _routing = _FORWARD(
        params, jnp.asarray(toks)[None], jnp.arange(T)[None],
        llama.init_kv_cache(CFG, 1, T, F32))
    return np.asarray(logits[0])


# ------------------------------------------------- the recurrence's forms


def _draw(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, F32)


def test_the_scan_over_a_ragged_wave_equals_the_plain_recurrence():
    """Seeded from a non-zero state, rows that end inside a segment, a
    one-token row, a dead row, a row that ends AT its last page end."""
    W, R, B_, S_ = 64, 5, 6, 4
    lens = np.array([19, 1, 0, 30, 7])
    starts = np.array([0, 19, 0, 20, 50])
    end_lens = np.array([16, 0, 0, 30, 5])
    src = np.array([2, -1, 0, 0, 3])          # a snapshot, the slot's own
    slots = np.array([0, 4, B_, 2, 5])        # (B_: nowhere)
    dst = np.array([1, 0, 0, 4, 0])           # (0: the bin)
    xd, la = _draw(0, W, H, P), -jnp.abs(_draw(1, W, H))
    Bm, Cm = _draw(2, W, G, N), _draw(3, W, G, N)
    slot0, snap0 = _draw(4, 2, B_, H * P, N), _draw(15, 2, 1 + S_, H * P, N)
    y, (slot, snap) = jax.jit(lambda *a: nemotron_h.ssm_segments(
        CFG, *a[:4], *(jnp.asarray(v) for v in (starts, lens, end_lens)),
        jnp.int32(1), *(jnp.asarray(v) for v in (src, slots, dst)),
        a[4:]))(xd, la, Bm, Cm, slot0, snap0)
    # the other layer of the pools is untouched, and so is what no row names
    np.testing.assert_array_equal(slot[0], slot0[0])
    np.testing.assert_array_equal(snap[0], snap0[0])
    np.testing.assert_array_equal(slot[1, [1, 3]], slot0[1, [1, 3]])
    np.testing.assert_array_equal(snap[1, [2, 3]], snap0[1, [2, 3]])
    for r in range(R):
        s, n, e = starts[r], lens[r], end_lens[r]
        if not n:
            continue
        seed = (snap0[1, src[r]] if src[r] > 0 else slot0[1, slots[r]]
                if src[r] < 0 else jnp.zeros((H * P, N)))
        S0 = seed.reshape(1, H, P, N)
        cut = lambda a, m: a[None, s:s + m]
        want, S = nemotron_h.ssm_recurrence(
            CFG, cut(xd, n), cut(la, n), cut(Bm, n), cut(Cm, n), S0)
        np.testing.assert_allclose(y[s:s + n], want[0], atol=2e-5)
        np.testing.assert_allclose(slot[1, slots[r]], S.reshape(H * P, N),
                                   atol=2e-5)
        if e and dst[r]:
            _, Se = nemotron_h.ssm_recurrence(
                CFG, cut(xd, e), cut(la, e), cut(Bm, e), cut(Cm, e), S0)
            np.testing.assert_allclose(snap[1, dst[r]],
                                       Se.reshape(H * P, N), atol=2e-5)


def test_the_one_token_step_equals_the_recurrence_and_merges_its_state():
    B_, K = 3, 4
    S0 = _draw(5, B_, H * P, N)
    xs, las = _draw(6, B_, K, H, P), -jnp.abs(_draw(7, B_, K, H))
    Bs, Cs = _draw(8, B_, K, G, N), _draw(9, B_, K, G, N)
    want, SK = nemotron_h.ssm_recurrence(CFG, xs, las, Bs, Cs,
                                         S0.reshape(B_, H, P, N))
    # the second of two layers: the other's buffers and state stay put
    pool = jnp.stack([_draw(16, B_, H * P, N), S0])
    bufs = (jnp.zeros((2, B_, K, H, P)), jnp.zeros((2, B_, K, G, N)),
            jnp.zeros((2, B_, K, H)))
    every = (jnp.arange(B_, dtype=jnp.int32), jnp.int32(B_))
    for j in range(K):
        y0 = nemotron_h.ssm_state_read(CFG, pool, jnp.int32(1), Cs[:, j],
                                       *every)
        y, bufs = nemotron_h.ssm_chunk_step(
            CFG, xs[:, j], las[:, j], Bs[:, j], Cs[:, j], y0, bufs,
            jnp.int32(1), jnp.int32(j))
        np.testing.assert_allclose(y, want[:, j], atol=2e-5)
    assert not any(np.asarray(b[0]).any() for b in bufs)
    state = {"ssm": pool,
             "conv": jnp.zeros((2, B_, 3, CFG.ssm_conv_dim))}
    # layer 0's buffers are zeros, its ``cs_K`` 0: its state stands
    merged = nemotron_h.merge_state(
        state, (jnp.zeros((2, B_, K, CFG.ssm_conv_dim)), *bufs), *every)
    np.testing.assert_allclose(merged["ssm"][1], SK.reshape(B_, H * P, N),
                               atol=2e-5)
    np.testing.assert_array_equal(merged["ssm"][0], pool[0])


def test_a_steps_decay_lies_strictly_inside_zero_and_one(params):
    """As the weights are drawn: over a thousand tokens no step's
    ``exp(dt a)`` is 0 or 1 in float32."""
    lp = params["segments"][0][0]
    dt = jax.nn.softplus(_draw(10, 1000, 1, H) * 3 + lp["dt_bias"][0])
    decay = np.asarray(jnp.exp(-jnp.exp(lp["A_log"][0]) * dt))
    assert 0.0 < decay.min() and decay.max() < 1.0


# ------------------------------------------- the served forwards, float32


class Served:
    """The served path's model calls around a float32 pool, with the seed
    and scatter rules of ``Engine._prefill_ragged_insert`` under
    snapshots: a wave of rows ``(slot, tokens, first position, table row,
    src, dst)``, ``src`` a snapshot slot to resume from, 0 for a cold row,
    -1 for the slot's own state; ``dst`` the snapshot slot that takes the
    state at the row's last page end."""

    def __init__(self, params):
        self.params = params
        self.cache = llama.init_paged_cache(
            get_config("tiny-nemotron", state_snapshots=SNAPS), SLOTS,
            MAX_SEQ, PAGES, PS, F32)
        assert self.cache["page_state"]["ssm"].shape[:2] == (4, 1 + SNAPS)

    def wave(self, rows, width):
        R, maxp = SLOTS, MAX_SEQ // PS
        toks = np.zeros(width, np.int32)
        tok_row = np.full(width, R, np.int32)
        tok_pos = np.full(width, maxp * PS, np.int32)
        starts, lens, plens = (np.zeros(R, np.int32) for _ in range(3))
        tables = np.zeros((R, maxp), np.int32)
        src, dst = np.zeros(R, np.int32), np.zeros(R, np.int32)
        slots = np.full(R, SLOTS, np.int32)
        at = 0
        for r, (slot, row_toks, p0, table, s, d) in enumerate(rows):
            n = len(row_toks)
            toks[at:at + n] = row_toks
            tok_row[at:at + n] = r
            tok_pos[at:at + n] = np.arange(p0, p0 + n)
            starts[r], lens[r], plens[r] = at, n, p0
            tables[r, :len(table)] = table
            src[r], dst[r], slots[r] = s, d, slot
            at += n
        c = self.cache
        src, slots, dst = (jnp.asarray(a) for a in (src, slots, dst))
        seed = {"conv": lfm2.seed_state(src, slots, c["state"]["conv"],
                                        c["page_state"]["conv"]),
                "ssm": (src, slots, dst, c["state"]["ssm"],
                        c["page_state"]["ssm"])}
        (logits, sk, sv, row_conv, end_conv, end_lens, slot_ssm, snap_ssm,
         routing) = _RAGGED(
            self.params, jnp.asarray(toks), jnp.asarray(tok_row),
            jnp.asarray(tok_pos), jnp.asarray(tables),
            jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(plens),
            c["k"], c["v"], seed)
        c["k"], c["v"] = paged_write_ragged(
            c["k"], c["v"], sk, sv, jnp.asarray(tok_row),
            jnp.asarray(tok_pos), jnp.asarray(tables))
        to = jnp.where(end_lens > 0, dst, 0)
        c["state"] = {"ssm": slot_ssm, "conv": c["state"]["conv"].at[
            :, slots].set(row_conv, mode="drop")}
        c["page_state"] = {"ssm": snap_ssm, "conv": c["page_state"][
            "conv"].at[:, to].set(end_conv)}
        for r, (slot, _t, _p, table, _s, _d) in enumerate(rows):
            c["page_table"] = c["page_table"].at[slot, :len(table)].set(
                jnp.asarray(table, jnp.int32))
        assert routing.shape == (width, CFG.n_routed_layers,
                                 CFG.experts_per_token)
        return np.asarray(logits), np.asarray(end_lens)

    def chunk(self, feed, pos0, K=8):
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


def test_layer_plan_of_the_published_pattern_is_a_period_and_a_tail():
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    kinds = {"M": "mamba", "E": "moe", "*": "full_attention"}
    cfg = get_config("tiny-nemotron", n_layers=52,
                     layer_types=tuple(kinds[c] for c in pattern))
    plan = nemotron_h.layer_plan(cfg)
    assert plan[0] == (tuple(kinds[c] for c in "MEMEM*E"), 5)
    assert sum(len(p) * n for p, n in plan) == 52
    assert (cfg.n_ssm_layers, cfg.n_routed_layers, cfg.n_attn_layers) == (
        23, 23, 6)


def test_cold_rows_packed_in_one_wave_match_the_whole_forward(params,
                                                              tokens):
    s = Served(params)
    a, b = tokens[0][:37], tokens[1][:21]
    got, end_lens = s.wave([(0, a, 0, [1, 2, 3], 0, 1),
                            (1, b, 0, [4, 5], 0, 2)], 64)
    np.testing.assert_allclose(got[0], whole(params, a)[-1], **TOL)
    np.testing.assert_allclose(got[1], whole(params, b)[-1], **TOL)
    assert list(end_lens[:2]) == [32, 16]


@pytest.mark.parametrize("boundary", [16, 32, 48])
def test_a_row_that_resumes_from_a_snapshot_matches_cold(params, tokens,
                                                         boundary):
    """The snapshot is the state at the row's LAST page end of the wave
    that took it; another sequence uses the slot in between."""
    s = Served(params)
    toks = tokens[0][:70]
    pages = list(range(1, 6))
    s.wave([(0, toks[:boundary + 5], 0, pages, 0, 3)], 64)
    s.wave([(0, tokens[2][:30], 0, [9, 10], 0, 0)], 32)
    got, _ = s.wave([(2, toks[boundary:], boundary, pages, 3, 0)], 64)
    np.testing.assert_allclose(got[0], whole(params, toks)[-1], **TOL)


def test_a_split_prompt_and_chunked_decode_carry_the_state(params, tokens):
    s = Served(params)
    toks = tokens[1][:60]
    pages = [7, 8, 9, 10, 11]
    s.wave([(1, toks[:23], 0, pages, 0, 0)], 32)
    got, _ = s.wave([(1, toks[23:44], 23, pages, -1, 0)], 32)
    want = whole(params, toks)
    np.testing.assert_allclose(got[0], want[43], **TOL)
    feed = np.zeros((16, SLOTS), np.int32)
    feed[:, 1] = toks[44:60]
    pos0 = np.zeros(SLOTS, np.int32)
    pos0[1] = 44
    out = np.concatenate([s.chunk(feed[:8], pos0),
                          s.chunk(feed[8:], pos0 + 8)])
    np.testing.assert_allclose(out[:, 1], want[44:60], **TOL)


@pytest.mark.parametrize("first", [44, 47],
                         ids=["inside_a_page", "ends_a_page"])
def test_a_riders_state_is_the_decode_steps(params, tokens, first):
    """A running row's one token taken by a round's wave, beside an
    admitted row (``src`` -1: from its slot's own state; ``dst`` 0: no
    snapshot, also where the token ends a page), against the same token
    taken by a decode step (a chunk of one, merged): the logits, the
    slot's state and conv rows, and the snapshots the wave was not given
    bit for bit what they were."""
    toks = tokens[1][:first + 1]
    pages = [7, 8, 9, 10, 11]
    rode, stepped = Served(params), Served(params)
    for s in (rode, stepped):
        s.wave([(1, toks[:first], 0, pages, 0, 2)], 64)
    snaps = jax.tree.map(np.asarray, rode.cache["page_state"])
    assert snaps["ssm"][:, 2].any()
    got, end_lens = rode.wave([(3, tokens[2][:20], 0, [20, 21], 0, 4),
                               (1, toks[first:], first, pages, -1, 0)], 32)
    assert list(end_lens[:2]) == [16, int((first + 1) % PS == 0)]
    feed = np.zeros((1, SLOTS), np.int32)
    feed[0, 1] = toks[first]
    pos0 = np.zeros(SLOTS, np.int32)
    pos0[1] = first
    out = stepped.chunk(feed, pos0, K=1)
    np.testing.assert_allclose(got[1], out[0, 1], **TOL)
    np.testing.assert_allclose(got[1], whole(params, toks)[-1], **TOL)
    for part in ("ssm", "conv"):
        np.testing.assert_allclose(rode.cache["state"][part][:, 1],
                                   stepped.cache["state"][part][:, 1],
                                   atol=2e-5)
        # the admitted row's snapshot is the wave's, every other the bin's
        kept = [1, 2, 3, 5]
        now = np.asarray(rode.cache["page_state"][part])
        np.testing.assert_array_equal(now[:, kept], snaps[part][:, kept])
        assert (now[:, 4] != snaps[part][:, 4]).any()
    for pool in ("k", "v"):
        np.testing.assert_allclose(rode.cache[pool][:, pages],
                                   stepped.cache[pool][:, pages], atol=2e-5)
    # and the row decodes on from there as if it had never left the chunk
    more = tokens[1][first + 1:first + 9]
    feed = np.zeros((8, SLOTS), np.int32)
    feed[:, 1] = more
    after = rode.chunk(feed, pos0 + (pos0 > 0))
    np.testing.assert_allclose(
        after[:, 1], whole(params, tokens[1][:first + 9])[first + 1:], **TOL)


def test_prefill_then_decode_match_the_benchmarks_reference(params, tokens):
    """Through pages and state, against the plain reference the benchmark
    holds the cell to: once with the reference choosing for itself and
    once following the routing the served path reports, in float32."""
    import json
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from benchmark.reference import nemotron_h_decoder as ref

    f = json.loads((root / "tests" / "benchmark" / "tiny"
                    / "tiny-nemotron.json").read_text())
    dims = dict(ref.dims(f), n_held=CFG.n_experts, first_held=0)
    s = Served(params)
    toks = tokens[2][:56]
    first, _ = s.wave([(3, toks[:40], 0, [20, 21, 22, 23], 0, 2)], 64)
    feed = np.zeros((16, SLOTS), np.int32)
    feed[:, 3] = toks[40:56]
    pos0 = np.zeros(SLOTS, np.int32)
    pos0[3] = 40
    out = np.concatenate([s.chunk(feed[:8], pos0),
                          s.chunk(feed[8:], pos0 + 8)])[:, 3]
    served = np.concatenate([first[:1], out])           # positions 39..55
    T = ref.Q_BLOCK
    padded = jnp.asarray(np.concatenate([toks, np.zeros(T - 56, np.int32)]))
    at = jnp.arange(39, 56)
    alone = np.asarray(ref.logits_at(params, dims, padded, at))
    np.testing.assert_allclose(served, alone, atol=5e-4)
    _l, _c, report = _FORWARD(params, padded[None], jnp.arange(T)[None],
                              llama.init_kv_cache(CFG, 1, T, F32))
    forced = np.asarray(ref.logits_at(params, dims, padded, at, report[0]))
    np.testing.assert_allclose(served, forced, atol=5e-4)


# ------------------------------------------- a mixer alone, an FFN alone


def _only(kind):
    return get_config("tiny-nemotron", n_layers=2, layer_types=(kind, kind))


def test_a_layer_with_no_ffn_and_one_with_no_mixer():
    toks = jnp.asarray(np.arange(3, 27))[None]
    pos = jnp.arange(24)[None]
    for kind, keys, absent in (
            ("mamba", {"in_proj", "out_proj", "A_log"}, {"w_up", "router"}),
            ("moe", {"router", "w_up", "w_down", "ws_up"},
             {"in_proj", "wq", "w_gate"})):
        cfg = _only(kind)
        p = nemotron_h.init_params(cfg, jax.random.PRNGKey(0), F32)
        (layer,), = p["segments"]
        assert keys <= set(layer) and not absent & set(layer)
        out = llama.forward(p, cfg, toks, pos,
                            llama.init_kv_cache(cfg, 1, 24, F32))
        assert np.isfinite(np.asarray(out[0])).all()
        # an FFN alone mixes nothing: a token's logits do not depend on
        # what came before it; a mixer alone does
        other = llama.forward(p, cfg, toks.at[0, 0].set(99), pos,
                              llama.init_kv_cache(cfg, 1, 24, F32))
        moved = np.abs(np.asarray(out[0] - other[0])[0, 5:]).max()
        assert (moved == 0) == (kind == "moe")
        assert cfg.n_attn_layers == 0 and cfg.stateful == (kind == "mamba")


def test_the_eight_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """The shared expert counted once: eight chips that each hold one
    expert of eight, and the whole layer on one."""
    cfg = _only("moe")
    p = nemotron_h.init_params(cfg, jax.random.PRNGKey(3), F32)
    lp = jax.tree.map(lambda a: a[0], p["segments"][0][0])
    h = _draw(12, 1, 10, cfg.dim)
    with jax.default_matmul_precision("highest"):
        whole_y, routing = nemotron_h.moe_ffn(cfg, None)(h, lp, 0)
        shared = lfm2.expert_ffn(h, None, lp["ws_up"], lp["ws_down"])
        parts = 0
        for e in range(cfg.n_experts):
            share = get_config("tiny-nemotron", n_layers=2,
                               layer_types=("moe", "moe"),
                               first_held_expert=e, n_experts_held=1)
            mine = {**lp, "w_up": lp["w_up"][e:e + 1],
                    "w_down": lp["w_down"][e:e + 1]}
            y, r = nemotron_h.moe_ffn(share, None)(h, mine, 0)
            parts = parts + (y - shared)
            held = np.asarray(r) >= 0
            assert (np.asarray(r)[held] == e).all()
    assert int(np.asarray(routing).min()) >= 0
    np.testing.assert_allclose(parts + shared, whole_y, atol=1e-5)


def test_the_gates_are_renormalised_scaled_and_the_bias_never_gates():
    h = _draw(13, 6, CFG.dim)
    w = _draw(14, CFG.dim, CFG.n_experts)
    bias = jnp.zeros((CFG.n_experts,)).at[3].set(100.0)
    chosen, gates = nemotron_h.route(CFG, h, w, bias)
    assert (np.asarray(chosen)[:, 0] == 3).all()
    np.testing.assert_allclose(np.asarray(gates).sum(-1),
                               CFG.routed_scaling_factor, rtol=1e-5)
    s = jax.nn.sigmoid(h @ w)
    np.testing.assert_allclose(
        gates[:, 0], 2.5 * s[:, 3] / jnp.take_along_axis(
            s, chosen, axis=-1).sum(-1), rtol=1e-4)


def test_the_fitted_bias_takes_every_expert_equally_often(params):
    """``fit_bias`` on scores that favour a few experts for every row, and
    ``init_params``' own: under it every routed layer's busiest expert
    over the rows it was fitted on is near the mean."""
    k, E = CFG.experts_per_token, CFG.n_experts
    s = jax.nn.sigmoid(_draw(21, 256, E) + 3.0 * _draw(22, 1, E))

    def busiest(chosen):
        load = np.bincount(np.asarray(chosen).ravel(), minlength=E)
        return load.max() / load.mean()

    bias = nemotron_h.fit_bias(CFG, s)
    assert busiest(jax.lax.top_k(s, k)[1]) > 2.0
    assert busiest(jax.lax.top_k(s + bias, k)[1]) < 1.2
    toks = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(11), 1),
        (nemotron_h.BALANCE_ROWS, nemotron_h.BALANCE_LEN), 0, CFG.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
    flat = jax.tree.map(
        lambda a: jnp.zeros_like(a), [
            [lp.get("expert_bias") for lp in seg]
            for seg in params["segments"]])
    unfitted = {**params, "segments": [
        [{**lp, "expert_bias": z} if z is not None else lp
         for lp, z in zip(seg, zs)]
        for seg, zs in zip(params["segments"], flat)]}
    worst = []
    for p in (params, unfitted):
        routing = _highest(llama.forward)(
            p, toks, pos, llama.init_kv_cache(CFG, *toks.shape, F32))[2]
        worst.append(max(busiest(routing[:, :, layer])
                         for layer in range(CFG.n_routed_layers)))
    assert worst[0] < 1.1 and worst[1] > 1.3, worst


# ------------------------------------------------------- the snapshot table


def test_a_snapshot_leaves_with_its_page_or_apart_from_it():
    lru = PrefixLRU(8, 4, manage_free=False)
    lru.keep_state_slots(2)
    a, b, c = b"a", b"b", b"c"
    (sa, _), (sb, _) = lru.take_state_slot(a, 2), lru.take_state_slot(b, 3)
    assert sa and sb and lru.state_slots_live() == 2
    # the same prompt twice: the chain has its snapshot
    assert lru.take_state_slot(a, 2) == (0, False)
    lru.register(a, (1, 2, 3, 4), 1)
    lru.register(b, (5, 6, 7, 8), 2)
    states = []
    lru.match([a, b], (1, 2, 3, 4, 5, 6, 7, 8), states=states)
    assert states == [sa, sb]
    # no free slot: the SHALLOWEST goes (its loss costs the fewest tokens),
    # never a busy one, and never for a newcomer that is no deeper
    assert lru.take_state_slot(c, 4, busy={sa, sb}) == (0, False)
    assert lru.take_state_slot(c, 2) == (0, False)
    assert lru.take_state_slot(c, 4) == (sa, True)
    states = []
    lru.match([a, b], (1, 2, 3, 4, 5, 6, 7, 8), states=states)
    assert states == [None, sb]                   # a's page stays
    # a superseded snapshot goes first, whatever its depth, and is no loss
    lru.supersede(sa)
    assert lru.take_state_slot(b"e", 1) == (sa, False)
    # a page that leaves frees its snapshot's slot
    assert lru.evict_lru(2) == [1, 2] and lru.state_slots_live() == 1
    assert lru.take_state_slot(b"f", 1)[0] == sb
    # a page computed again finds the new snapshot of its chain
    lru.register(a, (1, 2, 3, 4), 1)
    (sf, _), states = lru.take_state_slot(a, 2), []
    assert sf and not lru.register(a, (1, 2, 3, 4), 4)
    lru.match([a], (1, 2, 3, 4), states=states)
    assert states == [sf]
    lru.reset()
    assert lru.state_slots_live() == 0
    assert {lru.take_state_slot(bytes([i]), 1)[0] for i in range(3)} == {
        0, 1, 2}


# ------------------------------------------------------------- the engine


def _engine(snapshots=SNAPS, **kw):
    eng, _tok = build_backend_engine(
        get_config("tiny-nemotron", state_snapshots=snapshots),
        max_batch=SLOTS, max_seq=MAX_SEQ, seed=3, decode_chunk=8,
        paged=True, page_size=PS, kv_pool_tokens=2048, **kw)
    return eng


@pytest.fixture(scope="module", params=["resident", "scan"])
def engine(request):
    mp = pytest.MonkeyPatch()
    mp.setattr(nemotron_h, "SCAN_CHUNK", 8)
    if request.param == "scan":
        mp.setenv("SWARMDB_EMIT_RING", "0")
    eng = _engine()
    assert eng._stateful and eng._snapshots == SNAPS
    # nothing runs yet; a row that does rides this engine's waves as any
    # paged engine's (ISSUE 56: tests/test_wave_riders.py has the cases)
    assert eng._ragged_active() and eng._wave_riders() == []
    s = eng.slots[0]
    s.active, s.generated, s.request = True, [7], GenRequest(
        prompt=[5], sampling=SamplingParams(max_new_tokens=4))
    assert eng._wave_riders() == [0]
    s.active, s.generated, s.request = False, [], None
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


def test_a_hit_resumes_from_a_snapshot_and_an_evicted_one_is_forgone(
        engine, tokens):
    prompt = tokens[0][:70]
    base = {n: _counter(engine, n) for n in (
        "prefix_reused_tokens", "prefix_state_forgone_tokens",
        "ssm_state_tokens_resumed", "ssm_snapshots_taken",
        "ssm_snapshots_evicted", "ssm_wave_segments",
        "ssm_wave_segment_tokens", "wave_rider_tokens",
        "wave_riders_unseated")}
    since = lambda n: _counter(engine, n) - base[n]
    cold = _run(engine, prompt)
    assert since("ssm_snapshots_taken") == 1
    # 64 tokens to the last page end in segments of 8, and the 6 behind it
    assert (since("ssm_wave_segments"),
            since("ssm_wave_segment_tokens")) == (9, 70)
    _run(engine, tokens[1][:40], 9)            # someone else's state
    hit = _run(engine, prompt)
    assert since("prefix_reused_tokens") == 64
    assert since("ssm_state_tokens_resumed") == 64
    assert since("prefix_state_forgone_tokens") == 0
    assert hit["tokens"] == cold["tokens"]
    np.testing.assert_allclose(hit["req"].metadata["logprobs"],
                               cold["req"].metadata["logprobs"], atol=0.05)
    for seen in (cold, hit):
        rows = seen["req"].routing
        assert seen["req"].routing_complete
        assert rows.shape[1:] == (4, 2) and rows.min() >= 0
    # a longer prompt of the same conversation resumes behind page 4 and
    # its deeper snapshot takes a slot of its own
    longer = list(prompt) + list(tokens[2][:30])
    _run(engine, longer, 4)
    assert since("ssm_state_tokens_resumed") == 128
    # more conversations than the pool holds snapshots: the superseded
    # one and then the shallowest leave apart from their pages
    for i in range(SNAPS + 1):
        _run(engine, tokens[1][i + 1:i + 57], 4)
    assert since("ssm_snapshots_evicted") > 0
    again = _run(engine, prompt)
    assert since("prefix_state_forgone_tokens") == 64
    assert again["tokens"] == cold["tokens"]
    assert engine._prefix.state_slots_live() <= SNAPS
    assert _counter(engine, "ssm_snapshot_slots_live") <= _counter(
        engine, "ssm_snapshot_slots")
    # one request at a time: no row was running when another was
    # admitted, so none rode and none was left without a seat (both
    # counters are this engine's since ISSUE 56, registered at 0)
    assert since("wave_rider_tokens") == since("wave_riders_unseated") == 0
    assert "wave_riders_unseated" in engine.metrics.snapshot()["counters"]


def test_the_widest_wave_follows_the_query_heads_a_kv_head():
    """What the chip's compiler refused at 16 query heads a KV head is the
    attention kernel's, whatever else the layers are; a pool for Mamba-2
    layers says how many snapshots it keeps."""
    from swarmdb_tpu.ops.layers import ragged_wave_max_width

    assert ragged_wave_max_width(32, 2) == 1024
    assert ragged_wave_max_width(32, 8) is None
    assert ragged_wave_max_width(CFG.n_heads, CFG.n_kv_heads) is None
    wide, _tok = build_backend_engine(
        get_config("tiny-nemotron", n_heads=16, n_kv_heads=1), max_batch=2,
        max_seq=MAX_SEQ, paged=True, page_size=PS, kv_pool_tokens=1024)
    assert wide.paged.ragged_max_width == 1024
    with pytest.raises(ValueError, match="state_snapshots"):
        _engine(snapshots=0)


# --------------------------------------------------------------- refusals


def test_paths_that_cannot_carry_the_state_refuse_by_name(monkeypatch):
    with pytest.raises(NotImplementedError, match="Mamba-2 state"):
        build_backend_engine(CFG, max_batch=2, max_seq=64, paged=False)
    monkeypatch.setenv("SWARMDB_RAGGED_PREFILL", "0")
    with pytest.raises(NotImplementedError, match="conv state"):
        _engine()
    monkeypatch.delenv("SWARMDB_RAGGED_PREFILL")
    monkeypatch.setenv("SWARMDB_KV_DTYPE", "int8")
    with pytest.raises(NotImplementedError, match="int8"):
        _engine()


@pytest.mark.parametrize("path", [
    "forward_chunked", "forward_prefix_pages", "forward_pipelined",
    "forward_seq_parallel", "build_sharded_model", "rolling resume"])
def test_a_forward_without_state_refuses(path, engine):
    if path == "rolling resume":
        # what swarmtier's promotion, the supervisor's replay and the
        # fleet's handoff all come in by
        assert not engine.supports_rolling()
        with pytest.raises(NotImplementedError, match="kept pages"):
            engine.submit(GenRequest(prompt=[5, 6], resume_pages=[1],
                                     resume_len=16))
        return
    if path == "build_sharded_model":
        from swarmdb_tpu.parallel.serving import build_sharded_model

        with pytest.raises(NotImplementedError, match="Mamba-2 state"):
            build_sharded_model(CFG)
        return
    import inspect

    fn = getattr(llama, path)
    need = [p for p in inspect.signature(fn).parameters.values()
            if p.default is p.empty and p.kind is p.POSITIONAL_OR_KEYWORD]
    with pytest.raises(NotImplementedError, match="Mamba-2 state"):
        fn(None, CFG, *([None] * (len(need) - 2)))


def test_the_configuration_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="stand alone"):
        get_config("tiny-nemotron", layer_types=("conv", "moe") * 4
                   + ("mamba",))
    with pytest.raises(ValueError, match="ssm_heads"):
        get_config("tiny-nemotron", ssm_heads=0)
    assert isinstance(CFG, ModelConfig) and CFG.head_dim == 32 != 64 // 4


# ------------------- a decode chunk moves its live rows' state and no other


LANE = get_config("tiny-nemotron", ssm_heads=4, ssm_head_dim=64,
                  ssm_groups=2, ssm_state=128)        # H P = 256, N = 128


def _scattered(n_live, B_=32):
    """A page table of ``B_`` slots of which ``n_live`` hold a sequence,
    scattered, and the list ``live_row_list`` makes of it."""
    from swarmdb_tpu.ops.paged_kv import live_row_list

    live = np.zeros(B_, bool)
    live[np.random.default_rng(n_live).permutation(B_)[:n_live]] = True
    table = jnp.asarray(np.where(live[:, None], 1 + np.arange(B_)[:, None],
                                 0) * np.ones((1, 3), int), jnp.int32)
    return live, live_row_list(table)


@pytest.mark.parametrize("n_live", [0, 1, 9, 32])
def test_the_live_row_read_is_the_steps_read_on_the_live_rows(monkeypatch,
                                                              n_live):
    """The kernel (interpreted) and the loop against the batch-wide form
    ``sum(S * C)``: equal on the live slots, zeros on the others."""
    from swarmdb_tpu.ops import ssm_pallas

    Hh, Pp, Gg, Nn = (LANE.ssm_heads, LANE.ssm_head_dim, LANE.ssm_groups,
                      LANE.ssm_state)
    B_ = 32
    pool = _draw(20, 3, B_, Hh * Pp, Nn).astype(jnp.bfloat16)
    Cm = _draw(21, B_, Gg, Nn)
    live, rows = _scattered(n_live)
    Ch = jnp.repeat(Cm, Hh // Gg, axis=1)
    want = np.asarray(jnp.sum(
        pool[2].reshape(B_, Hh, Pp, Nn).astype(F32) * Ch[:, :, None, :],
        axis=-1))
    read = jax.jit(lambda *a: nemotron_h.ssm_state_read(LANE, *a))
    assert not ssm_pallas.takes(pool, Gg)             # the CPU: the loop
    loop = np.asarray(read(pool, jnp.int32(2), Cm, *rows))
    monkeypatch.setattr(ssm_pallas, "_on_tpu", lambda: True)
    assert ssm_pallas.takes(pool, Gg) and not ssm_pallas.takes(
        pool.astype(F32), Gg) and not ssm_pallas.takes(pool, 4)
    jax.clear_caches()
    kernel = np.asarray(jax.jit(lambda *a: nemotron_h.ssm_state_read(
        LANE, *a))(pool, jnp.int32(2), Cm, *rows))
    for got in (loop, kernel):
        np.testing.assert_allclose(got[live], want[live], rtol=2e-6,
                                   atol=2e-5)
        assert not got[~live].any()


@pytest.mark.parametrize("form", ["loop", "kernel"])
@pytest.mark.parametrize("n_live", [0, 1, 3, 6])
def test_a_chunks_merge_touches_the_live_slots_alone(monkeypatch, n_live,
                                                     form):
    """Every dead slot's state bit for bit what it was, the live ones
    ``exp(cs_K) S_0 + built`` as the plain recurrence leaves them: by the
    loop in float32 at the tiny widths, by the kernel (interpreted) in
    bfloat16 at lane-multiple ones, to a bf16 rounding."""
    from swarmdb_tpu.ops import ssm_pallas

    cfg, dt, tol = CFG, F32, dict(atol=2e-5)
    if form == "kernel":
        cfg, dt, tol = LANE, jnp.bfloat16, dict(rtol=2 ** -7, atol=1e-6)
        monkeypatch.setattr(ssm_pallas, "_on_tpu", lambda: True)
    Hh, Pp, Gg, Nn = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
    B_, K, L = 6, 4, 2
    live, rows = _scattered(n_live, B_)
    ssm = _draw(30, L, B_, Hh * Pp, Nn).astype(dt)
    conv = _draw(31, L, B_, 3, cfg.ssm_conv_dim).astype(dt)
    hz = _draw(32, L, B_, K, cfg.ssm_conv_dim).astype(dt)
    xd, la = _draw(33, L, B_, K, Hh, Pp), -jnp.abs(_draw(34, L, B_, K, Hh))
    hB = _draw(35, L, B_, K, Gg, Nn)
    assert ssm_pallas.takes(ssm, Gg, K) == (form == "kernel")
    merged = jax.jit(nemotron_h.merge_state)(
        {"ssm": ssm, "conv": conv}, (hz, xd, hB, jnp.cumsum(la, axis=2)),
        *rows)
    dead = ~live
    np.testing.assert_array_equal(merged["ssm"][:, dead], ssm[:, dead])
    np.testing.assert_array_equal(merged["conv"][:, dead], conv[:, dead])
    np.testing.assert_array_equal(merged["conv"][:, live], hz[:, live, -3:])
    for l in range(L):
        _y, SK = nemotron_h.ssm_recurrence(
            cfg, xd[l], la[l], hB[l], jnp.zeros_like(hB[l]),
            ssm[l].astype(F32).reshape(B_, Hh, Pp, Nn))
        np.testing.assert_allclose(
            merged["ssm"][l][live].astype(F32),
            SK.reshape(B_, Hh * Pp, Nn)[live], **tol)


def test_a_chunk_with_dead_slots_beside_it_matches_the_whole_forward(
        params, tokens):
    """``test_a_split_prompt_and_chunked_decode_carry_the_state``'s decode
    with a second sequence two slots on and two slots empty: each live
    slot's logits are its own whole forward's, the empty slots' state is
    untouched by two chunks."""
    s = Served(params)
    a, b = tokens[1][:60], tokens[2][:52]
    s.wave([(1, a[:44], 0, [7, 8, 9, 10, 11], 0, 0),
            (3, b[:36], 0, [12, 13, 14, 15], 0, 0)], 128)
    before = jax.tree.map(np.asarray, s.cache["state"])
    feed = np.zeros((16, SLOTS), np.int32)
    feed[:, 1], feed[:, 3] = a[44:60], b[36:52]
    pos0 = np.array([0, 44, 0, 36], np.int32)
    out = np.concatenate([s.chunk(feed[:8], pos0),
                          s.chunk(feed[8:], pos0 + 8 * (pos0 > 0))])
    np.testing.assert_allclose(out[:, 1], whole(params, a)[44:60], **TOL)
    np.testing.assert_allclose(out[:, 3], whole(params, b)[36:52], **TOL)
    after = jax.tree.map(np.asarray, s.cache["state"])
    for part in ("ssm", "conv"):
        np.testing.assert_array_equal(after[part][:, [0, 2]],
                                      before[part][:, [0, 2]])
        assert (after[part][:, [1, 3]] != before[part][:, [1, 3]]).any()


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_the_decode_step_carries_the_chunks_buffers_whole(params):
    """Every scanned segment of the decode step has the four buffers in
    its CARRY, whole over the Mamba-2 layers, none among what it slices a
    layer or stacks out, and nothing concatenates or slices them."""
    cfg = get_config("tiny-nemotron", state_snapshots=SNAPS)
    cache = llama.init_paged_cache(cfg, SLOTS, MAX_SEQ, PAGES, PS, F32)
    chunk_kv = llama.init_chunk_kv(CFG, SLOTS, 8, F32)
    whole_shapes = {b.shape for b in chunk_kv[2]}
    assert len(whole_shapes) == 4

    def of_a_buffer(shape):
        # a buffer or any run of its layers: its [B, K, ...] behind
        return any(tuple(shape[-len(s) + 1:]) == s[1:] for s in whole_shapes)

    jaxpr = jax.make_jaxpr(
        lambda *a: llama.forward_paged_chunked(params, CFG, *a))(
        jnp.zeros((SLOTS, 1), jnp.int32), jnp.zeros((SLOTS, 1), jnp.int32),
        cache, chunk_kv, jnp.int32(0)).jaxpr
    # the layer scans: the ones that carry ``x``
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"
             and (SLOTS, 1, CFG.dim) in {v.aval.shape for v in e.invars}]
    assert scans, "the tiny stack has a repeated segment"
    for e in scans:
        nc, ncar = e.params["num_consts"], e.params["num_carry"]
        shapes = [v.aval.shape for v in e.invars]
        carry, xs = shapes[nc:nc + ncar], shapes[nc + ncar:]
        assert whole_shapes <= set(carry)
        ys = [v.aval.shape for v in e.outvars[ncar:]]
        assert not any(map(of_a_buffer, (*xs, *ys)))
    for e in _eqns(jaxpr):
        if e.primitive.name in ("concatenate", "slice"):
            assert not any(of_a_buffer(v.aval.shape)
                           for v in (*e.invars, *e.outvars)
                           if hasattr(v.aval, "shape")), e


# ------------------- a wave's scan as one kernel over its live segments


def _wave_case(rows, width, n_slots=4, n_snaps=3):
    """A made wave at the lane-multiple widths: ``rows`` of ``(tokens,
    tokens up to the last page end, src, slot, dst)`` packed in stream
    order from token 3 on (no row starts at a tile's first row), bf16
    pools of two layers drawn non-zero."""
    R = len(rows)
    lens = np.array([r[0] for r in rows])
    starts = np.where(lens > 0, 3 + np.cumsum(lens) - lens, 0)
    plan = [starts, lens] + [np.array([r[i] for r in rows])
                             for i in (1, 2, 3, 4)]
    Hh, Pp, Gg, Nn = (LANE.ssm_heads, LANE.ssm_head_dim, LANE.ssm_groups,
                      LANE.ssm_state)
    xd, la = _draw(40, width, Hh, Pp), -jnp.abs(_draw(41, width, Hh)) * 0.3
    Bm, Cm = _draw(42, width, Gg, Nn), _draw(43, width, Gg, Nn)
    slot0 = _draw(44, 2, n_slots, Hh * Pp, Nn).astype(jnp.bfloat16)
    snap0 = _draw(45, 2, 1 + n_snaps, Hh * Pp, Nn).astype(jnp.bfloat16)
    assert starts[-1] + lens[-1] <= width and R <= 4
    return (xd, la, Bm, Cm), plan, (slot0, snap0)


# (tokens, of them up to the last page end, src, slot, dst); slot 4: nowhere
WAVES = {
    "a-row-from-zeros": ([(40, 32, 0, 1, 2)], 64),
    "a-row-from-a-snapshot": ([(40, 32, 3, 0, 1)], 64),
    "a-row-from-its-slots-own-state": ([(40, 32, -1, 2, 0)], 64),
    "a-row-that-ends-at-its-last-page-end": ([(48, 48, 2, 3, 1)], 64),
    "a-dead-row-between-two-live-ones": (
        [(21, 16, 1, 0, 2), (0, 0, 0, 4, 0), (30, 16, -1, 3, 3)], 64),
    "a-one-token-row": ([(1, 0, -1, 2, 0)], 8),
    "1-token-at-a-page-end": ([(1, 1, 2, 1, 3)], 8),
    "127-tokens": ([(127, 112, 0, 0, 1)], 256),
    "128-tokens": ([(128, 128, 1, 1, 2)], 256),
    "129-tokens": ([(129, 128, -1, 2, 3)], 256),
    "300-tokens": ([(300, 288, 3, 3, 1)], 512),
    "rows-of-129-1-and-300-with-nowhere-to-go": (
        [(129, 128, 0, 4, 0), (1, 0, -1, 0, 0), (300, 160, 2, 1, 3)], 512),
}


@pytest.mark.parametrize("case", list(WAVES))
def test_the_wave_kernel_is_the_loop_over_the_live_segments(monkeypatch,
                                                            case):
    """``ssm_pallas.ssm_wave_scan`` (interpreted) against ``ssm_segments``
    at the published segment and against ``ssm_recurrence``: ``y`` of the
    live tokens, exact zeros elsewhere, the states a bf16 rounding from
    the recurrence's, and every slot row and snapshot row the table does
    not name, the bin and the other layer among them, bit for bit what
    it was."""
    from swarmdb_tpu.ops import ssm_pallas

    monkeypatch.setattr(nemotron_h, "SCAN_CHUNK", 128)
    rows, width = WAVES[case]
    streams, plan, (slot0, snap0) = _wave_case(rows, width)
    starts, lens, end_lens, src, slots, dst = plan
    Hh, Pp, Nn = LANE.ssm_heads, LANE.ssm_head_dim, LANE.ssm_state
    n_slots = slot0.shape[1]
    assert not ssm_pallas.takes_wave((slot0, snap0), (width, Hh, Pp),
                                     LANE.ssm_groups)        # the CPU
    monkeypatch.setattr(ssm_pallas, "_on_tpu", lambda: True)
    assert ssm_pallas.takes_wave((slot0, snap0), (width, Hh, Pp),
                                 LANE.ssm_groups)
    for pools, stream, seg in (
            ((slot0.astype(F32), snap0.astype(F32)), (width, Hh, Pp), 128),
            ((slot0, snap0), (width, Hh, 48), 128),
            ((slot0, snap0), (width + 4, Hh, Pp), 128),
            ((slot0, snap0), (width, Hh, Pp), 8)):
        assert not ssm_pallas.takes_wave(pools, stream, LANE.ssm_groups, seg)
    as_i32 = [jnp.asarray(a, jnp.int32) for a in plan]
    y_loop, (slot_l, snap_l) = jax.jit(lambda *a: nemotron_h.ssm_segments(
        LANE, *a[:4], *as_i32[:3], jnp.int32(1), *as_i32[3:], a[4:]))(
            *streams, slot0, snap0)
    table, n_live = ssm_pallas.wave_segment_table(*as_i32, n_slots, width)
    assert int(n_live) == sum(-(-e // 128) + -(-(n - e) // 128)
                              for n, e in zip(lens, end_lens))
    # as the kernel takes them: ``x`` and a head's ``dt`` apart, ``x | B |
    # C`` a token's one row
    dt = 0.5 + jnp.abs(_draw(46, width, Hh))
    xd, la, Bm, Cm = streams
    xbc = jnp.concatenate([a.reshape(width, -1) for a in (
        xd / dt[..., None], Bm, Cm)], axis=1)       # the conv's row
    y, slot, snap = ssm_pallas.ssm_wave_scan(
        xbc, dt, la, table, n_live, jnp.int32(1), slot0, snap0,
        interpret=True)
    y = y.reshape(width, Hh, Pp)
    live = np.zeros(width, bool)
    named_slots, named_snaps = set(), set()
    for r in range(len(rows)):
        s, n, e = int(starts[r]), int(lens[r]), int(end_lens[r])
        if not n:
            continue
        live[s:s + n] = True
        seed = (snap0[1, src[r]] if src[r] > 0 else slot0[1, slots[r]]
                if src[r] < 0 else jnp.zeros((Hh * Pp, Nn)))
        S0 = seed.astype(F32).reshape(1, Hh, Pp, Nn)
        cut = lambda a, m: a[None, s:s + m]
        want, S = nemotron_h.ssm_recurrence(
            LANE, *(cut(a, n) for a in streams), S0)
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(y[s:s + n], want[0], atol=2e-6 * scale,
                                   rtol=1e-5)
        np.testing.assert_allclose(y[s:s + n], y_loop[s:s + n],
                                   atol=2e-6 * scale, rtol=1e-5)
        bf16 = dict(rtol=2 ** -7, atol=1e-4)
        if slots[r] < n_slots:
            named_slots.add(int(slots[r]))
            np.testing.assert_allclose(slot[1, slots[r]].astype(F32),
                                       S.reshape(Hh * Pp, Nn), **bf16)
        if e and dst[r]:
            named_snaps.add(int(dst[r]))
            _, Se = nemotron_h.ssm_recurrence(
                LANE, *(cut(a, e) for a in streams), S0)
            np.testing.assert_allclose(snap[1, dst[r]].astype(F32),
                                       Se.reshape(Hh * Pp, Nn), **bf16)
    assert not np.asarray(y)[~live].any()
    same = lambda a, b: np.testing.assert_array_equal(
        np.asarray(a.astype(F32)), np.asarray(b.astype(F32)))
    same(slot[0], slot0[0])
    same(snap[0], snap0[0])
    for b in set(range(n_slots)) - named_slots:
        same(slot[1, b], slot0[1, b])
    for b in set(range(snap0.shape[1])) - named_snaps:     # 0: the bin
        same(snap[1, b], snap0[1, b])
    # the loop's own: one rounding of float32 sums made in another order
    for got, loops, named in ((slot, slot_l, named_slots),
                              (snap, snap_l, named_snaps)):
        for b in named:
            np.testing.assert_allclose(got[1, b].astype(F32),
                                       loops[1, b].astype(F32), **bf16)


@pytest.mark.parametrize("first,tokens,want", [
    (32, 215, 3), (0, 1, 1), (15, 1, 1), (0, 128, 1), (0, 129, 2),
    (0, 300, 4), (7, 0, 0), (40, 7, 1), (250, 6, 1), (250, 7, 2)])
def test_the_hosts_count_of_a_rows_segments_is_the_scans(monkeypatch, first,
                                                         tokens, want):
    """``wave_segments`` on the host's integers against the table the
    kernel walks, with the page ends ``stream_mixers`` finds."""
    from swarmdb_tpu.ops import ssm_pallas

    monkeypatch.setattr(nemotron_h, "SCAN_CHUNK", 128)
    assert nemotron_h.wave_segments(first, tokens, PS) == want
    pos = first + np.arange(tokens)
    ends = np.nonzero((pos + 1) % PS == 0)[0]
    one = lambda v: jnp.asarray([v], jnp.int32)
    _table, n_live = ssm_pallas.wave_segment_table(
        one(0), one(tokens), one(ends[-1] + 1 if len(ends) else 0), one(0),
        one(0), one(0), 4, 512)
    assert int(n_live) == want


def test_a_wave_through_the_stack_takes_the_kernel_the_call_shows(
        monkeypatch, tokens):
    """``forward_ragged_prefill`` over bf16 pools at the lane-multiple
    widths: XLA's loop on the CPU, the kernel (interpreted) once
    ``_on_tpu`` says so, a table made once for all the layers; the same
    logits and conv rows, and the states to a bf16 rounding."""
    from swarmdb_tpu.ops import ssm_pallas

    monkeypatch.setattr(nemotron_h, "SCAN_CHUNK", 128)
    bf = jnp.bfloat16
    cfg = get_config("tiny-nemotron", ssm_heads=4, ssm_head_dim=64,
                     ssm_groups=2, ssm_state=128, state_snapshots=SNAPS)
    # a float32 stream: a bf16 one rounds the forms' last float32 bits into
    # another expert here and there
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(3), F32)
    cache = llama.init_paged_cache(cfg, SLOTS, MAX_SEQ, PAGES, PS, bf)
    R, maxp, W = SLOTS, MAX_SEQ // PS, 64
    rows = [(0, tokens[0][:37], [1, 2, 3], 1), (2, tokens[1][:21], [4, 5], 2)]
    toks, tok_row = np.zeros(W, np.int32), np.full(W, R, np.int32)
    tok_pos = np.full(W, maxp * PS, np.int32)
    starts, lens = np.zeros(R, np.int32), np.zeros(R, np.int32)
    tables = np.zeros((R, maxp), np.int32)
    slots, dst = np.full(R, SLOTS, np.int32), np.zeros(R, np.int32)
    at = 0
    for r, (slot, t, table, d) in enumerate(rows):
        n = len(t)
        toks[at:at + n], tok_row[at:at + n] = t, r
        tok_pos[at:at + n] = np.arange(n)
        starts[r], lens[r], slots[r], dst[r] = at, n, slot, d
        tables[r, :len(table)] = table
        at += n
    src = jnp.zeros(R, jnp.int32)
    seed = {"conv": lfm2.seed_state(src, jnp.asarray(slots),
                                    cache["state"]["conv"],
                                    cache["page_state"]["conv"]),
            "ssm": (src, jnp.asarray(slots), jnp.asarray(dst),
                    cache["state"]["ssm"], cache["page_state"]["ssm"])}
    args = [jnp.asarray(a) for a in (toks, tok_row, tok_pos, tables, starts,
                                     lens, np.zeros(R, np.int32))]
    run = lambda: jax.jit(
        lambda p, *a: llama.forward_ragged_prefill(p, cfg, *a))(
            params, *args, cache["k"], cache["v"], seed)
    calls = []
    scan = ssm_pallas.ssm_wave_scan
    monkeypatch.setattr(ssm_pallas, "ssm_wave_scan",
                        lambda *a, **k: calls.append(1) or scan(*a, **k))
    by_loop = run()
    assert not calls
    monkeypatch.setattr(ssm_pallas, "_on_tpu", lambda: True)
    jax.clear_caches()
    by_kernel = run()
    # traced once a layer body: a repeated segment's once for its repeats
    assert 0 < len(calls) <= cfg.n_ssm_layers
    for got, want in zip(jax.tree.leaves(by_kernel),
                         jax.tree.leaves(by_loop)):
        if W in want.shape:
            # the stream's live tokens: behind them the loop leaves what
            # its masked segments made, the kernel zeros
            live = (slice(None),) * want.shape.index(W) + (slice(0, at),)
            got, want = got[live], want[live]
        if want.shape == cache["page_state"]["ssm"].shape:
            assert not np.asarray(got[:, 0].astype(F32)).any()
            got, want = got[:, 1:], want[:, 1:]      # the bin: the loop's
        if jnp.issubdtype(want.dtype, jnp.floating):
            np.testing.assert_allclose(
                np.asarray(got.astype(F32)), np.asarray(want.astype(F32)),
                rtol=2 ** -7, atol=2e-3)
        else:
            np.testing.assert_array_equal(got, want)
