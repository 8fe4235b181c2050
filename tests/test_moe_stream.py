"""The decode shape's expert kernel (ops/moe_pallas.py) under the
interpreter at tiny lane-multiple widths: against ``lfm2.moe_block``'s
loop on the same inputs, through ``moe_block`` itself (so the wiring and
the routing returned are in the comparison), through a decode chunk of
``forward_paged_chunked``, and that the choice follows the call's rows."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmdb_tpu.models import lfm2, llama
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.ops import moe_pallas

D, F, E, K, N = 256, 256, 8, 2, 32
F32, BF16 = jnp.float32, jnp.bfloat16


def layer(dtype, n_layers=1, seed=0):
    """One routed layer's parameters, the expert matrices a flat
    ``[n_layers * E, ...]`` stack."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def draw(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32) / fan_in ** .5).astype(dtype)

    return {"router": draw(ks[0], (D, E), D),
            "expert_bias": 0.1 * jax.random.normal(ks[1], (E,), F32),
            "w_gate": draw(ks[2], (n_layers * E, D, F), D),
            "w_up": draw(ks[3], (n_layers * E, D, F), D),
            "w_down": draw(ks[4], (n_layers * E, F, D), F)}


def rows(dtype, n=N, seed=7):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 1, D),
                             F32).astype(dtype)


def both(monkeypatch, x, lp, live, base=0, tile_f=None):
    """``moe_block`` by its loop and by the kernel: (y, routing) twice."""
    if tile_f is not None:
        monkeypatch.setattr(moe_pallas, "TILE_F", tile_f)
    monkeypatch.setattr(moe_pallas, "takes", lambda *a: False)
    loop = lfm2.moe_block(x, lp, K, live, base)
    monkeypatch.setattr(moe_pallas, "takes", lambda *a: True)
    kernel = lfm2.moe_block(x, lp, K, live, base)
    return loop, kernel


def hits(x, lp, live):
    chosen, _ = lfm2.route(x.reshape(-1, D), lp["router"],
                           lp["expert_bias"], K)
    mask = np.ones(len(chosen), bool) if live is None \
        else np.asarray(live).reshape(-1)
    return set(np.asarray(chosen)[mask].reshape(-1).tolist())


def bf16_steps_apart(a, b):
    """|a - b| in units of the larger one's bf16 step."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    big = np.maximum(np.abs(a), np.abs(b))
    step = 2.0 ** (np.floor(np.log2(np.maximum(big, 1e-30))) - 7)
    return np.abs(a - b) / step


LIVE = {
    "every-row-live": lambda: None,
    "some-rows-dead": lambda: (jnp.arange(N) % 3 != 1).reshape(N, 1),
    "one-live-row": lambda: (jnp.arange(N) == 5).reshape(N, 1),
    "no-live-row": lambda: jnp.zeros((N, 1), bool),
}


@pytest.mark.parametrize("tile_f", [256, 128], ids=["one-tile", "two-tiles"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(LIVE) + ["every-expert-hit",
                                               "flat-stack-base"])
def test_the_kernel_equals_the_loop(monkeypatch, case, dtype, tile_f):
    flat = case == "flat-stack-base"
    lp = layer(dtype, n_layers=3 if flat else 1)
    base = 2 * E if flat else 0
    live = LIVE.get(case, LIVE["every-row-live"])()
    x = rows(dtype)
    n_hit = len(hits(x, lp, live))
    if case == "every-expert-hit":
        assert n_hit == E
    elif case == "one-live-row":
        assert n_hit == K
    elif case == "no-live-row":
        assert n_hit == 0
    (want, r_want), (got, r_got) = both(monkeypatch, x, lp, live, base,
                                        tile_f)
    assert got.shape == want.shape and got.dtype == want.dtype
    # the router and what it reports are not the kernel's
    np.testing.assert_array_equal(np.asarray(r_got), np.asarray(r_want))
    if case == "no-live-row":
        assert not np.asarray(got, np.float32).any()
    if dtype == F32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=0)
    elif tile_f == F:
        # one tile: the interpreter's dots are the loop's
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    else:
        assert bf16_steps_apart(got, want).max() <= 1
    if flat:
        # and it read this layer's experts, not the stack's first
        other = lfm2.moe_block(x, lp, K, live, 0)[0]
        assert np.abs(np.asarray(other, np.float32)
                      - np.asarray(got, np.float32)).max() > 1e-3


@pytest.mark.parametrize("hit,n,ids", [
    ([1, 1, 0, 1, 0, 0, 1, 0], 4, [0, 1, 3, 6, 6, 6, 6, 6]),
    ([0, 0, 0, 0, 0, 1, 0, 0], 1, [5] * 8),
    ([0] * 8, 0, [0] * 8),
    ([1] * 8, 8, list(range(8))),
    ([0, 0, 0, 0, 0, 0, 1, 1], 2, [6, 7, 7, 7, 7, 7, 7, 7]),
])
def test_hit_list_is_ascending_and_its_tail_repeats_the_last(hit, n, ids):
    n_hit, hit_ids = moe_pallas.hit_list(jnp.asarray(hit, bool))
    assert n_hit.shape == (1,) and int(n_hit[0]) == n
    assert np.asarray(hit_ids).tolist() == ids


def test_rows_are_padded_to_a_tile_and_cut_back(monkeypatch):
    """A step of 5 rows (not a sublane multiple) reads as its rows do
    among 32."""
    lp, x = layer(BF16), rows(BF16)
    _, (few, _r) = both(monkeypatch, x[:5], lp, None)
    _, (many, _r) = both(monkeypatch, x, lp, None)
    np.testing.assert_array_equal(np.asarray(few, np.float32),
                                  np.asarray(many[:5], np.float32))


def test_the_choice_follows_the_rows_of_the_call(monkeypatch):
    """A decode step's ``[32, 1, D]`` takes the kernel and so does a
    wave's ``[1, 256, D]``, whoever calls, up to the bound the race set;
    the first N over it, float32 weights, widths off the lanes and a
    backend that is no TPU keep the loop."""
    def calls(x, lp):
        jaxpr = jax.make_jaxpr(lambda x, lp: lfm2.moe_block(x, lp, K))(x, lp)
        return str(jaxpr).count("pallas_call")

    lp = layer(BF16)
    assert calls(rows(BF16), lp) == 0               # the CPU: the loop
    monkeypatch.setattr(moe_pallas, "_on_tpu", lambda: True)
    assert calls(rows(BF16), lp) == 1
    assert calls(rows(BF16, 256).reshape(1, 256, D), lp) == 1
    assert calls(rows(BF16, moe_pallas.MAX_ROWS).reshape(1, -1, D), lp) == 1
    assert calls(rows(BF16, moe_pallas.MAX_ROWS + 1), lp) == 0
    assert calls(rows(BF16, 1024).reshape(1, 1024, D), lp) == 0
    assert calls(rows(F32), layer(F32)) == 0
    narrow = {k: (v[..., :F - 64] if k in ("w_gate", "w_up") else
                  v[:, :F - 64] if k == "w_down" else v)
              for k, v in lp.items()}
    assert calls(rows(BF16), narrow) == 0


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_a_decode_chunk_reads_the_same_with_the_kernel(monkeypatch, dtype):
    """Eight greedy steps of ``forward_paged_chunked``, two live lanes of
    four: the same tokens, routing, logits and conv state with the kernel
    as with the loop. At bf16 the kernel engages by its own test of the
    call (only the backend question is answered here), and the two
    programs differ by what the CPU's compiler makes of the bf16 round
    trips around them (a step of bf16, not the kernel's: the block alone
    is equal to the bit, above); float32 is forced, and equal."""
    lt = ("conv", "conv", "full_attention", "conv") * 2
    cfg = get_config("tiny-lfm2", dim=256, expert_ffn_dim=256, ffn_dim=256,
                     n_heads=4, n_kv_heads=2, layer_types=lt)
    ps, slots, steps = 16, 4, 8
    params = lfm2.init_params(cfg, jax.random.PRNGKey(3), dtype)
    table = jnp.zeros((slots, cfg.max_seq_len // ps), jnp.int32)
    table = table.at[1, 0].set(3).at[2, 0].set(5)

    def decode(kernel):
        if dtype == BF16:
            monkeypatch.setattr(moe_pallas, "_on_tpu", lambda: kernel)
        else:
            monkeypatch.setattr(moe_pallas, "takes", lambda *a: kernel)
        cache = llama.init_paged_cache(cfg, slots, cfg.max_seq_len, 16, ps,
                                       dtype)
        cache["page_table"] = table
        chunk_kv = llama.init_chunk_kv(cfg, slots, steps, dtype)

        def forward(tok, pos, ck, s):
            return llama.forward_paged_chunked(params, cfg, tok, pos, cache,
                                               ck, s)

        tok = jnp.asarray([[7], [11], [13], [17]], jnp.int32)
        assert ("pallas_call" in str(jax.make_jaxpr(forward)(
            tok, tok, chunk_kv, jnp.int32(0)))) == kernel
        step = jax.jit(forward)
        toks, logits, routes = [], [], []
        for s in range(steps):
            lg, chunk_kv, routing = step(
                tok, jnp.full((slots, 1), s, jnp.int32), chunk_kv,
                jnp.int32(s))
            tok = jnp.argmax(lg[:, 0], axis=-1)[:, None].astype(jnp.int32)
            toks.append(np.asarray(tok[:, 0]))
            logits.append(np.asarray(lg[:, 0], np.float32))
            routes.append(np.asarray(routing))
        merged = llama.merge_paged_chunk(cache, chunk_kv,
                                         jnp.zeros(slots, jnp.int32))
        return (np.stack(toks), np.stack(logits), np.stack(routes),
                np.asarray(merged["state"], np.float32))

    want, got = decode(False), decode(True)
    live = [1, 2]
    toks, logits, routing, state = (
        (g[:, live], w[:, live]) for g, w in zip(got, want))
    np.testing.assert_array_equal(*toks)
    np.testing.assert_array_equal(*routing)
    if dtype == F32:
        np.testing.assert_allclose(*logits, atol=1e-4, rtol=0)
        np.testing.assert_allclose(*state, atol=1e-5, rtol=0)
    else:
        np.testing.assert_allclose(*logits, atol=0.05, rtol=0)
        # in bf16 steps of the state's largest entries
        step = 2.0 ** (np.floor(np.log2(np.abs(state[1]).max())) - 7)
        assert np.abs(state[0] - state[1]).max() <= 2 * step


# ---- deepseek-v2's shape (PR 44): D 5120, F 1536, a held share of the
# experts the router scores


def _wide_layer(n_scored, n_held, seed=4):
    d, f = 5120, 1536
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)

    def draw(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32) / fan_in ** .5).astype(BF16)

    return {"router": draw(ks[0], (d, n_scored), d),
            "expert_bias": jnp.zeros((n_scored,), F32),
            "w_gate": draw(ks[1], (n_held, d, f), d),
            "w_up": draw(ks[2], (n_held, d, f), d),
            "w_down": draw(ks[3], (n_held, f, d), f)}


@pytest.mark.parametrize("tile_f", [None, 512],
                         ids=["tile-of-1536-is-768", "three-tiles"])
def test_the_kernel_equals_the_loop_at_deepseek_widths(monkeypatch, tile_f):
    """D 5120, F 1536: ``tile_of`` gives 768 (two tiles an expert, where
    lfm2's 1792 takes 896), and the down matmul's reduction is split as
    at the tiny widths: within a bf16 step of the loop."""
    assert moe_pallas.tile_of(1536) == 768 and moe_pallas.tile_of(1792) == 896
    lp = _wide_layer(4, 4)
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 1, 5120),
                          F32).astype(BF16)
    (want, r_want), (got, r_got) = both(monkeypatch, x, lp, None,
                                        tile_f=tile_f or moe_pallas.TILE_F)
    np.testing.assert_array_equal(np.asarray(r_got), np.asarray(r_want))
    assert bf16_steps_apart(got, want).max() <= 1


@pytest.mark.parametrize("first", [0, 4], ids=["first-group", "second-group"])
def test_a_held_share_streams_only_what_is_held(monkeypatch, first):
    """The router scores 8 experts, the weights hold 4 of them from
    ``first``: the kernel and the loop agree, a choice outside the held
    ones is reported ``~e`` and reads no weight, and a call none of whose
    choices is held returns zeros."""
    lp = layer(BF16)
    held = {**lp, **{k: lp[k][first:first + 4]
                     for k in ("w_gate", "w_up", "w_down")}}
    x = rows(BF16)

    def run(kernel):
        monkeypatch.setattr(moe_pallas, "takes", lambda *a: kernel)
        return lfm2.moe_block(x, held, K, None, 0, held=(first, 4))

    (want, r_want), (got, r_got) = run(False), run(True)
    np.testing.assert_array_equal(np.asarray(r_got), np.asarray(r_want))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    r = np.asarray(r_got)
    experts = np.where(r < 0, ~r, r)
    assert ((r >= 0) == ((experts >= first) & (experts < first + 4))).all()
    assert (r < 0).any() and (r >= 0).any()
    # the part of the uncut layer that these experts give
    whole_gate = lfm2.moe_block(x, lp, K)[1]
    np.testing.assert_array_equal(experts, np.asarray(whole_gate))


# ------------------------------------------------- un-gated relu² experts


def _relu2_layer(d, f, e, dtype, seed=9):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)

    def draw(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32) / fan_in ** .5).astype(dtype)

    return {"router": draw(ks[0], (d, e), d),
            "expert_bias": 0.1 * jax.random.normal(ks[1], (e,), F32),
            "w_up": draw(ks[2], (e, d, f), d),
            "w_down": draw(ks[3], (e, f, d), f)}


@pytest.mark.parametrize("d,f,tile_f,dtype", [
    (256, 256, 128, F32), (256, 256, 128, BF16), (256, 256, None, BF16),
    (2688, 1920, None, BF16)],
    ids=["f32-two-tiles", "bf16-two-tiles", "bf16-one-tile",
         "nemotron-2688-1856-as-laid-out"])
def test_the_kernel_equals_the_loop_for_ungated_relu2_experts(
        monkeypatch, d, f, tile_f, dtype):
    """A layer without ``w_gate`` streams two matrices an expert. The
    published 2688 / 1856 is kept at 1920 columns, the upper 64 zero
    (``nemotron_h.lanes_up``): three tiles of 640."""
    from swarmdb_tpu.models import nemotron_h

    e = 4
    lp = _relu2_layer(d, f, e, dtype)
    if f == 1920:
        assert nemotron_h.lanes_up(1856) == f and moe_pallas.tile_of(f) == 640
        lp["w_up"] = lp["w_up"].at[..., 1856:].set(0)
        lp["w_down"] = lp["w_down"].at[:, 1856:].set(0)
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 1, d),
                          F32).astype(dtype)
    monkeypatch.setattr(moe_pallas, "_on_tpu", lambda: True)
    assert moe_pallas.takes(16, BF16, lp["w_up"]) == (dtype == BF16)
    (want, r_want), (got, r_got) = both(monkeypatch, x, lp, None, 0, tile_f)
    np.testing.assert_array_equal(np.asarray(r_got), np.asarray(r_want))
    if dtype == F32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=0)
    else:
        # three tiles split the down matmul's sum three ways: where the
        # sum cancels to 1e-5 of its terms one element in 43,008 lands
        # two bf16 steps off
        steps = bf16_steps_apart(got, want)
        assert steps.max() <= (2 if f == 1920 else 1)
        assert (steps > 1).mean() < 1e-3
    # and the loop's expert is the un-gated form by hand
    xf = x.reshape(-1, d)
    one = lfm2.expert_ffn(xf, None, lp["w_up"][0], lp["w_down"][0])
    np.testing.assert_allclose(
        np.asarray(one, np.float32),
        np.asarray(jnp.square(jax.nn.relu(
            xf.astype(F32) @ lp["w_up"][0].astype(F32))).astype(dtype)
            .astype(F32) @ lp["w_down"][0].astype(F32)),
        atol=2e-2 if dtype == BF16 else 1e-4, rtol=2e-2)
