"""Llama model tests: shapes, KV-cache decode equivalence, and numerics
parity against HF transformers (torch CPU) on a tiny config."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmdb_tpu.models import llama
from swarmdb_tpu.models.configs import TINY_DEBUG, get_config


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = TINY_DEBUG
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def test_forward_shapes(tiny_setup):
    cfg, params = tiny_setup
    B, T, S = 2, 5, 32
    cache = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    tokens = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T) % cfg.vocab_size
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    logits, (ck, cv) = llama.forward(params, cfg, tokens, positions, cache)
    assert logits.shape == (B, T, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert ck.shape == (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim)


def test_prefill_then_decode_matches_full_forward(tiny_setup):
    """Incremental decode through the KV cache must reproduce the full
    forward pass — the core correctness property of the serving engine."""
    cfg, params = tiny_setup
    B, T, S = 1, 8, 32
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    # full forward
    cache = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    full_logits, _ = llama.forward(params, cfg, tokens, positions, cache)

    # prefill first 5, then decode 3 one-at-a-time
    cache = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    _, cache = llama.forward(params, cfg, tokens[:, :5], positions[:, :5], cache)
    outs = []
    for t in range(5, T):
        logits_t, cache = llama.forward(
            params, cfg, tokens[:, t:t + 1], positions[:, t:t + 1], cache)
        outs.append(logits_t)
    inc = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(full_logits[:, 5:], inc, rtol=2e-4, atol=2e-4)


def test_mixed_position_batch_decode(tiny_setup):
    """Continuous batching: two slots at different decode offsets in one
    batched step must each match their single-sequence result."""
    cfg, params = tiny_setup
    S = 32
    key = jax.random.PRNGKey(2)
    seq_a = jax.random.randint(key, (1, 6), 0, cfg.vocab_size)
    seq_b = jax.random.randint(jax.random.PRNGKey(3), (1, 3), 0, cfg.vocab_size)

    def run_single(seq):
        T = seq.shape[1]
        cache = llama.init_kv_cache(cfg, 1, S, dtype=jnp.float32)
        pos = jnp.arange(T, dtype=jnp.int32)[None]
        logits, _ = llama.forward(params, cfg, seq, pos, cache)
        return logits[:, -1]

    ref_a, ref_b = run_single(seq_a), run_single(seq_b)

    # batch both into slots; prefill separately then joint decode of last token
    cache = llama.init_kv_cache(cfg, 2, S, dtype=jnp.float32)
    ca = llama.init_kv_cache(cfg, 1, S, dtype=jnp.float32)
    _, ca = llama.forward(params, cfg, seq_a[:, :-1],
                          jnp.arange(5, dtype=jnp.int32)[None], ca)
    cb = llama.init_kv_cache(cfg, 1, S, dtype=jnp.float32)
    _, cb = llama.forward(params, cfg, seq_b[:, :-1],
                          jnp.arange(2, dtype=jnp.int32)[None], cb)
    cache = (
        jnp.concatenate([ca[0], cb[0]], axis=1),
        jnp.concatenate([ca[1], cb[1]], axis=1),
    )
    tokens = jnp.concatenate([seq_a[:, -1:], seq_b[:, -1:]], axis=0)  # [2,1]
    positions = jnp.array([[5], [2]], dtype=jnp.int32)
    logits, _ = llama.forward(params, cfg, tokens, positions, cache)
    np.testing.assert_allclose(logits[0, 0], ref_a[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(logits[1, 0], ref_b[0], rtol=2e-4, atol=2e-4)


def _hf_tiny_model(cfg):
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.dim,
        intermediate_size=cfg.ffn_dim,
        num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta,
        max_position_embeddings=cfg.max_seq_len,
        attention_bias=False,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    return model


def hf_to_params(model, cfg):
    """Convert HF Llama weights to our pytree layout (cited convention:
    our w* are [in, out] = transpose of torch Linear [out, in])."""
    import torch

    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    L = cfg.n_layers

    def stack(fmt, transpose=True):
        mats = [sd[fmt.format(i)] for i in range(L)]
        arr = np.stack([m.T if transpose else m for m in mats])
        return jnp.asarray(arr, dtype=jnp.float32)

    params = {
        "embed": jnp.asarray(sd["model.embed_tokens.weight"], jnp.float32),
        "layers": {
            "attn_norm": stack("model.layers.{}.input_layernorm.weight", transpose=False),
            "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
            "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
            "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
            "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
            "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight", transpose=False),
            "w_gate": stack("model.layers.{}.mlp.gate_proj.weight"),
            "w_up": stack("model.layers.{}.mlp.up_proj.weight"),
            "w_down": stack("model.layers.{}.mlp.down_proj.weight"),
        },
        "final_norm": jnp.asarray(sd["model.norm.weight"], jnp.float32),
        "lm_head": jnp.asarray(sd["lm_head.weight"].T, jnp.float32),
    }
    return params


def test_numerics_match_hf_reference():
    """Logits must match HF transformers' Llama (torch CPU) bit-for-nearly."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    cfg = get_config("tiny-debug")
    model = _hf_tiny_model(cfg)
    params = hf_to_params(model, cfg)

    B, T = 2, 7
    rng = np.random.default_rng(0)
    tokens_np = rng.integers(0, cfg.vocab_size, size=(B, T))
    with torch.no_grad():
        hf_logits = model(torch.tensor(tokens_np)).logits.numpy()

    cache = llama.init_kv_cache(cfg, B, 16, dtype=jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    ours, _ = llama.forward(params, cfg, jnp.asarray(tokens_np, jnp.int32),
                            positions, cache)
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-3, atol=2e-3)


def test_logits_at_matches_full_forward():
    """Head-at-last-position prefill (engine forward_last_fn) matches the
    full forward's logits at those positions (same math; only reduction
    tiling may differ -> tight tolerance, not bitwise)."""
    cfg = get_config("tiny-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    B, T = 3, 9
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(B, T)),
                         jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    lengths = jnp.asarray([9, 4, 7], jnp.int32)

    full, (ck, cv) = llama.forward(params, cfg, tokens, positions,
                                   llama.init_kv_cache(cfg, B, T))
    last, (ck2, cv2) = llama.forward(params, cfg, tokens, positions,
                                     llama.init_kv_cache(cfg, B, T),
                                     logits_at=lengths - 1)
    np.testing.assert_allclose(
        np.asarray(last),
        np.asarray(full[jnp.arange(B), lengths - 1]),
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(ck2))


def test_merge_chunk_scatter_matches_einsum():
    """The scatter-form chunk merge (SWARMDB_MERGE=scatter) must be
    bit-identical to the one-hot-einsum form for in-range chunks AND for
    chunks overshooting the lane end (einsum: hit-mask drop; scatter:
    mode='drop')."""
    from swarmdb_tpu.ops.layers import (merge_chunk_kv,
                                        merge_chunk_kv_scatter)

    rng = np.random.default_rng(11)
    L, B, S, Kc, H, D = 3, 5, 32, 8, 2, 4
    ck = jnp.asarray(rng.normal(size=(L, B, S, H, D)).astype(np.float32))
    cv = jnp.asarray(rng.normal(size=(L, B, S, H, D)).astype(np.float32))
    hk = jnp.asarray(rng.normal(size=(L, B, Kc, H, D)).astype(np.float32))
    hv = jnp.asarray(rng.normal(size=(L, B, Kc, H, D)).astype(np.float32))
    # rows: interior, position 0, exactly flush with the end, overshooting
    # by half a chunk, overshooting entirely except one column
    starts = jnp.asarray(np.array([10, 0, S - Kc, S - Kc // 2, S - 1],
                                  np.int32))
    ek, ev = merge_chunk_kv(ck, cv, hk, hv, starts)
    sk, sv = merge_chunk_kv_scatter(ck, cv, hk, hv, starts)
    np.testing.assert_array_equal(np.asarray(ek), np.asarray(sk))
    np.testing.assert_array_equal(np.asarray(ev), np.asarray(sv))


# ---------------------------------------------------------------------------
# one decoder, every forward, both families: each forward is its own
# attention-and-cache step around the same layer body, so stepped over a
# short sequence it must give ``forward``'s logits whatever the FFN is


@functools.lru_cache(maxsize=None)
def _family_setup(family):
    from swarmdb_tpu.models import mixtral
    from swarmdb_tpu.models.configs import TINY_MOE

    cfg, mod = ((TINY_DEBUG, llama) if family == "dense"
                else (TINY_MOE, mixtral))
    # TINY_MOE's capacity is a token an expert for every token of a call
    # (N * 2 * 2.0 / 4), so no call size drops one and calls of different
    # sizes can be compared
    params = mod.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    B, T, S = 2, 12, 16
    tokens = jnp.asarray(
        np.random.default_rng(9).integers(1, cfg.vocab_size, size=(B, T)),
        jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    # (a routed family's forwards return their routing last, here and in
    # every call below: tests/test_routing_record.py holds them to it)
    want, cache, *_routing = llama.forward(
        params, cfg, tokens, positions,
        llama.init_kv_cache(cfg, B, S, dtype=jnp.float32))
    return cfg, params, tokens, want, cache


def _pages_of(cache, n_tokens, ps):
    """A page pool holding each row's first ``n_tokens`` of ``cache``
    (row b's pages are 1 + b * maxp ...; page 0 is the trash page) and the
    rows' page table."""
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
    "forward_chunked", "forward_paged_chunked",
    "forward_prefix_pages", "forward_prefix_lane"])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_every_forward_gives_forwards_logits(family, name):
    cfg, params, tokens, want, cache = _family_setup(family)
    B, T = tokens.shape
    ps, P0 = 4, 8           # two pages of history, then four more tokens
    pool_k, pool_v, table = _pages_of(cache, P0, ps)

    if name.startswith("forward_prefix"):
        args = (params, cfg, tokens[:, P0:], table[:, :P0 // ps],
                jnp.full((B,), P0, jnp.int32), pool_k, pool_v)
        if name == "forward_prefix_lane":
            got, lane_k, *_ = llama.forward_prefix_lane(*args, T // ps)
            np.testing.assert_allclose(np.asarray(lane_k[:, :, :T]),
                                       np.asarray(cache[0][:, :, :T]),
                                       rtol=1e-4, atol=1e-4)
        else:
            got, *_ = llama.forward_prefix_pages(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, P0:]),
                                   rtol=1e-4, atol=1e-4)
        return

    # decode: the first P0 tokens are history, the rest come a step each
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
            got, chunk, *_ = llama.forward_chunked(params, cfg, tok, pos,
                                                   history, chunk, at)
        else:
            got, chunk, *_ = llama.forward_paged_chunked(
                params, cfg, tok, pos, paged, chunk, at)
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(want[:, P0 + step]),
                                   rtol=1e-4, atol=1e-4)


def test_routed_ragged_prefill_gives_forwards_logits():
    """The ragged prefill of a routed configuration, which the engine
    does not wire yet (backend/service.py: a packed stream and a
    row-bucketed wave reckon different capacities): on one row with no
    padding the two calls hold the same tokens, so the capacities agree
    and the logits and the suffix K/V must be ``forward``'s."""
    cfg, params, tokens, want, cache = _family_setup("moe")
    T, ps = tokens.shape[1], 4
    pool_k, pool_v, table = _pages_of(cache, 0, ps)
    got, sfx_k, _, _routing = llama.forward_ragged_prefill(
        params, cfg, tokens[0], jnp.zeros((T,), jnp.int32),
        jnp.arange(T, dtype=jnp.int32), table[:1],
        jnp.asarray([0], jnp.int32), jnp.asarray([T], jnp.int32),
        jnp.asarray([0], jnp.int32), pool_k, pool_v)
    alone, (ck, _), _routing = llama.forward(
        params, cfg, tokens[:1], jnp.arange(T, dtype=jnp.int32)[None],
        llama.init_kv_cache(cfg, 1, T, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(alone[0, -1]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sfx_k), np.asarray(ck[:, 0]),
                               rtol=1e-4, atol=1e-4)
