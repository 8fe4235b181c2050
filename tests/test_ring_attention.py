"""Ring attention + sequence-parallel prefill vs the dense reference path
(8 virtual devices, conftest.py)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from swarmdb_tpu.models import llama
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.ops.layers import gqa_attention
from swarmdb_tpu.ops.ring_attention import ring_attention
from swarmdb_tpu.parallel import make_mesh

shard_map = functools.partial(jax.shard_map, check_vma=False)


def _ring_mesh():
    return make_mesh(8, data=8, model=1, expert=1)


def test_ring_attention_matches_dense():
    B, T, Hq, Hkv, D = 2, 32, 4, 2, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, T, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)).astype(np.float32))
    pos = jnp.tile(jnp.arange(T)[None], (B, 1))

    mesh = _ring_mesh()
    ring = shard_map(
        lambda q, k, v, qp, kp: ring_attention(q, k, v, qp, kp, "data"),
        mesh=mesh,
        in_specs=(P(None, "data"), P(None, "data"), P(None, "data"),
                  P(None, "data"), P(None, "data")),
        out_specs=P(None, "data"),
    )
    out = ring(q, k, v, pos, pos)

    # dense reference: gqa_attention over a "cache" holding exactly k/v
    ref = gqa_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_ring_attention_shuffled_chunks_still_causal():
    """Causality is by global position, not ring layout: give device i a
    non-contiguous slice of positions and the result must still match."""
    B, T, Hq, Hkv, D = 1, 16, 2, 1, 8
    rng = np.random.default_rng(1)
    q = rng.normal(size=(B, T, Hq, D)).astype(np.float32)
    k = rng.normal(size=(B, T, Hkv, D)).astype(np.float32)
    v = rng.normal(size=(B, T, Hkv, D)).astype(np.float32)
    pos = np.tile(np.arange(T)[None], (B, 1))

    perm = rng.permutation(T)
    mesh = _ring_mesh()
    ring = shard_map(
        lambda q, k, v, qp, kp: ring_attention(q, k, v, qp, kp, "data"),
        mesh=mesh,
        in_specs=(P(None, "data"), P(None, "data"), P(None, "data"),
                  P(None, "data"), P(None, "data")),
        out_specs=P(None, "data"),
    )
    out_perm = ring(
        jnp.asarray(q[:, perm]), jnp.asarray(k[:, perm]),
        jnp.asarray(v[:, perm]),
        jnp.asarray(pos[:, perm]), jnp.asarray(pos[:, perm]),
    )
    ref = gqa_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        jnp.asarray(pos))
    # un-permute the ring output back to natural order
    inv = np.argsort(perm)
    np.testing.assert_allclose(np.asarray(out_perm)[:, inv], np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_seq_parallel_prefill_matches_dense_forward():
    cfg = get_config("tiny-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, T = 1, 64  # 8 tokens per device
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size, size=(B, T)),
                         jnp.int32)
    positions = jnp.tile(jnp.arange(T)[None], (B, 1))

    mesh = _ring_mesh()
    logits_sp, (ks, vs) = llama.forward_seq_parallel(
        params, cfg, tokens, positions, mesh
    )

    cache = llama.init_kv_cache(cfg, B, T, dtype=jnp.float32)
    logits_ref, (ck, cv) = llama.forward(params, cfg, tokens, positions, cache)

    np.testing.assert_allclose(np.asarray(logits_sp), np.asarray(logits_ref),
                               rtol=2e-3, atol=2e-3)
    # the prompt KV matches the slot cache contents
    np.testing.assert_allclose(np.asarray(ks), np.asarray(ck),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(vs), np.asarray(cv),
                               rtol=2e-3, atol=2e-3)


def test_seq_parallel_then_decode_continuation():
    """Long-prefill KV scattered into a slot cache must support ordinary
    decode continuation (the engine hook)."""
    cfg = get_config("tiny-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    B, T, S = 1, 32, 48
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size, size=(B, T)), jnp.int32)
    positions = jnp.tile(jnp.arange(T)[None], (B, 1))

    mesh = _ring_mesh()
    logits_sp, (ks, vs) = llama.forward_seq_parallel(
        params, cfg, tokens, positions, mesh
    )
    next_tok = jnp.argmax(logits_sp[:, -1], -1).astype(jnp.int32)

    # scatter prompt KV into a larger slot cache and decode one step
    cache = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    ck = cache[0].at[:, :, :T].set(jax.device_get(ks))
    cv = cache[1].at[:, :, :T].set(jax.device_get(vs))
    logits_d, _ = llama.forward(
        params, cfg, next_tok[:, None], jnp.asarray([[T]]), (ck, cv)
    )

    # reference: dense forward over the full T+1 sequence
    full = jnp.concatenate([tokens, next_tok[:, None]], axis=1)
    pos_full = jnp.tile(jnp.arange(T + 1)[None], (B, 1))
    cache_ref = llama.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    logits_ref, _ = llama.forward(params, cfg, full, pos_full, cache_ref)
    np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                               np.asarray(logits_ref[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_seq_parallel_respects_sliding_window():
    """Windowed configs must agree between forward() and the ring path
    (review finding: window was only half-plumbed)."""
    from dataclasses import replace

    cfg = replace(get_config("tiny-debug"), sliding_window=8)
    params = llama.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    B, T = 1, 32
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size, size=(B, T)), jnp.int32)
    positions = jnp.tile(jnp.arange(T)[None], (B, 1))

    mesh = _ring_mesh()
    logits_sp, _ = llama.forward_seq_parallel(params, cfg, tokens, positions, mesh)
    cache = llama.init_kv_cache(cfg, B, T, dtype=jnp.float32)
    logits_ref, _ = llama.forward(params, cfg, tokens, positions, cache)
    np.testing.assert_allclose(np.asarray(logits_sp), np.asarray(logits_ref),
                               rtol=2e-3, atol=2e-3)
    # sanity: the window actually changes the result vs full attention
    full, _ = llama.forward(
        params, replace(cfg, sliding_window=None), tokens, positions,
        llama.init_kv_cache(cfg, B, T, dtype=jnp.float32))
    assert not np.allclose(np.asarray(logits_ref), np.asarray(full))
