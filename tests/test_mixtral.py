"""Mixtral MoE tests: routing invariants, cache equivalence, HF parity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmdb_tpu.models import mixtral
from swarmdb_tpu.models.configs import TINY_MOE, get_config


@pytest.fixture(scope="module")
def tiny_moe():
    cfg = TINY_MOE
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def test_forward_shapes_and_cache(tiny_moe):
    cfg, params = tiny_moe
    B, T, S = 2, 5, 32
    cache = mixtral.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    tokens = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T) % cfg.vocab_size
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    logits, (ck, cv) = mixtral.forward(params, cfg, tokens, pos, cache)
    assert logits.shape == (B, T, cfg.vocab_size)
    assert ck.shape == (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim)


def test_prefill_decode_equivalence(tiny_moe):
    cfg, params = tiny_moe
    B, T, S = 1, 6, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    cache = mixtral.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    full, _ = mixtral.forward(params, cfg, tokens, pos, cache)

    cache = mixtral.init_kv_cache(cfg, B, S, dtype=jnp.float32)
    _, cache = mixtral.forward(params, cfg, tokens[:, :4], pos[:, :4], cache)
    outs = []
    for t in range(4, T):
        l, cache = mixtral.forward(params, cfg, tokens[:, t:t+1], pos[:, t:t+1], cache)
        outs.append(l)
    inc = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full[:, 4:]), np.asarray(inc),
                               rtol=2e-4, atol=2e-4)


def test_moe_block_top1_picks_best_expert():
    """With top_k=1 and capacity >= tokens, output must equal the argmax
    expert's FFN applied per token (gate weight 1.0)."""
    D, F, E, N = 8, 16, 4, 6
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (1, N, D), jnp.float32)
    router = jax.random.normal(ks[1], (D, E), jnp.float32)
    wg = jax.random.normal(ks[2], (E, D, F), jnp.float32) * 0.1
    wu = jax.random.normal(ks[3], (E, D, F), jnp.float32) * 0.1
    wd = jax.random.normal(ks[4], (E, F, D), jnp.float32) * 0.1

    y, load, _ = mixtral.moe_block(x, router, wg, wu, wd, top_k=1,
                                capacity_factor=float(E))  # no drops
    # manual per-token expert apply
    xf = x[0]
    sel = jnp.argmax(xf @ router, axis=-1)
    expected = []
    for n in range(N):
        e = int(sel[n])
        g = jax.nn.silu(xf[n] @ wg[e])
        u = xf[n] @ wu[e]
        expected.append((g * u) @ wd[e])
    expected = jnp.stack(expected)[None]
    np.testing.assert_allclose(np.asarray(y), np.asarray(expected),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.sum(load)) == pytest.approx(1.0)  # top-1: loads sum to 1


def test_moe_capacity_drops_overflow():
    """Force every token to one expert with capacity 1: only one token's
    output is nonzero."""
    D, F, E, N = 4, 8, 4, 8
    x = jnp.ones((1, N, D), jnp.float32)
    router = jnp.zeros((D, E), jnp.float32).at[:, 2].set(10.0)  # all -> expert 2
    key = jax.random.PRNGKey(0)
    wg = jax.random.normal(key, (E, D, F), jnp.float32)
    wu = jnp.ones((E, D, F), jnp.float32)
    wd = jnp.ones((E, F, D), jnp.float32)
    # capacity_factor chosen so C = 1: N*k*cf/E = 8*1*cf/4 = 1 -> cf = 0.5
    y, _, _ = mixtral.moe_block(x, router, wg, wu, wd, top_k=1, capacity_factor=0.5)
    nonzero_rows = jnp.sum(jnp.any(jnp.abs(y[0]) > 1e-9, axis=-1))
    assert int(nonzero_rows) == 1


def _hf_tiny_mixtral(cfg):
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import MixtralConfig, MixtralForCausalLM

    hf_cfg = MixtralConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.dim,
        intermediate_size=cfg.ffn_dim,
        num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        num_local_experts=cfg.n_experts,
        num_experts_per_tok=cfg.experts_per_token,
        rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta,
        max_position_embeddings=cfg.max_seq_len,
        sliding_window=None,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    m = MixtralForCausalLM(hf_cfg)
    m.eval()
    return m


def test_numerics_match_hf_mixtral():
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    cfg = get_config("tiny-moe")
    model = _hf_tiny_mixtral(cfg)
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    L, E = cfg.n_layers, cfg.n_experts

    def stack(fmt, transpose=True):
        mats = [sd[fmt.format(i)] for i in range(L)]
        return jnp.asarray(np.stack([m.T if transpose else m for m in mats]),
                           jnp.float32)

    def stack_experts(fmt, transpose=True):
        out = []
        for i in range(L):
            per = [sd[fmt.format(i, e)] for e in range(E)]
            out.append(np.stack([m.T if transpose else m for m in per]))
        return jnp.asarray(np.stack(out), jnp.float32)

    params = {
        "embed": jnp.asarray(sd["model.embed_tokens.weight"], jnp.float32),
        "layers": {
            "attn_norm": stack("model.layers.{}.input_layernorm.weight", False),
            "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
            "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
            "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
            "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
            "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight", False),
            "router": stack("model.layers.{}.block_sparse_moe.gate.weight"),
            # HF expert naming: w1=gate [F,D], w2=down [D,F], w3=up [F,D]
            "w_gate": stack_experts("model.layers.{}.block_sparse_moe.experts.{}.w1.weight"),
            "w_up": stack_experts("model.layers.{}.block_sparse_moe.experts.{}.w3.weight"),
            "w_down": stack_experts("model.layers.{}.block_sparse_moe.experts.{}.w2.weight"),
        },
        "final_norm": jnp.asarray(sd["model.norm.weight"], jnp.float32),
        "lm_head": jnp.asarray(sd["lm_head.weight"].T, jnp.float32),
    }

    B, T = 2, 7
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(B, T))
    with torch.no_grad():
        hf_logits = model(torch.tensor(toks)).logits.numpy()
    cache = mixtral.init_kv_cache(cfg, B, 16, dtype=jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    ours, _ = mixtral.forward(params, cfg, jnp.asarray(toks, jnp.int32), pos, cache)
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=3e-3, atol=3e-3)


def test_wrong_family_raises(tiny_moe):
    cfg, params = tiny_moe
    from swarmdb_tpu.models import llama
    from swarmdb_tpu.models.configs import TINY_DEBUG
    with pytest.raises(ValueError):
        llama.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        mixtral.init_params(TINY_DEBUG, jax.random.PRNGKey(0))
