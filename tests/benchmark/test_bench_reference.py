"""The plain reference against the program's ``llama.forward`` on a tiny
seeded model in float32: the two are written apart and must agree."""

import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def test_reference_matches_llama_forward():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import decoder
    from swarmdb_tpu.models import llama
    from swarmdb_tpu.models.configs import ModelConfig

    cfg = ModelConfig(name="t", vocab_size=300, dim=64, n_layers=3,
                      n_heads=8, n_kv_heads=2, ffn_dim=96, norm_eps=1e-6,
                      rope_theta=5e6, max_seq_len=decoder.Q_BLOCK)
    params = llama.init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    T = decoder.Q_BLOCK
    tokens = jax.random.randint(jax.random.PRNGKey(1), (T,), 3, 300)
    with jax.default_matmul_precision("highest"):
        want, _ = llama.forward(params, cfg, tokens[None],
                                jnp.arange(T)[None],
                                llama.init_kv_cache(cfg, 1, T,
                                                    dtype=jnp.float32))
    at = jnp.asarray([0, 1, 17, 100, T - 1])
    got = decoder.logits_at(
        params, dict(n_heads=8, n_kv_heads=2, eps=1e-6, theta=5e6), tokens,
        at)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0])[at],
                               atol=2e-4, rtol=2e-4)
