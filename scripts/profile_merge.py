#!/usr/bin/env python
"""Round-4: localize the fixed ~20 ms of full-cache `copy.*` ops the chunk
trace shows around the decode while-loop, and race merge formulations.

Variants (all greedy, B=128, K=16, S=256 unless overridden):
  A current: cache closed over as scan constant, donated, einsum+where merge
  B cache threaded through the scan carry instead of closure
  C no donation (copies should become explicit/visible)
  D scatter-form merge (.at[b, start+j].set) instead of einsum+where
  E no merge at all (floor)

Also dumps the optimized HLO of variant A and prints every `copy` /
`select` op touching a cache-shaped operand, so trace names map to HLO.

Run (in the one process that owns the chip): python scripts/profile_merge.py
"""
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from swarmdb_tpu.models import llama
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.backend.sampling import make_slot_keys, sample_tokens
from swarmdb_tpu.utils.xla_cache import enable_compile_cache

enable_compile_cache()

B = int(sys.argv[1]) if len(sys.argv) > 1 else 128
K = int(sys.argv[2]) if len(sys.argv) > 2 else 16
S = int(sys.argv[3]) if len(sys.argv) > 3 else 256
cfg = get_config("llama-1b-bench")
print(f"device={jax.devices()[0]} B={B} K={K} S={S}", flush=True)

params = llama.init_params(cfg, jax.random.PRNGKey(0))
jax.block_until_ready(params)
keys = make_slot_keys(0, B)
temp = jnp.zeros((B,), jnp.float32)
topk = jnp.zeros((B,), jnp.int32)
topp = jnp.ones((B,), jnp.float32)


def body_step(params, cache, tok, pos, chunk_kv, step):
    logits, chunk_kv = llama.forward_chunked(
        params, cfg, tok[:, None], pos[:, None], cache, chunk_kv, step)
    nxt = sample_tokens(logits[:, -1], keys, pos, temp, topk, topp,
                        use_filters=False, assume_greedy=True)
    return nxt, chunk_kv


def merge_scatter(cache, chunk_kv, start):
    ck, cv = cache
    hk, hv = chunk_kv  # [L, B, Kc, H, D]
    Kc = hk.shape[2]
    b_idx = jnp.arange(B)[:, None]                       # [B, 1]
    cols = start[:, None] + jnp.arange(Kc)[None, :]      # [B, Kc]
    ck = ck.at[:, b_idx, cols].set(hk)
    cv = cv.at[:, b_idx, cols].set(hv)
    return ck, cv


def make(variant):
    def _decode(params, last_tokens, positions, cache):
        chunk_kv = llama.init_chunk_kv(cfg, B, K)

        if variant == "B":
            def body(carry, step):
                tok, pos, cache, chunk_kv = carry
                nxt, chunk_kv = body_step(params, cache, tok, pos, chunk_kv,
                                          step)
                return (nxt, pos + 1, cache, chunk_kv), nxt

            (last, _, cache, chunk_kv), sampled = jax.lax.scan(
                body, (last_tokens, positions, cache, chunk_kv),
                jnp.arange(K, dtype=jnp.int32))
        else:
            def body(carry, step):
                tok, pos, chunk_kv = carry
                nxt, chunk_kv = body_step(params, cache, tok, pos, chunk_kv,
                                          step)
                return (nxt, pos + 1, chunk_kv), nxt

            (last, _, chunk_kv), sampled = jax.lax.scan(
                body, (last_tokens, positions, chunk_kv),
                jnp.arange(K, dtype=jnp.int32))

        if variant == "D":
            cache = merge_scatter(cache, chunk_kv, positions)
        elif variant == "E":
            pass
        else:
            cache = llama.merge_chunk(cache, chunk_kv, positions)
        return jnp.concatenate([last_tokens[None], sampled], 0), last, cache

    donate = () if variant == "C" else (3,)
    return jax.jit(_decode, donate_argnums=donate)


def run(label, fn, n=6):
    cache = llama.init_kv_cache(cfg, B, S)
    jax.block_until_ready(cache)
    last = jnp.zeros((B,), jnp.int32)
    pos = jnp.full((B,), 64, jnp.int32)
    best, t_compile = 1e9, None
    for i in range(n):
        t0 = time.perf_counter()
        all_toks, last, cache = fn(params, last, pos, cache)
        np.asarray(jax.device_get(all_toks))
        dt = time.perf_counter() - t0
        if i == 0:
            t_compile = dt
        else:
            best = min(best, dt)
    print(f"  {label:46s} {best*1e3:8.1f} ms   (first {t_compile:5.1f} s)",
          flush=True)
    return best


run("A current (const cache, donate, einsum merge)", make("A"))
run("B cache in scan carry", make("B"))
run("C no donation", make("C"))
run("D scatter merge", make("D"))
run("E no merge (floor)", make("E"))

# ---- HLO dump of A: find the copies --------------------------------------
try:
    cache = llama.init_kv_cache(cfg, B, S)
    last = jnp.zeros((B,), jnp.int32)
    pos = jnp.full((B,), 64, jnp.int32)
    txt = make("A").lower(params, last, pos, cache).compile().as_text()
    cache_shape = f"bf16[{cfg.n_layers},{B},{S},{cfg.n_kv_heads},{cfg.head_dim}]"
    n = 0
    for line in txt.splitlines():
        if re.search(r"%?(copy|select)[.\d]*\s*=", line) and "bf16[16,128" in line:
            print("   ", line.strip()[:160], flush=True)
            n += 1
            if n > 24:
                break
    print(f"  ({n} cache-sized copy/select lines)", flush=True)
except Exception as e:
    print(f"HLO dump unavailable: {type(e).__name__}: {e}", flush=True)
