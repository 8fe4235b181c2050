#!/usr/bin/env python
"""Round-4 decode-latency investigation (VERDICT r3 weak #1).

Times each piece of the engine hot path in isolation on the real device:
param init, a bare forward step, a sampled decode chunk, device_get sync,
host->device arg transfer, and the full Engine chunk — with
jax_log_compiles on so silent retraces are visible.

Run:  python scripts/profile_decode.py [model] [batch] [chunk]
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_log_compiles", True)

model = sys.argv[1] if len(sys.argv) > 1 else "llama-1b-bench"
B = int(sys.argv[2]) if len(sys.argv) > 2 else 32
K = int(sys.argv[3]) if len(sys.argv) > 3 else 16
S = 256

from swarmdb_tpu.models import llama
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.backend.sampling import make_slot_keys, sample_tokens

cfg = get_config(model)
dev = jax.devices()[0]
print(f"device: {dev} platform={dev.platform}", flush=True)


def t(label, fn, n=3):
    out = None
    for i in range(n):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        print(f"  {label} [{i}]: {dt*1e3:.1f} ms", flush=True)
    return out


print("== param init ==", flush=True)
t0 = time.perf_counter()
params = llama.init_params(cfg, jax.random.PRNGKey(0))
jax.block_until_ready(params)
print(f"  init_params: {time.perf_counter()-t0:.2f} s", flush=True)
nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
print(f"  param bytes: {nbytes/1e9:.2f} GB", flush=True)

cache = llama.init_kv_cache(cfg, B, S)
jax.block_until_ready(cache)

print("== tiny sync latency (host<->device round trip) ==", flush=True)
one = jnp.ones((8,), jnp.int32)
jax.block_until_ready(one)
for i in range(3):
    t0 = time.perf_counter()
    np.asarray(jax.device_get(one))
    print(f"  device_get tiny [{i}]: {(time.perf_counter()-t0)*1e3:.1f} ms",
          flush=True)

print("== host->device arg transfer (32KB numpy via jit arg) ==", flush=True)
f_id = jax.jit(lambda x: x + 1)
arg = np.zeros((B,), np.float32)
t("jit(x+1) with np arg", lambda: f_id(arg))

print("== bare forward decode step (no sampling) ==", flush=True)
fwd = jax.jit(lambda p, t_, pos, c: llama.forward(p, cfg, t_, pos, c))
toks = jnp.zeros((B, 1), jnp.int32)
pos = jnp.zeros((B, 1), jnp.int32)
out = t("forward [B,1]", lambda: fwd(params, toks, pos, cache), n=4)

print("== sampling alone ==", flush=True)
logits = jnp.zeros((B, cfg.vocab_size), jnp.float32)
keys = make_slot_keys(0, B)
temp = np.zeros(B, np.float32)
topk = np.zeros(B, np.int32)
topp = np.ones(B, np.float32)
samp = jax.jit(sample_tokens)
posv = jnp.zeros((B,), jnp.int32)
t("sample_tokens", lambda: samp(logits, keys, posv, temp, topk, topp), n=4)

print("== full K-step chunk (scan of forward+sample), NO donation ==", flush=True)


def _decode(params, last_tokens, positions, cache, base_keys, temp, topk, topp):
    def body(carry, _):
        tok, pos, cache = carry
        logits, cache = llama.forward(params, cfg, tok[:, None], pos[:, None], cache)
        nxt = sample_tokens(logits[:, -1], base_keys, pos, temp, topk, topp)
        return (nxt, pos + 1, cache), nxt

    (last, _, cache), sampled = jax.lax.scan(
        body, (last_tokens, positions, cache), None, length=K)
    all_toks = jnp.concatenate([last_tokens[None], sampled], axis=0)
    return all_toks, last, cache


dec_nodonate = jax.jit(_decode)
last = jnp.zeros((B,), jnp.int32)
positions_np = np.zeros((B,), np.int32)

print("  -- no-donate --", flush=True)
state = [last, cache]
for i in range(4):
    t0 = time.perf_counter()
    all_toks, l2, c2 = dec_nodonate(params, state[0], positions_np, state[1],
                                    keys, temp, topk, topp)
    jax.block_until_ready(all_toks)
    print(f"  chunk nodonate [{i}]: {(time.perf_counter()-t0)*1e3:.1f} ms",
          flush=True)
    state = [l2, c2]

print("  -- donate cache (engine config) --", flush=True)
dec_donate = jax.jit(_decode, donate_argnums=(3,))
cache2 = llama.init_kv_cache(cfg, B, S)
jax.block_until_ready(cache2)
state = [last, cache2]
for i in range(4):
    t0 = time.perf_counter()
    all_toks, l2, c2 = dec_donate(params, state[0], positions_np, state[1],
                                  keys, temp, topk, topp)
    jax.block_until_ready(all_toks)
    print(f"  chunk donate [{i}]: {(time.perf_counter()-t0)*1e3:.1f} ms",
          flush=True)
    state = [l2, c2]

print("  -- donate + device_get pattern (engine loop shape) --", flush=True)
for i in range(4):
    t0 = time.perf_counter()
    all_toks, l2, c2 = dec_donate(params, state[0], positions_np, state[1],
                                  keys, temp, topk, topp)
    block = np.asarray(jax.device_get(all_toks))
    dt = time.perf_counter() - t0
    tps = B * K / dt
    print(f"  engine-shape chunk [{i}]: {dt*1e3:.1f} ms  (= {tps:.0f} tok/s)",
          flush=True)
    state = [l2, c2]

print("done", flush=True)
