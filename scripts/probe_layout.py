#!/usr/bin/env python
"""Round-4: measure XLA-TPU HBM padding for KV-cache layouts.

Hypothesis (PROFILE.md "open items"): the decode chunk's ~3x-over-roofline
attention cost is tile padding. XLA-TPU tiles the last TWO dims of an HBM
buffer to (16, 128) for bf16; the cache's trailing [Hkv=8, D=64] block
pads to (16, 128) -> 4x bytes. A [.., D, S] = [.., 64, 256] trailing block
is tile-exact -> 1x.

Measures real bytes via device memory_stats deltas, then times the
attention einsum in both layouts.

Run: python scripts/probe_layout.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

dev = jax.devices()[0]
print(f"device: {dev} platform={dev.platform}", flush=True)

L, B, S, H, D = 16, 128, 256, 8, 64
logical = L * B * S * H * D * 2  # bf16 bytes


def used():
    st = dev.memory_stats()
    return st.get("bytes_in_use", 0) if st else 0


def measure(shape, label):
    base = used()
    x = jax.device_put(jnp.zeros(shape, jnp.bfloat16))
    x.block_until_ready()
    got = used() - base
    print(f"  {label:28s} {str(shape):32s} {got/2**20:8.1f} MiB "
          f"({got/(np.prod(shape)*2):.2f}x logical)", flush=True)
    return x


print(f"logical cache bytes: {logical/2**20:.1f} MiB (one of K/V)", flush=True)
a = measure((L, B, S, H, D), "current [L,B,S,H,D]")
del a
b = measure((L, B, H, D, S), "proposed K [L,B,H,D,S]")
del b
c = measure((L, B, H, S, D), "alt [L,B,H,S,D]")
del c
d = measure((L, B, S, H * D), "merged [L,B,S,H*D]")
del d

# ---- attention einsum timing, both layouts --------------------------------
G = 4  # Hq // Hkv


def t(label, fn, *args):
    f = jax.jit(fn)
    out = f(*args)
    jax.block_until_ready(out)
    best = 1e9
    for _ in range(5):
        t0 = time.perf_counter()
        out = f(*args)
        # tiny reduction device_get to force sync
        float(jnp.sum(out[0] if isinstance(out, tuple) else out)
              .astype(jnp.float32))
        best = min(best, time.perf_counter() - t0)
    print(f"  {label:44s} {best*1e3:8.1f} ms", flush=True)


key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (B, 1, H, G, D), jnp.bfloat16)


def make_attn(kv_sub):
    """Score/out einsums parameterized by the per-layer K/V subscripts
    (e.g. 'bskd'); softmax/accumulate scaffolding shared."""
    def attn(q, ks, vs):
        def one(carry, kv):
            k, v = kv
            s = jnp.einsum(f"btkgd,{kv_sub}->bkgts", q, k,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            o = jnp.einsum(f"bkgts,{kv_sub}->btkgd", p, v,
                           preferred_element_type=jnp.float32)
            return carry + jnp.sum(o.astype(jnp.float32)), None

        tot, _ = jax.lax.scan(one, jnp.float32(0), (ks, vs))
        return tot

    return attn


LAYOUTS = (
    # label, full-array shape, per-layer K/V einsum subscripts
    ("current  [B,S,H,D]", (L, B, S, H, D), "bskd"),   # engine layout
    ("proposed [B,H,D,S]", (L, B, H, D, S), "bkds"),   # tile-exact
    ("batched  [B,H,S,D]", (L, B, H, S, D), "bksd"),   # (b,h) batch-leading
)
print("attention over full cache, L layers scanned, 1 decode step:",
      flush=True)
for label, shape, sub in LAYOUTS:
    ks = jax.random.normal(key, shape, jnp.bfloat16)
    vs = jax.random.normal(key, shape, jnp.bfloat16)
    t(f"{label} (1 step, all layers)", make_attn(sub), q, ks, vs)
