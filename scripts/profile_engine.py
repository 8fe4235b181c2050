#!/usr/bin/env python
"""Round-4: end-to-end Engine timing with per-phase instrumentation.

Monkeypatches Engine._admit / _prefill_batch / _process_block with wall
timers to find where the 6.6 s/chunk of BENCH_r03 goes.

Run (in the one process that owns the chip): python scripts/profile_engine.py
"""

import time

import numpy as np

from swarmdb_tpu.backend.engine import Engine, GenRequest
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.models import llama
from swarmdb_tpu.models.configs import get_config

import jax
import sys

from swarmdb_tpu.utils.xla_cache import enable_compile_cache

enable_compile_cache()

model = "llama-1b-bench"
B = int(sys.argv[1]) if len(sys.argv) > 1 else 32
S, K = 256, 16
cfg = get_config(model)
params = llama.init_params(cfg, jax.random.PRNGKey(0))
jax.block_until_ready(params)

fwd = lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c)
init_cache = lambda b, s: llama.init_kv_cache(cfg, b, s)
chunked_fns = (
    lambda p, t, pos, c, hkv, s: llama.forward_chunked(p, cfg, t, pos, c, hkv, s),
    lambda b, k: llama.init_chunk_kv(cfg, b, k),
    llama.merge_chunk,
)

# pipeline_depth=1: with dispatch-ahead (the serving default) the
# per-phase timers stop decomposing wall time — _process_block would
# measure overlap-hidden waits, not decode cost
engine = Engine(fwd, init_cache, params, max_batch=B, max_seq=S,
                decode_chunk=K, eos_id=-1, chunked_fns=chunked_fns,
                pipeline_depth=1)

times = {"admit": 0.0, "prefill": 0.0, "decode": 0.0,
         "admit_n": 0, "prefill_n": 0, "decode_n": 0}

for name in ("_admit", "_prefill_batch", "_process_block"):
    orig = getattr(engine, name)
    key = {"_admit": "admit", "_prefill_batch": "prefill",
           "_process_block": "decode"}[name]

    def wrap(orig=orig, key=key):
        def inner(*a, **kw):
            t0 = time.perf_counter()
            out = orig(*a, **kw)
            times[key] += time.perf_counter() - t0
            times[key + "_n"] += 1
            return out
        return inner

    setattr(engine, name, wrap())

engine.start()

# ~45-token prompts like the serve bench's byte-tokenized chat prompt
rng = np.random.default_rng(0)
prompt = rng.integers(1, cfg.vocab_size, size=45).tolist()
sampling = SamplingParams(max_new_tokens=16, temperature=0.0)

N = int(sys.argv[2]) if len(sys.argv) > 2 else 256
done = []
import threading
ev = threading.Event()

def on_done(rid, toks, reason):
    done.append((time.time(), len(toks)))
    if len(done) >= N:
        ev.set()

print("engine.warmup() (compiles all variants)...", flush=True)
t0 = time.time()
engine.warmup()
print(f"warmup done in {time.time()-t0:.1f}s", flush=True)
for k in times:
    times[k] = 0 if k.endswith("_n") else 0.0

t0 = time.time()
for i in range(N):
    engine.submit(GenRequest(prompt=list(prompt), sampling=sampling,
                             on_done=on_done))
ev.wait(timeout=600)
elapsed = time.time() - t0
n = len(done)
toks = n * 16
print(f"\n== {n} requests, {toks} tokens in {elapsed:.2f}s "
      f"=> {n/elapsed:.1f} req/s, {toks/elapsed:.0f} tok/s", flush=True)
print(f"admit:   {times['admit']:.2f}s over {times['admit_n']} calls "
      f"({1e3*times['admit']/max(1,times['admit_n']):.1f} ms avg)")
print(f"  prefill: {times['prefill']:.2f}s over {times['prefill_n']} calls "
      f"({1e3*times['prefill']/max(1,times['prefill_n']):.1f} ms avg)")
print(f"decode:  {times['decode']:.2f}s over {times['decode_n']} calls "
      f"({1e3*times['decode']/max(1,times['decode_n']):.1f} ms avg)")
other = elapsed - times["admit"] - times["decode"]
print(f"other (loop/host): {other:.2f}s")
engine.stop()
