#!/usr/bin/env python
"""Round-6: decompose the tooluse gap (VERDICT r5 #3 — 8.0 msgs/sec vs
serve's 44.8 on the same CPU, prefix hit 6.7% vs 26%).

Three measurements, mirroring the PROFILE r4 serve decomposition:

1. MoE-dispatch floor: the Mixtral block's einsum (capacity one-hot)
   dispatch vs the scatter fast path at the tooluse prefill geometry
   [Bp, bucket] — per-block and full-forward wall time, plus the dense
   (tiny-debug) forward as the non-MoE reference.
2. Served-workload phase breakdown: the bench_tooluse traffic shape
   through a real ServingService, reporting the phase_us_* family
   (queue_wait / prefill / decode / host_sync), prompt
   padding share (flight counter), and prefix hit rate with the
   sink-anchored window on and off (SWARMDB_ANCHOR_HEAD).
3. Prompt-render cost: build_prompt volume rendered vs retained at the
   adaptive history cap (_history_limit_for) vs the flat 64 default.

Run: JAX_PLATFORMS=cpu python scripts/profile_tooluse.py [seconds]
Emits one JSON line per section; paste into PROFILE.md.
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

SECONDS = float(sys.argv[1]) if len(sys.argv) > 1 else 10.0


def section_moe_floor() -> dict:
    """Per-block + full-forward cost of both MoE dispatch forms at the
    tooluse prefill geometry, vs the dense reference."""
    from swarmdb_tpu.models import llama, mixtral
    from swarmdb_tpu.models.configs import get_config

    Bp, T = 16, 256
    out = {"section": "moe_floor", "geometry": [Bp, T]}
    cfg = get_config("tiny-moe")
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    lp = params["layers"]
    x = jax.random.normal(jax.random.PRNGKey(1), (Bp, T, cfg.dim),
                          jnp_dtype := np.float32)
    del jnp_dtype

    def timed(fn, *args, reps=10):
        o = fn(*args)
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        for _ in range(reps):
            o = fn(*args)
        jax.block_until_ready(o)
        return (time.perf_counter() - t0) / reps

    for mode in ("einsum", "scatter"):
        # swarmlint: disable=SWL201 -- one jit per A/B dispatch mode (2 total) by design
        blk = jax.jit(lambda x, m=mode: mixtral.moe_block(
            x, lp["router"][0], lp["w_gate"][0], lp["w_up"][0],
            lp["w_down"][0], cfg.experts_per_token, dispatch=m)[0])
        out[f"moe_block_{mode}_ms"] = round(timed(blk, x) * 1e3, 1)

    toks = np.zeros((Bp, T), np.int32)
    pos = np.broadcast_to(np.arange(T, dtype=np.int32)[None], (Bp, T))
    for mode in ("einsum", "scatter"):
        # swarmlint: disable=SWL201 -- one jit per A/B dispatch mode (2 total) by design
        fwd = jax.jit(lambda p, t, po, c, m=mode: mixtral.forward(
            p, cfg, t, po, c, moe_dispatch=m)[0])
        cache = mixtral.init_kv_cache(cfg, Bp, T)
        dt = timed(fwd, params, toks, pos, cache)
        out[f"forward_{mode}_ms"] = round(dt * 1e3, 1)
        out[f"forward_{mode}_tok_per_s"] = round(Bp * T / dt)
    dcfg = get_config("tiny-debug")
    dparams = llama.init_params(dcfg, jax.random.PRNGKey(0))
    dfwd = jax.jit(lambda p, t, po, c: llama.forward(p, dcfg, t, po, c)[0])
    dcache = llama.init_kv_cache(dcfg, Bp, T)
    dt = timed(dfwd, dparams, toks, pos, dcache)
    out["dense_forward_ms"] = round(dt * 1e3, 1)
    out["dense_forward_tok_per_s"] = round(Bp * T / dt)
    out["einsum_vs_scatter_x"] = round(
        out["forward_einsum_ms"] / out["forward_scatter_ms"], 1)
    return out


def section_served(anchor_head: str) -> dict:
    """bench_tooluse's traffic shape through a real stack; phase family +
    padding + hit rate under the given SWARMDB_ANCHOR_HEAD."""
    os.environ["SWARMDB_ANCHOR_HEAD"] = anchor_head
    from swarmdb_tpu.backend.service import ServingService
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.core.messages import MessageType
    from swarmdb_tpu.core.runtime import SwarmDB

    n_users, max_batch, new_tokens = 16, 16, 16
    phases = ("queue_wait", "prefill", "decode", "host_sync")
    with tempfile.TemporaryDirectory() as tmp:
        db = SwarmDB(broker=LocalBroker(), save_dir=tmp,
                     autosave_interval=1e9, max_messages_per_file=10**9)
        svc = ServingService.from_model_name(
            db, "tiny-moe", backend_id="tpu-0", max_batch=max_batch,
            max_seq=256, decode_chunk=16, prefill_batch=16)
        users = [f"tool_user_{i}" for i in range(n_users)]
        for a in ("assistant_0", "assistant_1"):
            db.register_agent(a)
            db.assign_llm_backend(a, "tpu-0")
        for u in users:
            db.register_agent(u)
        db.set_llm_load_balancing(True)
        svc.start(warmup=False)
        completed = db.metrics.counters["completed_messages"]
        try:
            sent = 0

            def pump(stop_at):
                nonlocal sent
                while time.time() < stop_at:
                    if sent - completed.value < max_batch * 2:
                        db.send_message(
                            users[sent % n_users],
                            f"assistant_{sent % 2}",
                            {"name": "lookup_weather",
                             "arguments": {"city": f"city_{sent % 7}",
                                           "unit": "C"}},
                            message_type=MessageType.FUNCTION_CALL,
                            metadata={"generation": {
                                "max_new_tokens": new_tokens,
                                "temperature": 0.0}})
                        sent += 1
                    else:
                        time.sleep(0.002)

            while completed.value < 8 and time.time() < time.time() + 60:
                pump(time.time() + 1.0)
            ph0 = {p: db.metrics.counters[f"phase_us_{p}"].value
                   for p in phases}
            c0 = completed.value
            flight0 = svc.engine.metrics.counters[
                "prefill_padding_tokens"].value
            pt0 = db.metrics.counters["prompt_tokens"].value
            hit0 = dict(svc.engine._prefix.stats()) if svc.engine._prefix \
                else {"hit_tokens": 0, "miss_tokens": 0}
            t0 = time.time()
            pump(t0 + SECONDS)
            while (completed.value < sent
                   and time.time() - t0 < SECONDS + 5.0):
                time.sleep(0.05)
            dt = time.time() - t0
            hs = svc.engine._prefix.stats() if svc.engine._prefix else hit0
            hit = hs["hit_tokens"] - hit0["hit_tokens"]
            miss = hs["miss_tokens"] - hit0["miss_tokens"]
            pad = (svc.engine.metrics.counters[
                "prefill_padding_tokens"].value - flight0)
            pt = db.metrics.counters["prompt_tokens"].value - pt0
            out = {
                "section": "served",
                "anchor_head_pages": anchor_head,
                "msgs_per_sec": round((completed.value - c0) / dt, 2),
                "window_s": round(dt, 1),
                "phase_seconds": {
                    p: round((db.metrics.counters[f"phase_us_{p}"].value
                              - ph0[p]) / 1e6, 2) for p in phases},
                "prefix_hit_rate": (round(hit / (hit + miss), 4)
                                    if hit + miss else None),
                "prefill_padding_share": (round(pad / (pad + pt), 4)
                                          if pad + pt else None),
                "anchored_heads": db.metrics.counters[
                    "window_heads_anchored"].value,
            }
        finally:
            svc.stop()
            db.close()
    return out


def section_render_cost() -> dict:
    """Host-side prompt-render volume: flat 64-message history vs the
    adaptive cap at S=256 (the retained budget is ~239 tokens)."""
    from swarmdb_tpu.backend.service import (_history_limit_for,
                                             build_prompt)
    from swarmdb_tpu.backend.tokenizer import ByteTokenizer
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.core.runtime import SwarmDB

    tok = ByteTokenizer(vocab_size=512)
    out = {"section": "render_cost", "adaptive_limit_s256":
           _history_limit_for(256)}
    with tempfile.TemporaryDirectory() as tmp:
        db = SwarmDB(broker=LocalBroker(), save_dir=tmp,
                     autosave_interval=1e9)
        db.register_agent("u")
        db.register_agent("a")
        mid = None
        for i in range(80):
            mid = db.send_message(
                "u", "a", json.dumps({"name": "lookup_weather",
                                      "arguments": {"city": f"c{i % 7}"}}))
        msg = db.get_message(mid)
        for label, limit in (("flat64", 64),
                             ("adaptive", _history_limit_for(256))):
            t0 = time.perf_counter()
            reps = 200
            for _ in range(reps):
                toks = build_prompt(db, msg, tok, history_limit=limit)
            out[f"render_{label}_tokens"] = len(toks)
            out[f"render_{label}_us"] = round(
                (time.perf_counter() - t0) / reps * 1e6)
        db.close()
    return out


def main() -> None:
    print(json.dumps(section_moe_floor()), flush=True)
    print(json.dumps(section_render_cost()), flush=True)
    for anchor in ("0", "4"):
        print(json.dumps(section_served(anchor)), flush=True)


if __name__ == "__main__":
    main()
