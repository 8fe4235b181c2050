#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that swarmdb_tpu still starts on the chip.

    python chip_smoke.py             one TPU chip: phases `paged` and `dense`
    python chip_smoke.py --chips 4   four chips:   phases `lanes` and `tp`

One process owns the chip(s). The script drives the serving path once
through the entry points a user calls (HTTP app -> runtime -> broker ->
``ServingService`` -> ``Engine`` -> reply) at the full width of
Llama-3-8B (dim 4096, 32/8 heads, head_dim 128, FFN 14336, vocab
128,256; bf16; random weights from ``--seed``). Depth is cut to what one
chip holds; only the tensor-parallel phase runs all 32 layers.

Each phase prints one JSON line (set-up facts, not performance) and
checks every greedy reply against ``llama.forward`` — plain XLA, no
kernel, no pool. Any failed phase exits non-zero. No accelerator: exit 2
and no result. The last line of standard output, on success only, is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

MODEL = "llama3-8b"
# the server's default knobs (api/server.py `_serve_knobs`)
MAX_BATCH, MAX_SEQ, CHUNK, PAGE = 8, 1024, 8, 16
NEW_TOKENS = 24
GEN = {"generation": {"temperature": 0.0, "max_new_tokens": NEW_TOKENS}}

# Logit check. Weights are random, so two correct implementations flip
# the argmax wherever the top two logits are closer than their rounding
# noise: tokens cannot be compared, logits can. For every generated
# token the reference's logit of the token the engine chose must lie
# within LOGIT_TOL of the reference's maximum at that position. Engine
# and reference both run bf16 matmuls, in different orders (Pallas
# online softmax over pages, chunked decode against a paged pool, vs one
# dense einsum forward): logits here are ~N(0, 1) with a maximum near 5
# over 128k entries, one bf16 rounding of such a value is up to 0.02,
# and the disagreement measured on a v5e over all phases stayed under
# 0.1. A kernel that mis-tiles or mis-masks moves the chosen token to a
# typical logit, 4-5 below the maximum.
LOGIT_TOL = 0.25

# Depth for one chip: the largest multiple of 8 whose weights, KV
# stores and the widest program's temporaries fit. `memory_analysis()`
# of every warm-up program compiled for a described v5e at N=16 (PR 22
# rehearsal): paged decode keeps one pool-sized temporary (0.82 GB beside
# a 0.81 GB pool); the dense engine's widest prefill (8 rows x 1024
# tokens behind a 63-page prefix gather) needs 4.36 GB of temporaries,
# 14.24 GB live in all. The reference check's [1024, 128256] float32
# logits are 0.53 GB. So: weights + KV + 4.4 GB within 95% of the
# device's limit. 16 layers: 9.08 GB of weights; 24 would need 18 GB.
TEMP_ALLOWANCE = int(4.4e9)


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


class PhaseFailed(Exception):
    pass


def require(cond: bool, why: str) -> None:
    if not cond:
        raise PhaseFailed(why)


# ------------------------------------------------------------------ sizing


def param_bytes(cfg) -> int:
    import jax

    from swarmdb_tpu.models import llama

    return tree_bytes(jax.eval_shape(lambda k: llama.init_params(cfg, k),
                                     jax.random.PRNGKey(0)))


def kv_bytes(cfg) -> int:
    """Slots plus the default prefix budget (half again), K and V, bf16 —
    the paged pool and the dense cache + side pool hold the same tokens."""
    tokens = MAX_BATCH * MAX_SEQ * 3 // 2 + PAGE
    return tokens * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2 * 2


def choose_layers(bytes_limit: int):
    from swarmdb_tpu.models.configs import get_config

    full = get_config(MODEL).n_layers
    for n in range(full - full % 8, 0, -8):
        cfg = get_config(MODEL, n_layers=n)
        need = param_bytes(cfg) + kv_bytes(cfg) + TEMP_ALLOWANCE
        if need <= 0.95 * bytes_limit:
            return cfg, need
    raise PhaseFailed(f"not even 8 layers fit in {bytes_limit} bytes")


def tree_bytes(tree) -> int:
    import jax

    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def mem_stats(dev) -> dict:
    s = dev.memory_stats() or {}
    return {k: int(s[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                   "bytes_limit") if k in s}


# ------------------------------------------------------- observing engines


class Recorder:
    """Notes what each physical engine was asked and what it answered:
    (lane, prompt token ids, generated token ids, finish reason). Wraps
    ``Engine.submit`` — below the service, the supervisor and the lane
    router, so it sees exactly the tokens the device saw."""

    def __init__(self, engines) -> None:
        self.records = []
        self._lock = threading.Lock()
        for lane, eng in enumerate(engines):
            self._wrap(lane, eng)

    def _wrap(self, lane: int, eng) -> None:
        inner = eng.submit

        def submit(req):
            rec = {"lane": lane, "prompt": list(req.prompt),
                   "resume_len": req.resume_len, "tokens": None}
            done = req.on_done

            def on_done(rid, tokens, reason):
                rec["tokens"], rec["reason"] = list(tokens), reason
                if done is not None:
                    done(rid, tokens, reason)

            req.on_done = on_done
            with self._lock:
                self.records.append(rec)
            return inner(req)

        eng.submit = submit

    def finished(self):
        with self._lock:
            return [r for r in self.records if r["tokens"] is not None]


def compiled_count(engines) -> int:
    return sum(e._compiled_count() for e in engines)


def reference_gaps(cfg, params, records):
    """For each record, max over its generated positions of (reference
    maximum logit - reference logit of the engine's token). One
    ``llama.forward`` over prompt + reply per record, fresh cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from swarmdb_tpu.models import llama

    @jax.jit
    def gaps_of(params, tokens):              # tokens [1, MAX_SEQ]
        pos = jnp.arange(MAX_SEQ, dtype=jnp.int32)[None]
        cache = llama.init_kv_cache(cfg, 1, MAX_SEQ)
        logits, _ = llama.forward(params, cfg, tokens, pos, cache)
        nxt = jnp.roll(tokens[0], -1)         # token that followed t
        chosen = jnp.take_along_axis(logits[0], nxt[:, None], axis=1)[:, 0]
        return logits[0].max(axis=-1) - chosen

    out = []
    for rec in records:
        require(rec["resume_len"] == 0, "a rolling resume reached the smoke")
        p, g = rec["prompt"], rec["tokens"]
        require(len(g) > 0, f"empty generation ({rec.get('reason')})")
        seq = (p + g)[:MAX_SEQ]
        toks = np.zeros((1, MAX_SEQ), np.int32)
        toks[0, :len(seq)] = seq
        gaps = np.asarray(gaps_of(params, toks))
        # position len(p)-1+i predicts g[i]
        span = gaps[len(p) - 1:len(p) - 1 + len(g)]
        require(np.isfinite(span).all(), "non-finite reference logits")
        out.append(float(span.max()))
    return out


# ------------------------------------------------------------------- HTTP


class HttpServer:
    """The aiohttp app on an ephemeral port, on its own loop thread."""

    def __init__(self, app) -> None:
        from aiohttp import web

        self._web, self._app = web, app
        self._loop = asyncio.new_event_loop()
        self._ready = threading.Event()
        self.port = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="chip-smoke-http")
        self._thread.start()
        if not self._ready.wait(30) or self.port is None:
            raise PhaseFailed("HTTP app did not start")

    def _run(self) -> None:
        asyncio.set_event_loop(self._loop)
        self._runner = self._web.AppRunner(self._app)
        self._loop.run_until_complete(self._runner.setup())
        site = self._web.TCPSite(self._runner, "127.0.0.1", 0)
        self._loop.run_until_complete(site.start())
        self.port = self._runner.addresses[0][1]
        self._ready.set()
        self._loop.run_forever()

    def stop(self) -> None:
        asyncio.run_coroutine_threadsafe(
            self._runner.cleanup(), self._loop).result(60)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(30)

    def post(self, path: str, body: dict, token: str = None,
             timeout: float = 600):
        """POST JSON; returns the parsed JSON body, or for an SSE reply
        the list of (event, data) pairs."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}",
            data=json.dumps(body).encode(), method="POST",
            headers={"Content-Type": "application/json"})
        if token:
            req.add_header("Authorization", f"Bearer {token}")
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            if not resp.headers.get("Content-Type", "").startswith(
                    "text/event-stream"):
                return json.loads(resp.read())
            events, event = [], None
            for raw in resp:
                line = raw.decode().rstrip("\n")
                if line.startswith("event:"):
                    event = line[6:].strip()
                elif line.startswith("data:"):
                    data = line[5:].strip()
                    # token events carry the raw text piece, others JSON
                    events.append((event, data if event == "token"
                                   else json.loads(data)))
            return events


def wait_reply(poll, msg_id: str, timeout: float = 600):
    """Poll an inbox until the reply to ``msg_id`` arrives."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        for m in poll():
            meta = m["metadata"] if isinstance(m, dict) else m.metadata
            if meta.get("reply_to") == msg_id:
                return m
    raise PhaseFailed(f"no reply to {msg_id} within {timeout:.0f}s")


def text(n: int, salt: str) -> str:
    """Deterministic filler of exactly ``n`` characters (= n byte tokens)."""
    words = (salt + " swarm agents route messages through the broker to "
             "the engine and stream replies token by token ")
    return (words * (n // len(words) + 1))[:n]


# ------------------------------------------------------------- the phases


def make_db(tmp: str):
    """The runtime with its default broker: the native C++ log engine,
    built from broker/cpp/broker.cpp, where a toolchain exists."""
    from swarmdb_tpu.core.runtime import SwarmDB

    return SwarmDB(save_dir=os.path.join(tmp, "history"))


def serve_over_http(http, service):
    """token -> register -> /admin/llm_backend -> /messages plain, SSE,
    and a second turn; returns (requests served over HTTP, tokens
    generated for the SSE stream)."""
    def token(user):
        return http.post("/auth/token", {"username": user,
                                         "password": "x"})["access_token"]

    admin, alice = token("admin"), token("alice")
    for agent in ("alice", "bob"):
        http.post("/agents/register", {"agent_id": agent}, admin)
    r = http.post("/admin/llm_backend",
                  {"agent_id": "bob", "backend_id": service.backend_id},
                  admin)
    require(r.get("status") == "assigned", f"llm_backend: {r}")

    def inbox():
        return http.post("/agents/receive", {"timeout": 1.0}, alice)

    # 1. plain, short prompt (a small rung)
    m1 = http.post("/messages", {"receiver_id": "bob", "metadata": GEN,
                                 "content": text(40, "one")}, alice)
    wait_reply(inbox, m1["id"])
    # 2. SSE stream, long prompt: its first wave is a rung >= 256
    events = http.post("/messages", {
        "receiver_id": "bob", "metadata": GEN, "stream": True,
        "content": text(420, "two")}, alice)
    kinds = [e for e, _ in events]
    require("error" not in kinds, f"SSE error event: {events}")
    require(kinds[0] == "message" and "reply" in kinds
            and kinds[-1] == "done",
            f"SSE stream incomplete: {kinds[:4]}..{kinds[-3:]}")
    # token events carry decoded text, and random weights rarely emit a
    # byte token: count what the streamed reply says was generated
    n_sse = dict(events)["reply"]["metadata"]["completion_tokens"]
    # 3. next turn of the same conversation: its history is cached
    m3 = http.post("/messages", {"receiver_id": "bob", "metadata": GEN,
                                 "content": text(60, "three")}, alice)
    wait_reply(inbox, m3["id"])
    return 3, n_sse


def serve_over_broker(db, backend_agent: str, users, turns: int = 1) -> int:
    """send_message / receive_messages rounds, straight on the runtime."""
    n = 0
    for user in users:
        db.register_agent(user)
        for turn in range(turns):
            mid = db.send_message(user, backend_agent,
                                  text(48 + 150 * turn, f"{user} {turn}"),
                                  metadata=dict(GEN))
            wait_reply(lambda: db.receive_messages(user, timeout=1.0), mid)
            n += 1
    return n


def run_service_phase(name, cfg, db, engine, tokenizer, lanes, *, t0, dev,
                      http, warmup, users, turns, extra=None, also=None):
    """Wrap ``engine`` in a ServingService the way ``from_model_name``
    does, serve, check, tear down. ``lanes``: the physical engines;
    ``also()``: more traffic while the engines still run."""
    import jax

    from swarmdb_tpu.backend.service import ServingService
    from swarmdb_tpu.obs.profiler import profiler

    service = ServingService(db, engine, tokenizer, backend_id="tpu-0")
    engine.flight.meta.update({"backend_id": "tpu-0", "model": cfg.name})
    recorder = Recorder(lanes)
    log(f"{name}: starting service (warmup={warmup})")
    t_w = time.time()
    service.start(warmup=warmup)
    warm_s = time.time() - t_w
    compiled0 = compiled_count(lanes)
    log(f"{name}: warm in {warm_s:.0f}s, {compiled0} programs; serving")
    server = None
    try:
        n_http = n_sse = 0
        if http:
            from swarmdb_tpu.api.app import ApiConfig, create_app

            server = HttpServer(create_app(db, ApiConfig(), serving=service))
            n_http, n_sse = serve_over_http(server, service)
        db.register_agent("bob")
        db.assign_llm_backend("bob", service.backend_id)
        n_broker = serve_over_broker(db, "bob", users, turns)
        if also is not None:
            also()
        compiled1 = compiled_count(lanes)
        records = recorder.finished()
        require(len(records) >= n_http + n_broker,
                f"{len(records)} generations for {n_http + n_broker} requests")
        for rec in records:
            require(rec["reason"] in ("length", "eos"),
                    f"finish reason {rec['reason']!r}")
        counters = db.metrics.snapshot()["counters"]
        # the profiler is one per process: read its ragged waves only in
        # a phase whose engines pack ragged waves
        waves = sorted(row["width"] for row in profiler().dispatch_profile()
                       if row["kind"] == "ragged"
                       and lanes[0]._ragged_active())
    finally:
        service.stop()
        if server is not None:
            server.stop()       # the app's shutdown hook closes the db
        db.close()
    if warmup:
        require(compiled1 == compiled0,
                f"{compiled1 - compiled0} compiles after warm-up")
    log(f"{name}: checking {len(records)} replies against llama.forward")
    gaps = reference_gaps(cfg, lanes[0].params, records)
    require(max(gaps) <= LOGIT_TOL,
            f"engine token up to {max(gaps):.3f} below the reference "
            f"maximum (tolerance {LOGIT_TOL})")
    out = {
        "phase": name, "ok": True, "platform": dev.platform,
        "device_kind": dev.device_kind, "count": len(jax.devices()),
        "model": cfg.name, "n_layers": cfg.n_layers,
        "weight_bytes": tree_bytes(lanes[0].params),
        "kv_bytes": tree_bytes(lanes[0].cache),
        "setup_s": round(time.time() - t0, 1),
        "warmup_compile_s": round(warm_s, 1), "warmup": warmup,
        "programs_after_warmup": compiled0,
        "compiles_after_warmup": compiled1 - compiled0,
        "requests_http": n_http, "sse_tokens": n_sse,
        "requests_broker": n_broker,
        "tokens_served": sum(len(r["tokens"]) for r in records),
        "prompt_tokens": [len(r["prompt"]) for r in records],
        "ragged_wave_widths": waves,
        "prefix_reused_tokens": int(counters.get("prefix_reused_tokens", 0)),
        "logit_gap_max": round(max(gaps), 4), "logit_tol": LOGIT_TOL,
        "broker": type(db.broker).__name__,
    }
    out.update(extra or {})
    out.update(mem_stats(dev))
    return out, records


def phase_paged(cfg, seed, tmp, dev, cache_dir):
    from swarmdb_tpu.backend.service import build_backend_engine
    from swarmdb_tpu.ops.layers import (decode_kernel_choice,
                                        prefill_kernel_choice)

    kernels = {"decode": decode_kernel_choice(MAX_SEQ),
               "prefill": prefill_kernel_choice()}
    require(kernels == {"decode": "pallas", "prefill": "pallas-ragged"},
            f"kernel choice is not the TPU default: {kernels}")
    t0 = time.time()
    db = make_db(tmp)
    engine, tok = build_backend_engine(
        cfg, max_batch=MAX_BATCH, max_seq=MAX_SEQ, seed=seed,
        decode_chunk=CHUNK, paged=True, page_size=PAGE, metrics=db.metrics,
        flight_dir=os.path.join(tmp, "flight"))
    out, _ = run_service_phase(
        "paged", cfg, db, engine, tok, [engine], t0=t0, dev=dev, http=True,
        warmup=True, users=["carol"], turns=1,
        extra={"kernels": kernels, "cache_dir": cache_dir})
    require(out["ragged_wave_widths"] and out["ragged_wave_widths"][-1] >= 256,
            f"no ragged rung >= 256 ran: {out['ragged_wave_widths']}")
    require(out["prefix_reused_tokens"] > 0, "no prefix-cache hit")
    require(out["sse_tokens"] > 0, "no SSE stream served")
    return out


def phase_dense(cfg, seed, tmp, dev, cache_dir):
    """What SERVE_MODEL alone gives: the dense slot cache (SWARMDB_PAGED
    unset), XLA attention unless SWARMDB_PALLAS=1 opts into a kernel."""
    from swarmdb_tpu.backend.service import build_backend_engine
    from swarmdb_tpu.ops import layers

    t0 = time.time()
    db = make_db(tmp)
    engine, tok = build_backend_engine(
        cfg, max_batch=MAX_BATCH, max_seq=MAX_SEQ, seed=seed,
        decode_chunk=CHUNK, paged=False, page_size=PAGE, metrics=db.metrics,
        flight_dir=os.path.join(tmp, "flight"))
    kernels = {"decode": ("pallas-dense" if layers._pallas_decode_enabled()
                          else "xla-einsum"), "prefill": "xla-einsum"}
    out, _ = run_service_phase(
        "dense", cfg, db, engine, tok, [engine], t0=t0, dev=dev, http=False,
        warmup=True, users=["dave", "erin"], turns=2,
        extra={"kernels": kernels, "cache_dir": cache_dir})
    require(out["prefix_reused_tokens"] > 0, "no prefix-cache hit")
    return out


def phase_lanes(cfg, seed, tmp, dev, cache_dir):
    """Four one-chip replicas behind the load balancer (ShardLaneGroup)."""
    import jax

    from swarmdb_tpu.backend.sampling import SamplingParams
    from swarmdb_tpu.backend.tokenizer import default_tokenizer
    from swarmdb_tpu.parallel.mesh import make_mesh
    from swarmdb_tpu.parallel.serving import build_serving_engine
    from swarmdb_tpu.utils.hashing import stable_partition

    t0 = time.time()
    db = make_db(tmp)
    mesh = make_mesh(4, data=4, model=1, expert=1)
    group, _info = build_serving_engine(
        cfg, mesh, paged=True, max_batch=4 * MAX_BATCH, max_seq=MAX_SEQ,
        seed=seed, page_size=PAGE, decode_chunk=CHUNK, metrics=db.metrics,
        flight_dir=os.path.join(tmp, "flight"))
    devices = list(mesh.devices.flat)
    require(len(group.lanes) == 4, f"{len(group.lanes)} lanes")
    for d, lane in enumerate(group.lanes):
        for leaf in jax.tree.leaves((lane.params, lane.cache)):
            # committed, or jit computes the lane on the default device
            require(leaf.committed and leaf.devices() == {devices[d]},
                    f"lane {d} holds an array on {leaf.devices()} "
                    f"(committed: {leaf.committed})")
    # the same greedy prompt on every lane: same weights, same tokens
    prompt = default_tokenizer(cfg.vocab_size).encode(text(200, "same"))
    replies = []

    def same_prompt_everywhere():
        for lane in group.lanes:
            replies.append(lane.generate_sync(
                prompt, SamplingParams(max_new_tokens=NEW_TOKENS),
                timeout=600)[0])

    # a conversation is pinned to the lane its (sender, receiver) pair
    # hashes to (backend/service.py): pick two users for each lane
    users = []
    for lane in range(4):
        users += [u for u in (f"user{i}" for i in range(64))
                  if stable_partition("|".join(sorted((u, "bob"))), 4)
                  == lane][:2]
    out, records = run_service_phase(
        "lanes", cfg, db, group, default_tokenizer(cfg.vocab_size),
        list(group.lanes), t0=t0, dev=dev, http=False, warmup=True,
        users=users, turns=1,
        also=same_prompt_everywhere,
        extra={"cache_dir": cache_dir,
               "lane_devices": [str(d) for d in devices]})
    served = sorted({r["lane"] for r in records[:8]})
    out["lanes_served_messages"] = served
    require(served == [0, 1, 2, 3],
            f"lanes that served a message: {served}")
    out["same_prompt_tokens_equal"] = all(r == replies[0] for r in replies)
    require(out["same_prompt_tokens_equal"],
            f"lanes disagree on one greedy prompt: {replies}")
    return out


def phase_tp(cfg, seed, tmp, dev, cache_dir):
    """Tensor parallel over four chips: the only layout that holds all 32
    layers (4 GB of weights a chip). Dense cache — TP + paged is not
    built (parallel/serving.py). No warm-up: programs compile on first
    traffic, so only what the requests reach is compiled."""
    import jax

    from swarmdb_tpu.backend.tokenizer import default_tokenizer
    from swarmdb_tpu.parallel.mesh import make_mesh
    from swarmdb_tpu.parallel.serving import build_serving_engine

    t0 = time.time()
    db = make_db(tmp)
    mesh = make_mesh(4, data=1, model=4, expert=1)
    engine, sm = build_serving_engine(
        cfg, mesh, paged=False, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
        seed=seed, decode_chunk=CHUNK, metrics=db.metrics,
        flight_dir=os.path.join(tmp, "flight"))
    wq = sm.params["layers"]["wq"]
    require(len(wq.devices()) == 4 and
            wq.addressable_shards[0].data.shape[-1] * 4 == wq.shape[-1],
            f"wq is not split four ways: {wq.sharding}")
    # what the compiler plans per device for the greedy decode program
    # (the rehearsal's reading, taken here on the attached chips; the
    # executable lands in the persistent cache and the engine reuses it)
    fn, specs = engine.warmup_call_plan()[2]
    ma = fn.lower(*specs).compile().memory_analysis()
    planned = {"arguments": ma.argument_size_in_bytes,
               "outputs": ma.output_size_in_bytes,
               "aliased": ma.alias_size_in_bytes,
               "temporaries": ma.temp_size_in_bytes}
    out, _ = run_service_phase(
        "tp", cfg, db, engine, default_tokenizer(cfg.vocab_size), [engine],
        t0=t0, dev=dev, http=False, warmup=False,
        users=["frank", "grace"], turns=2,
        extra={"cache_dir": cache_dir, "mesh": dict(mesh.shape),
               "decode_memory_analysis_per_device": planned,
               "per_device": [mem_stats(d) for d in jax.devices()]})
    return out


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform!r}); "
              "this script does not run on anything else", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found {len(devs)}",
              file=sys.stderr)
        return 2

    from swarmdb_tpu.models.configs import get_config
    from swarmdb_tpu.utils.xla_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    limit = mem_stats(dev).get("bytes_limit", 16 * 2 ** 30)
    cfg, need = choose_layers(limit)
    log(f"{dev.device_kind} x{len(devs)}, {limit} bytes a chip; "
        f"{MODEL} at {cfg.n_layers} layers needs {need}; cache {cache_dir}")
    if args.chips == 4:
        phases = [(phase_lanes, cfg), (phase_tp, get_config(MODEL))]
    else:
        phases = [(phase_paged, cfg), (phase_dense, cfg)]

    failed = False
    for phase, pcfg in phases:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            try:
                out = phase(pcfg, args.seed, tmp, dev, cache_dir)
            except Exception as exc:  # any failure fails the run
                import traceback

                traceback.print_exc()
                out = {"phase": phase.__name__[6:], "ok": False,
                       "error": f"{type(exc).__name__}: {exc}"[:2000]}
                failed = True
        print(json.dumps(out), flush=True)
        if failed:
            break
        # Release the phase's arrays before the next engine is built.
        # Dropping references is not enough: the resident decode
        # program's executable holds its host callback (a bound method
        # of the engine) and jax's in-memory executable cache holds the
        # executable; aiohttp memoises its middleware chain per app in a
        # module-level lru_cache, which keeps the app, the service and
        # the engine. Nothing of a finished phase is needed again, so
        # whatever sizeable array is still alive is deleted by hand
        # (not the small ones: jax's own runtime tokens are among them).
        # The persistent cache on disk is untouched.
        jax.clear_caches()
        gc.collect()
        for leftover in jax.live_arrays():
            if leftover.nbytes >= 2 ** 20:
                leftover.delete()
        left = mem_stats(dev).get("bytes_in_use", 0)
        log(f"released; {left} bytes still in use")
        if left > 0.05 * limit:
            print(f"chip_smoke: {left} bytes still held after phase "
                  f"{out['phase']}", file=sys.stderr)
            return 1
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
