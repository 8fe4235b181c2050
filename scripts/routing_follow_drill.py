#!/usr/bin/env python3
"""Routing-follow drill: is the served path's reported routing enough for a
float32 reference to follow it?

    python3 scripts/routing_follow_drill.py --spec tests/benchmark/tiny/spec.json \\
        --workload tiny-moe.chat --platform cpu --seconds 3 --seeds 77,58

A configuration that routes cannot be held to a reference that routes by
its own float32 stream: at a near tie a correct bf16 program chooses
another expert and a logit jumps by 0.1-2 with nothing wrong (PERF.md
section 6, PR 29). What did separate a sound run from a fault there was a
reference FORCED to the program's own choices. This drill proves that
premise through the engine, before any benchmark code rests on it.

For each seed it builds the cell's stack with ``benchmark/harness/stack.
Stack`` (imported, not edited), sends the cell's traffic for ``--seconds``,
keeps each finished request's ``GenRequest.routing`` (a wrapper round
``on_done`` that closes over the request, as the harness's ``Recorder``
is), samples records as ``check.sample`` does, and compares each with the
FOLLOWER below: the sparse-expert decoder in plain ``jax.numpy``, float32
under ``default_matmul_precision("highest")``, importing nothing from
``swarmdb_tpu/models`` or ``ops``, that takes the record's routing in
place of its own top-k (gates from its own float32 router logits at the
followed experts, dropped choices left out). A record reads, exactly as
``check.logit_gaps`` does, the largest (reference maximum - reference
logit of the engine's token) over its generated positions: once forced,
once unforced.

One JSON line a seed, then a summary. Exit 0 when every sampled record of
every seed reads at most ``check.LOGIT_TOL`` forced on every position; with
``--break-sampler`` (PR 29's control: every decode program takes the
second-best token) exit 0 only when every seed FAILS forced. Beside the
gaps a line holds the window's ``out_tokens_per_s`` and ``tpot_p90_ms`` and
the program's routing counters, so the same run says what the emission
costs and how much the served path drops. On a program that reports no
routing (a parent commit) the forced reading is left out and the rest
stands. An engine cannot be freed in its process (ROADMAP Design 12): at a
size where two stacks do not fit the device together, give each seed a
process of its own (one ``--seeds`` value a call).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# PR 29's twelve seeds of ``tiny-moe.chat`` (PERF.md section 6): the rule
# of PR 27 reads over the tolerance on five of them with nothing wrong
PR29_SEEDS = (2147483659, 3000000019, 1000003, 77, 424243, 2900000001,
              2900000002, 31337, 1234567891, 99991, 808080808, 58)
DRAIN_S = 60.0
CHECK_SAMPLE = 4
Q_BLOCK = 256


# ------------------------------------------------------------- the follower


def follower_dims(cfg_file):
    """What the follower needs beside the weights, from the keys of the
    published ``config.json`` in the configuration file."""
    return dict(n_heads=cfg_file["num_attention_heads"],
                n_kv_heads=cfg_file["num_key_value_heads"],
                eps=float(cfg_file["rms_norm_eps"]),
                theta=float(cfg_file["rope_theta"]),
                top_k=cfg_file["num_experts_per_tok"])


def _follower():
    """The follower's two jitted pieces, made once jax is imported."""
    import jax
    import jax.numpy as jnp

    def rmsnorm(x, w, eps):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                 + eps) * w.astype(jnp.float32)

    def rope(x, pos, theta):
        hd = x.shape[-1]
        inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                               axis=-1)

    @functools.partial(jax.jit, static_argnames=(
        "i", "n_heads", "n_kv_heads", "eps", "theta", "top_k", "follow"))
    def layer(x, layers, routing, *, i, n_heads, n_kv_heads, eps, theta,
              top_k, follow):
        """Layer ``i`` of the stacked weights over one whole sequence;
        x [T, D] float32, T a multiple of Q_BLOCK. ``routing`` [T, k] is
        the record's row of this layer (the expert, or ~expert where the
        program dropped the choice); read only when ``follow``."""
        with jax.default_matmul_precision("highest"):
            f32 = lambda a: a.astype(jnp.float32)
            w = lambda name: layers[name][i]
            T, D = x.shape
            hd = D // n_heads
            g = n_heads // n_kv_heads
            pos = jnp.arange(T)
            h = rmsnorm(x, w("attn_norm"), eps)
            q = rope((h @ f32(w("wq"))).reshape(T, n_heads, hd), pos, theta)
            k = rope((h @ f32(w("wk"))).reshape(T, n_kv_heads, hd), pos,
                     theta)
            v = (h @ f32(w("wv"))).reshape(T, n_kv_heads, hd)

            def attend(args):
                qb, pb = args
                s = jnp.einsum("qkgd,skd->kgqs", qb, k) / jnp.sqrt(
                    jnp.float32(hd))
                s = jnp.where(
                    pos[None, None, None, :] <= pb[None, None, :, None],
                    s, -jnp.inf)
                return jnp.einsum("kgqs,skd->qkgd",
                                  jax.nn.softmax(s, axis=-1), v)

            nb = T // Q_BLOCK
            att = jax.lax.map(attend, (
                q.reshape(nb, Q_BLOCK, n_kv_heads, g, hd),
                pos.reshape(nb, Q_BLOCK)))
            x = x + att.reshape(T, n_heads * hd) @ f32(w("wo"))

            h2 = rmsnorm(x, w("mlp_norm"), eps)
            r = h2 @ f32(w("router"))                           # [T, E]
            n_experts = r.shape[-1]
            if follow:
                # the program's choices; its own float32 logits there
                idx = (routing ^ (routing >> 15)).astype(jnp.int32)
                kept = (routing >= 0).astype(jnp.float32)
            else:
                idx = jax.lax.top_k(r, top_k)[1]
                kept = jnp.ones(idx.shape, jnp.float32)
            gates = jax.nn.softmax(jnp.take_along_axis(r, idx, axis=-1),
                                   axis=-1) * kept              # [T, k]
            gate = jnp.sum(jax.nn.one_hot(idx, n_experts)
                           * gates[..., None], axis=1)          # [T, E]

            # every expert for every token, one expert at a time, the
            # unchosen weighted 0: plain, and one float32 copy of one
            # expert's weights at a time beside the program's own
            def expert(e, acc):
                wg, wu, wd = (f32(layers[n][i, e])
                              for n in ("w_gate", "w_up", "w_down"))
                y = (jax.nn.silu(h2 @ wg) * (h2 @ wu)) @ wd
                return acc + gate[:, e][:, None] * y

            return x + jax.lax.fori_loop(0, n_experts, expert,
                                         jnp.zeros_like(x))

    @functools.partial(jax.jit, static_argnames=("eps",))
    def head(x, at, final_norm, lm_head, *, eps):
        with jax.default_matmul_precision("highest"):
            return rmsnorm(x[at], final_norm, eps) @ lm_head.astype(
                jnp.float32)

    return layer, head


def follower_logits(pieces, params, dims, tokens, at, routing=None):
    """Float32 logits [len(at), V] of one sequence at positions ``at``;
    ``routing`` [T, L_routed, k], where given, is followed in place of the
    follower's own top-k."""
    import jax.numpy as jnp

    layer, head = pieces
    x = params["embed"][tokens].astype(jnp.float32)
    n_layers = params["layers"]["wq"].shape[0]
    for i in range(n_layers):
        x = layer(x, params["layers"],
                  routing[:, i] if routing is not None
                  else jnp.zeros((x.shape[0], dims["top_k"]), jnp.int16),
                  i=i, follow=routing is not None, **dims)
    lm_head = params.get("lm_head")
    if lm_head is None:
        lm_head = params["embed"].T
    return head(x, at, params["final_norm"], lm_head, eps=dims["eps"])


def record_gaps(pieces, params, dims, rec, routing, max_at):
    """The gap of every generated position of one record, as
    ``check.logit_gaps`` reckons it; followed where ``routing`` is given."""
    import jax.numpy as jnp
    import numpy as np

    p, g = rec["prompt"], rec["tokens"][:max_at]
    seq = p + g
    T = -(-len(seq) // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((T,), np.int32)
    toks[:len(seq)] = seq
    at = np.zeros((max_at,), np.int32)
    at[:len(g)] = np.arange(len(p) - 1, len(p) - 1 + len(g))
    rows = None
    if routing is not None:
        # position len(p) - 1 + i predicts g[i]: every position up to the
        # last but one of prompt + generated went through the stack
        need = len(seq) - 1
        if len(routing) < need:
            raise ValueError(f"{len(routing)} routing rows for a sequence "
                             f"that needs {need}")
        rows = np.zeros((T,) + routing.shape[1:], np.int16)
        rows[:need] = routing[:need]
        rows = jnp.asarray(rows)
    logits = np.asarray(follower_logits(
        pieces, params, dims, jnp.asarray(toks), jnp.asarray(at), rows)
    )[:len(g)]
    if not np.isfinite(logits).all():
        raise ValueError("non-finite follower logits")
    return logits.max(axis=-1) - logits[np.arange(len(g)), np.asarray(g)]


# ------------------------------------------------------------------ the run


class RoutingTap:
    """Per message id, the routing its request held when ``on_done``
    fired. Wraps ``Engine.submit`` outside the harness's ``Recorder``."""

    def __init__(self, engines) -> None:
        self.records = {}
        for eng in engines:
            self._wrap(eng)

    def _wrap(self, eng) -> None:
        inner = eng.submit

        def submit(req):
            mid = req.metadata.get("message_id")
            if mid is not None and not req.metadata.get("_drill_wrapped"):
                req.metadata["_drill_wrapped"] = True
                done = req.on_done

                def on_done(rid, tokens, reason):
                    self.records[mid] = {
                        "routing": getattr(req, "routing", None),
                        "complete": getattr(req, "routing_complete", None)}
                    if done is not None:
                        done(rid, tokens, reason)

                req.on_done = on_done
            return inner(req)

        eng.submit = submit


def run_seed(cell, seed: int, seconds: float, pieces, rate=None) -> dict:
    """One stack, one window of the cell's traffic, the sampled records
    compared; the seed's line."""
    import jax
    import numpy as np

    from benchmark.harness import check, loadgen, stats
    from benchmark.harness import spec as specs
    from benchmark.harness import stack as stacks

    with tempfile.TemporaryDirectory(prefix="routedrill_") as tmp:
        stack = stacks.Stack(cell.config, seed, tmp)
        tap = RoutingTap(stack.lanes)
        warm_s = stack.start()
        try:
            traffic = dict(cell.traffic)
            if rate is not None:
                traffic["rate_per_s"] = rate
            plan = specs.load_generator(traffic["generator"]).plan(
                traffic, seed, seconds)
            driver = loadgen.Driver(stack, plan)
            driver.prepare()
            driver.run(seconds, DRAIN_S)
            rows = driver.joined()
            win = [r for r in rows if r["phase"] == "window"]
            snap = stack.db.metrics.snapshot()
            load = stack.db.metrics.latencies[
                "moe_load_max_over_mean"].summary()
        finally:
            stack.stop()
        recs = [stack.recorder.get(r["id"]) for r in win if r["id"]]
        sample = check.sample([r for r in recs if r], seed, CHECK_SAMPLE)
        by_rec = {id(rec): mid for mid, rec in
                  stack.recorder.records.items()}
        params = stack.lanes[0].params
        dims = follower_dims(stack.cfg_file)
        finished = [r for r in stack.recorder.records.values()
                    if r["tokens"]]
        records = []
        for rec in sample:
            got = tap.records.get(by_rec[id(rec)], {})
            routing = got.get("routing")
            unforced = record_gaps(pieces, params, dims, rec, None,
                                   check.MAX_AT)
            line = {"prompt": len(rec["prompt"]),
                    "generated": len(rec["tokens"]),
                    "reason": rec["reason"],
                    # a later turn of a conversation: its prompt holds an
                    # earlier record's whole prompt, so it met the cache
                    "multi_turn": any(
                        o is not rec and len(o["prompt"]) < len(rec["prompt"])
                        and rec["prompt"][:len(o["prompt"])] == o["prompt"]
                        for o in finished),
                    "unforced_max": float(unforced.max()),
                    "unforced_over_tol": int(
                        (unforced > check.LOGIT_TOL).sum())}
            if routing is not None:
                forced = record_gaps(pieces, params, dims, rec,
                                     np.asarray(routing), check.MAX_AT)
                line.update(
                    routing_rows=int(len(routing)),
                    routing_complete=bool(got.get("complete")),
                    dropped_choices=int((np.asarray(routing) < 0).sum()),
                    forced_max=float(forced.max()),
                    forced_over_tol=int((forced > check.LOGIT_TOL).sum()))
            records.append(line)
        e2e = stats.end_to_end(rows, driver.t0, seconds)
        counters = snap["counters"]
        out = {
            "seed": seed, "cell": cell.name, "warm_s": round(warm_s, 1),
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "window_messages": len(win),
            "window_replied": sum(r["reply_t"] is not None for r in win),
            "records": records,
            "forced_max": max((r["forced_max"] for r in records
                               if "forced_max" in r), default=None),
            "unforced_max": max((r["unforced_max"] for r in records),
                                default=None),
            "out_tokens_per_s": e2e.get("out_tokens_per_s"),
            "tpot_p90_ms": e2e.get("tpot_p90_ms"),
            "counters": {k: counters.get(k, 0) for k in (
                "moe_assignments", "moe_dropped_assignments",
                "routing_incomplete_requests", "prefix_reused_tokens",
                "decode_slot_chunks")},
            "moe_load_max_over_mean": load,
            "logit_tol": check.LOGIT_TOL}
        # the next seed's stack needs the room this one's weights hold
        del params, stack, tap, driver
        jax.clear_caches()
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spec", default=os.path.join(
        ROOT, "tests", "benchmark", "tiny", "spec.json"))
    ap.add_argument("--workload", default="tiny-moe.chat")
    ap.add_argument("--seeds", default=",".join(map(str, PR29_SEEDS)))
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rate", type=float, default=None,
                    help="messages a second, in place of the mix's own")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    ap.add_argument("--break-sampler", action="store_true",
                    help="the control: every decode program takes the "
                         "second-best token; every seed must fail forced")
    ap.add_argument("--out", default=None, help="file for the lines")
    args = ap.parse_args(argv)

    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != args.platform:
        print(f"drill: jax found {jax.devices()[0].platform!r}, not "
              f"{args.platform!r}", file=sys.stderr)
        return 2
    from swarmdb_tpu.utils.xla_cache import enable_compile_cache

    from benchmark.harness import spec as specs

    enable_compile_cache()
    if args.break_sampler:
        import swarmdb_tpu.backend.engine as engine

        engine.sample_tokens = lambda logits, *a, **k: jnp.argsort(
            logits, axis=-1)[:, -2].astype(jnp.int32)
    cell = specs.load_cell(args.spec, args.workload)
    pieces = _follower()
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.time()
        line = run_seed(cell, seed, args.seconds, pieces, args.rate)
        line["took_s"] = round(time.time() - t, 1)
        line["broken_sampler"] = bool(args.break_sampler)
        lines.append(line)
        print(json.dumps(line), flush=True)
    tol = lines[0]["logit_tol"]
    forced = [ln["forced_max"] for ln in lines]
    followed = all(f is not None for f in forced)
    passed = [f is not None and f <= tol for f in forced]
    summary = {
        "summary": True, "seeds": len(lines), "followed": followed,
        "forced_at_most_tol": sum(passed),
        "unforced_at_most_tol": sum(
            ln["unforced_max"] is not None and ln["unforced_max"] <= tol
            for ln in lines),
        "forced_max": max((f for f in forced if f is not None),
                          default=None),
        "unforced_max": max((ln["unforced_max"] for ln in lines
                             if ln["unforced_max"] is not None),
                            default=None),
        "broken_sampler": bool(args.break_sampler)}
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            for ln in lines + [summary]:
                f.write(json.dumps(ln) + "\n")
    if not followed:
        return 0 if not args.break_sampler else 1   # nothing to follow
    if args.break_sampler:
        return 0 if not any(passed) else 1
    return 0 if all(passed) else 1


if __name__ == "__main__":
    sys.exit(main())
