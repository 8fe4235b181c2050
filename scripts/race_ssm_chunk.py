#!/usr/bin/env python3
"""A decode chunk of a ``nemotron_h`` stack alone, ms a step (PR 51).

    chiprun --timeout 1500 -- python3 scripts/race_ssm_chunk.py    # the chip
    python3 scripts/race_ssm_chunk.py --platform cpu --tiny        # a smoke

Eight steps of ``llama.forward_paged_chunked`` and one
``llama.merge_paged_chunk`` as ONE jitted program, at the widths of
``benchmark/configs/nemotron-3-nano-30b-a3b.json`` (``--layers`` of its 52,
32 slots, a chunk of 8, bf16, weights from ``--seed``), with a page table
made here: ``--live`` slots of ``--context`` frozen tokens each, spread
over the batch, the others empty. Every slot is fed the same fixed tokens
whatever the tree, so two trees do the same work: ``--root`` names the
tree whose ``swarmdb_tpu`` is timed (a copy of the parent commit, to read
both in one call). ``--reps`` chunks back to back, the best of ``--runs``;
with ``--trace DIR`` one more run is traced and its device operations
summed by name (``trace_reduce.summary``) into ``DIR/<label>.json``.
One JSON line a case. No cell runs this script.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--label", default="tree")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--layers", type=int, default=52)
    ap.add_argument("--live", default="8,32")
    ap.add_argument("--context", type=int, default=512)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None)
    args = ap.parse_args(argv)
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [args.root, here]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from swarmdb_tpu.models import llama, nemotron_h
    from swarmdb_tpu.models.configs import get_config

    if args.tiny:
        cfg, B, ps, dtype = get_config("tiny-nemotron"), 4, 16, jnp.float32
    else:
        from benchmark.harness import spec

        f = json.load(open(os.path.join(
            here, "benchmark/configs/nemotron-3-nano-30b-a3b.json")))
        f["num_hidden_layers"] = args.layers
        f["program"]["layer_types"] = f["program"]["layer_types"][
            :args.layers]
        f["program"]["state_snapshots"] = 1
        cfg, B, ps, dtype = spec.model_config(f), 32, 16, jnp.bfloat16
    K = 8
    ctx = min(args.context, cfg.max_seq_len - 2 * K)
    maxp = -(-(ctx + 2 * K) // ps)
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(args.seed), dtype)
    jax.block_until_ready(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (K, B, 1), 3,
                                min(cfg.vocab_size, 260))

    def chunk(params, cache, pos0):
        def step(kv, s):
            _logits, kv, *_routing = llama.forward_paged_chunked(
                params, cfg, tokens[s], (pos0 + s)[:, None], cache, kv, s)
            return kv, ()

        kv, _ = jax.lax.scan(step, llama.init_chunk_kv(cfg, B, K, dtype),
                             jnp.arange(K, dtype=jnp.int32))
        return llama.merge_paged_chunk(cache, kv, pos0)

    # the weights are an argument: a closure's would be the program's
    # constants, copied to the host where it is lowered
    run = jax.jit(chunk, donate_argnums=1)
    for n in (int(v) for v in args.live.split(",")):
        n = min(n, B)
        live = np.zeros(B, bool)
        live[B - 1 - (np.arange(n) * B) // max(n, 1)] = n > 0
        table = np.zeros((B, maxp), np.int32)
        table[live] = 1 + np.arange(n * maxp).reshape(n, maxp)
        cache = llama.init_paged_cache(cfg, B, maxp * ps, 1 + B * maxp, ps,
                                       dtype)
        # a state that is not zeros: a product with zeros costs the same,
        # but the outputs should be a step's
        cache["state"] = jax.tree.map(
            lambda a: (0.1 * jax.random.normal(
                jax.random.PRNGKey(2), a.shape, jnp.float32)).astype(a.dtype),
            cache["state"])
        cache["page_table"] = jnp.asarray(table)
        pos0 = jnp.asarray(np.where(live, ctx, 0).astype(np.int32))
        t = time.time()
        cache = jax.block_until_ready(run(params, cache, pos0))
        compile_s = time.time() - t
        best = float("inf")
        for _ in range(args.runs):
            t = time.time()
            for _ in range(args.reps):
                cache = run(params, cache, pos0)
            jax.block_until_ready(cache)
            best = min(best, (time.time() - t) / (args.reps * K))
        rec = {"label": args.label, "live": n, "slots": B,
               "layers": cfg.n_layers, "ms_a_step": 1e3 * best,
               "first_call_s": round(compile_s, 1),
               "device": jax.devices()[0].device_kind}
        if args.trace:
            import tempfile

            from benchmark.harness import trace_reduce

            tmp = tempfile.mkdtemp()
            jax.profiler.start_trace(tmp)
            for _ in range(args.reps):
                cache = run(params, cache, pos0)
            jax.block_until_ready(cache)
            jax.profiler.stop_trace()
            trace = trace_reduce.load_xplane(trace_reduce.find_xplane(tmp))
            os.makedirs(args.trace, exist_ok=True)
            with open(os.path.join(args.trace,
                                   f"{args.label}_{n}.json"), "w") as out:
                json.dump(trace_reduce.summary(trace, per_line=80), out)
            red = trace_reduce.reduce(trace, top=12)
            rec["busy_ms_a_step"] = 1e3 * red["busy_s"] / (args.reps * K)
            rec["device_ops_ms_a_step"] = [
                [name, round(1e3 * s / (args.reps * K), 4)]
                for name, s in red["breakdown"]["device_ops"]]
        print(json.dumps(rec), flush=True)
        del cache
    return 0


if __name__ == "__main__":
    sys.exit(main())
