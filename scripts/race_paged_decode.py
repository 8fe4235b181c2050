#!/usr/bin/env python3
"""The chunked paged decode kernel alone, ms a call (PR 30's table; PR 47).

    chiprun -- python3 scripts/race_paged_decode.py              # the chip
    python3 scripts/race_paged_decode.py --platform cpu --tiny   # a smoke

``ops/attention_pallas.paged_decode_gqa_attention_chunked`` at a cell's
shapes (32 query and 8 KV heads of 128, page 16, a table of 256 pages,
bf16, a chunk of 8; ``--batch 16`` is ``mistral7b.chat``'s and 32
``lfm2-8b-a1b.chat``'s), ``--reps`` dependent calls inside one jitted
``scan`` (a call's output is the next one's query), so that dispatch is
not the reading; the best of ``--runs`` runs. The cases:

- ``empty``: no live slot (every table row all trash);
- ``<live>x<ctx>``: that many live slots of that many frozen tokens each,
  spread over the batch, the other slots empty; ``B`` live is the full
  batch;
- ``mix``: 5 live slots of 300-1,000 tokens, the cell's mix of PR 30.

Each case is read twice: with the live list as the forward makes it
(``live_row_list``), and ``walked``: every slot in the list, empty ones
too, which is what a call cost while a slot was a grid step.
No cell runs this script.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def cases(B: int, maxp: int, ps: int, contexts, lives):
    """(name, starts [B]) of every case: ``starts[b]`` frozen tokens in
    slot b, 0 for an empty slot."""
    import numpy as np

    full = maxp * ps - 8               # a chunk's room under the table
    out = [("empty", np.zeros(B, np.int32))]
    for ctx in (min(c, full) for c in contexts):
        for n in (n for n in lives if 0 < n <= B):
            starts = np.zeros(B, np.int32)
            # live slots spread over the batch, the last slot among them
            starts[B - 1 - (np.arange(n) * B) // n] = ctx
            out.append((f"{n}x{ctx}", starts))
    mix = np.zeros(B, np.int32)
    mix[[1, B // 4 + 1, B // 2, B - 4, B - 1]] = [300, 1000, 520, 760, 410]
    out.append(("mix-5x300-1000", mix))
    return out


def race(kernel, args) -> list:
    """Time ``kernel`` (the signature of
    ``paged_decode_gqa_attention_chunked``) on every case; one record a
    reading."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from swarmdb_tpu.ops.paged_kv import live_row_list

    Hq, Hkv, D = (4, 2, 128) if args.tiny else (32, 8, 128)
    ps, maxp, Kc = (8, 16, 8) if args.tiny else (16, 256, 8)
    contexts = [100] if args.tiny else [300, 1000, 4088]
    interpret = jax.default_backend() != "tpu"
    dt = jnp.bfloat16
    records = []
    for B in (int(b) for b in args.batch.split(",")):
        P = 1 + B * maxp
        key = jax.random.PRNGKey(B)
        kq, kk, kv, kc = jax.random.split(key, 4)
        q0 = jax.random.normal(kq, (B, Hq, D), dt)
        pool_k = jax.random.normal(kk, (P, ps, Hkv, D), dt)
        pool_v = jax.random.normal(kv, (P, ps, Hkv, D), dt)
        chunk = jax.random.normal(kc, (2, B, Kc, Hkv, D), dt)
        step = jnp.asarray(3, jnp.int32)
        # every slot owns its pages; a row's table holds its live ones
        own = 1 + np.arange(B * maxp, dtype=np.int32).reshape(B, maxp)

        # the pools are arguments: as closure constants they would be
        # baked into the executable
        @jax.jit
        def run(q, pool_k, pool_v, table, starts, rows, n_live):
            def body(q, _):
                o = kernel(q, pool_k, pool_v, table, chunk[0], chunk[1],
                           starts, step, rows, n_live, interpret=interpret)
                return o, None

            return jax.lax.scan(body, q, None, length=args.reps)[0]

        for name, starts in cases(B, maxp, ps, contexts,
                                  (1, 3, 5, 8, B)):
            live_pages = -(-starts // ps)
            table = np.where(np.arange(maxp)[None] < live_pages[:, None],
                             own, 0).astype(np.int32)
            lists = {"live": live_row_list(jnp.asarray(table)),
                     "walked": (jnp.arange(B, dtype=jnp.int32),
                                jnp.int32(B))}
            for how, (rows, n_live) in lists.items():
                ops = (q0, pool_k, pool_v, jnp.asarray(table),
                       jnp.asarray(starts), rows, n_live)
                jax.block_until_ready(run(*ops))
                best = float("inf")
                for _ in range(args.runs):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(*ops))
                    best = min(best, time.perf_counter() - t0)
                rec = {"B": B, "case": name, "list": how,
                       "n_live": int(n_live),
                       "ms_per_call": best * 1e3 / args.reps}
                records.append(rec)
                print(json.dumps(rec), flush=True)
    return records


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", default="16,32")
    ap.add_argument("--reps", type=int, default=64)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/race_paged_decode.json")
    args = ap.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    from swarmdb_tpu.ops.attention_pallas import (
        paged_decode_gqa_attention_chunked)

    if args.platform == "tpu" and jax.default_backend() != "tpu":
        print("no TPU here: --platform cpu --tiny is the smoke",
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    records = race(paged_decode_gqa_attention_chunked, args)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "platform": dev.platform,
                   "reps": args.reps, "records": records}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
