#!/usr/bin/env python3
"""AOT rehearsal: does a configuration's engine fit one v5e chip?

    JAX_PLATFORMS=cpu python3 benchmark/aot_rehearsal.py benchmark/configs/<name>.json \
        [--layers N] [--batch N] [--pool-tokens N] [--only REGEX] [--threads N]

Builds the configuration's engine here on the CPU (weights are zeros: only
shapes matter), takes ``Engine.warmup_call_plan()`` and compiles every
program for a described, unattached ``v5e:2x2`` chip with the TPU's own
compiler. Prints each program's ``memory_analysis()`` and the plan's total
(the widest program: arguments + outputs - aliased + temporaries) against
95% of the chip's memory. Nothing runs: a compile that passes is not a
chip run, and says nothing about results or times. A script, not a test:
it loads the TPU library, which one process at a time may do.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# what `chip_smoke.py` read from the attached chip (PR 22): bytes_limit
BYTES_LIMIT = 16909336064


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--pool-tokens", type=int)
    ap.add_argument("--only", default=None, help="program names to compile")
    ap.add_argument("--threads", type=int, default=4)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from swarmdb_tpu.backend.service import build_backend_engine
    from swarmdb_tpu.models import llama

    from benchmark.harness import spec

    cfg_file = json.load(open(args.config))
    if args.layers:
        cfg_file["num_hidden_layers"] = args.layers
    serving = cfg_file["serving"]
    if args.batch:
        serving["max_batch"] = args.batch
    if args.pool_tokens:
        serving["kv_pool_tokens"] = args.pool_tokens
    cfg = spec.model_config(cfg_file)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    # steer the program's own questions: it asks jax.default_backend() to
    # choose between a Pallas kernel and its interpreter
    jax.default_backend = lambda: "tpu"
    llama.random_dense = lambda key, shape, fan_in, dtype: jnp.zeros(shape,
                                                                     dtype)
    engine, _tok = build_backend_engine(
        cfg, max_batch=serving["max_batch"], max_seq=serving["max_seq"],
        seed=0, decode_chunk=serving["decode_chunk"], paged=serving["paged"],
        page_size=serving["page_size"],
        kv_pool_tokens=serving.get("kv_pool_tokens"))

    def tree_bytes(t):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(t))

    weights, pool = tree_bytes(engine.params), tree_bytes(engine.cache)
    print(json.dumps({
        "config": cfg.name, "n_layers": cfg.n_layers,
        "max_batch": engine.max_batch, "max_seq": engine.max_seq,
        "pool_pages": engine.paged.num_pages if engine.paged else None,
        "weight_bytes": weights, "kv_pool_bytes": pool}), flush=True)

    def on_chip(s):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), s)

    plan = []
    for fn, specs in engine.warmup_call_plan():
        name = getattr(fn, "__name__", None) or getattr(
            getattr(fn, "__wrapped__", None), "__name__", "program")
        shape = next((s.shape for s in specs[1:2]), ())
        label = f"{name}{list(shape)}"
        if args.only and not re.search(args.only, label):
            continue
        plan.append((label, fn, on_chip(specs)))

    def compile_one(item):
        label, fn, specs = item
        t = time.time()
        try:
            ma = fn.lower(*specs).compile().memory_analysis()
        except Exception as exc:
            return {"program": label, "refused":
                    f"{type(exc).__name__}: {exc}"[:600]}
        live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        return {"program": label, "compile_s": round(time.time() - t, 1),
                "arguments": ma.argument_size_in_bytes,
                "outputs": ma.output_size_in_bytes,
                "aliased": ma.alias_size_in_bytes,
                "temporaries": ma.temp_size_in_bytes,
                "code": ma.generated_code_size_in_bytes, "live": live}

    rows = []
    with concurrent.futures.ThreadPoolExecutor(args.threads) as ex:
        for row in ex.map(compile_one, plan):
            print(json.dumps(row), flush=True)
            rows.append(row)
    refused = [r for r in rows if "refused" in r]
    ok = [r for r in rows if "live" in r]
    widest = max(ok, key=lambda r: r["live"]) if ok else None
    total = {"programs": len(rows), "refused": len(refused),
             "widest_program": widest and widest["program"],
             "plan_bytes": widest and widest["live"],
             "max_temporaries": max((r["temporaries"] for r in ok), default=0),
             "limit_95": int(0.95 * BYTES_LIMIT),
             "fits": bool(widest and not refused
                          and widest["live"] <= 0.95 * BYTES_LIMIT)}
    print(json.dumps(total), flush=True)
    return 0 if total["fits"] else 1


if __name__ == "__main__":
    sys.exit(main())
