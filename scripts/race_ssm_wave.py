#!/usr/bin/env python3
"""One Mamba-2 layer's scan of a ragged wave alone: ``nemotron_h.
ssm_segments`` (XLA's loop over the live segments) against
``ssm_pallas.ssm_wave_scan`` (one kernel over them), us a segment (PR 53).

    chiprun -- python3 scripts/race_ssm_wave.py              # the chip
    python3 scripts/race_ssm_wave.py --platform cpu --tiny   # a smoke

At the widths of ``benchmark/configs/nemotron-3-nano-30b-a3b.json`` (64
heads of 64, 8 groups, state 128, bf16 pools of 32 slots and 8 snapshots)
on made waves, ``--waves`` as ``rows x tokens`` (a row resumes behind a
page-aligned prefix, so it is cut at its last page end: a row of 215
tokens is segments of 128, 80 and 7): ``--layers`` calls of a form back
to back as ONE jitted program, each on its own layer of the pools, the
best of ``--runs``. A case's line: both forms' us a call and a segment,
the bytes a segment must move (a block of state where one is read or
written, ``dt x``, ``B``, ``C``, ``dt a`` and ``y`` of the live tokens)
over the kernel's time as a share of the chip's bandwidth
(``benchmark/harness/peaks.py``), and how far the kernel's ``y`` and
states lie from the loop's. No cell runs this script.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--waves", default="1x215,2x120,1x1000,8x1")
    ap.add_argument("--layers", type=int, default=23)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, here)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import peaks
    from swarmdb_tpu.models import nemotron_h
    from swarmdb_tpu.models.configs import get_config
    from swarmdb_tpu.ops import ssm_pallas

    f32 = jnp.float32
    if args.tiny:
        cfg = get_config("tiny-nemotron", ssm_heads=4, ssm_head_dim=64,
                         ssm_groups=2, ssm_state=128)
        B, S, L = 4, 2, 2
    else:
        from benchmark.harness import spec

        cfg = spec.model_config(json.load(open(os.path.join(
            here, "benchmark/configs/nemotron-3-nano-30b-a3b.json"))))
        B, S, L = 32, 8, args.layers
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    ps, Q = 16, nemotron_h.SCAN_CHUNK
    on_chip = args.platform == "tpu"
    key = lambda i: jax.random.PRNGKey(args.seed * 100 + i)
    block = H * P * N * 2                     # a state block in bf16
    a_token = 4 * (2 * H * P + 2 * G * N + H)

    for wave in args.waves.split(","):
        rows, toks = (int(v) for v in wave.split("x"))
        if args.tiny:
            toks = min(toks, 40)
        W = 8
        while W < rows * toks:
            W *= 2
        R = max(rows, 8)
        lens = np.zeros(R, np.int32)
        lens[:rows] = toks
        starts = np.zeros(R, np.int32)
        starts[:rows] = np.arange(rows) * toks
        end_lens = lens // ps * ps
        # a row resumes from a snapshot, its state goes to its slot and
        # the state at its last page end to a snapshot of its own
        src = np.where(lens > 0, 1 + np.arange(R) % S, 0).astype(np.int32)
        slots = np.where(lens > 0, np.arange(R) % B, B).astype(np.int32)
        dst = np.where(end_lens > 0, 1 + (np.arange(R) + rows) % S,
                       0).astype(np.int32)
        n_seg = int(np.sum(-(-end_lens // Q) + -(-(lens - end_lens) // Q)))
        must = (n_seg and rows * block                     # the seeds
                + int(np.sum(end_lens > 0)) * block + rows * block
                + rows * toks * a_token)
        xd = 0.1 * jax.random.normal(key(0), (W, H, P), f32)
        la = -jnp.exp(jax.random.uniform(key(1), (W, H), f32, -7.0, 0.5))
        Bm = jax.random.normal(key(2), (W, G, N), f32)
        Cm = jax.random.normal(key(3), (W, G, N), f32)
        j = lambda a: jnp.asarray(a, jnp.int32)
        plan = tuple(j(a) for a in (starts, lens, end_lens))
        where = tuple(j(a) for a in (src, slots, dst))

        def pools():
            draw = lambda i, n: (0.1 * jax.random.normal(
                key(i), (L, n, H * P, N), f32)).astype(jnp.bfloat16)
            return draw(4, B), draw(5, 1 + S)

        def by_loop(xd, la, Bm, Cm, slot, snap):
            def layer(l, c):
                y, ps_ = nemotron_h.ssm_segments(
                    cfg, xd, la, Bm, Cm, *plan, l, *where, c[1:])
                return (c[0] + y, *ps_)
            return jax.lax.fori_loop(0, L, layer,
                                     (jnp.zeros_like(xd), slot, snap))

        def by_kernel(xd, la, Bm, Cm, slot, snap):
            table, n_live = ssm_pallas.wave_segment_table(
                *plan, *where, B, W)
            # as the kernel takes them: a token's ``x | B | C`` one row as
            # the conv leaves it, ``dt`` (1 here) apart
            xbc = jnp.concatenate([a.reshape(W, -1) for a in (xd, Bm, Cm)],
                                  axis=1)

            def layer(l, c):
                y, slot, snap = ssm_pallas.ssm_wave_scan(
                    xbc, jnp.ones_like(la), la, table, n_live, l, *c[1:],
                    interpret=not on_chip)
                return (c[0] + y.reshape(W, H, P), slot, snap)
            return jax.lax.fori_loop(0, L, layer,
                                     (jnp.zeros_like(xd), slot, snap))

        rec = {"wave": wave, "width": W, "segments": n_seg, "layers": L,
               "device": jax.devices()[0].device_kind}
        outs = {}
        for name, form in (("loop", by_loop), ("kernel", by_kernel)):
            # swarmlint: disable=SWL201 -- one jit a form a wave by design: each wave is its own shapes
            run = jax.jit(form, donate_argnums=(4, 5))
            t = time.time()
            out = jax.block_until_ready(run(xd, la, Bm, Cm, *pools()))
            rec[f"{name}_first_call_s"] = round(time.time() - t, 1)
            outs[name] = [np.asarray(a.astype(f32)) for a in out]
            best = float("inf")
            for _ in range(args.runs if on_chip else 0):
                ps_ = jax.block_until_ready(pools())
                t = time.time()
                jax.block_until_ready(run(xd, la, Bm, Cm, *ps_))
                best = min(best, time.time() - t)
            if on_chip:
                rec[f"{name}_us_a_call"] = 1e6 * best / L
                rec[f"{name}_us_a_segment"] = 1e6 * best / L / max(n_seg, 1)
        live = (np.arange(W)[:, None] >= starts[None, :rows]) & (
            np.arange(W)[:, None] < (starts + lens)[None, :rows])
        live = live.any(axis=1)
        for i, what in enumerate(("y", "slot", "snap")):
            a, b = outs["kernel"][i], outs["loop"][i]
            if what == "y":
                a, b = a[live], b[live]
            elif what == "snap":
                a, b = a[:, 1:], b[:, 1:]   # the bin is the loop's alone
            rec[f"{what}_gap"] = float(np.max(np.abs(a - b), initial=0.0))
            rec[f"{what}_scale"] = float(np.max(np.abs(b), initial=0.0))
        if on_chip:
            rec["bytes_a_segment"] = must / max(n_seg, 1)
            rec["kernel_bandwidth_share"] = (
                must / (rec["kernel_us_a_call"] * 1e-6)
                / peaks.peaks(rec["device"])["hbm_bytes_per_s"])
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
