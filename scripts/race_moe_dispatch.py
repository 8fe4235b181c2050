#!/usr/bin/env python3
"""Race forms of a dropless top-k expert FFN at one layer's published
widths (PR 36): hidden 2048, expert width 1792, 32 experts, top-4, bf16
(``--widths`` for another layer's).

    chiprun -- python3 scripts/race_moe_dispatch.py            # the chip
    python3 scripts/race_moe_dispatch.py --platform cpu --tiny # a smoke

Every form computes every chosen expert for every live token (no capacity)
and must agree with the plainest one (``dense``) before its time counts.
``live`` marks rows that take part: a dead row (a padded token of a wave,
an empty lane of a decode step) has its gates zeroed and, where a form can
skip work, takes none. Times are ms a call: ``REPS`` dependent calls
inside one jitted ``scan``, so that dispatch is not the reading.

Forms:
- ``dense``: every expert for every token, unchosen gated 0.
- ``loop``: a ``fori_loop`` over experts, an expert no live row chose
  skipped by ``lax.cond``: reads only the chosen experts' weights.
- ``ragged``: token-choices sorted by expert, ``jax.lax.ragged_dot`` over
  the groups.
- ``gmm``: the same sort with jax's Pallas grouped matmul (megablox), as
  a reading of what a kernel would give; not a candidate for the program
  (``--gmm``).
- ``stream``: the served path's kernel up to 512 rows (PR 37,
  ``ops/moe_pallas.stream_experts``): one Pallas call that walks the
  compacted list of hit experts and streams their tiles through a double
  buffer; float32 between the matmuls, as ``lfm2._swiglu``. One entry an
  F tile of ``--tiles`` (``stream_tf896``).
- ``compact``: the same compacted list walked by a ``fori_loop`` with a
  dynamic trip count and no ``cond``, in the kernel's precisions: what
  the conditionals alone cost.

The weights are arguments of every form: as closure constants they are
baked into each executable (1.6 GB a program, and the first call of this
script ran its machine out of host memory that way).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--reps", type=int, default=16)
    ap.add_argument("--gmm", action="store_true",
                    help="also time jax's Pallas grouped matmul (TPU)")
    ap.add_argument("--waves", action="store_true",
                    help="also a step with no live row and the other rungs "
                         "of a ragged wave: 64, 128, 1024, 2048 and 4096 "
                         "rows (PR 37)")
    ap.add_argument("--tiles", default="256,896,1792",
                    help="F tiles of the form ``stream`` (those that "
                         "divide F are raced)")
    ap.add_argument("--widths", default=None,
                    help="D,F,E,k of another layer than lfm2's: "
                         "5120,1536,20,1 is deepseek-v2.chat's 20 held "
                         "experts, of which a row chooses about one (PR 44)")
    args = ap.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp

    from swarmdb_tpu.ops import moe_pallas

    D, F, E, K = (128, 256, 8, 2) if args.tiny else (2048, 1792, 32, 4)
    if args.widths:
        D, F, E, K = (int(v) for v in args.widths.split(","))
    dt = jnp.bfloat16
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    w_gate = (jax.random.normal(ks[0], (E, D, F), jnp.float32) / D ** .5).astype(dt)
    w_up = (jax.random.normal(ks[1], (E, D, F), jnp.float32) / D ** .5).astype(dt)
    w_down = (jax.random.normal(ks[2], (E, F, D), jnp.float32) / F ** .5).astype(dt)
    router = (jax.random.normal(ks[3], (D, E), jnp.float32) / D ** .5).astype(dt)
    bias = 0.1 * jax.random.normal(ks[4], (E,), jnp.float32)

    weights = (w_gate, w_up, w_down)

    def route(x, live):
        s = jax.nn.sigmoid(x.astype(jnp.float32) @ router.astype(jnp.float32))
        _, sel = jax.lax.top_k(s + bias, K)
        g = jnp.take_along_axis(s, sel, axis=-1)
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
        return sel, g * live[:, None]

    def _gate(x, live):
        sel, g = route(x, live)
        gate = jnp.sum(jax.nn.one_hot(sel, E, dtype=jnp.float32)
                       * g[..., None], axis=1)                    # [N, E]
        return gate, jnp.any(gate > 0, axis=0)

    def dense(x, live, w_gate, w_up, w_down):
        gate, _hit = _gate(x, live)
        h = jax.nn.silu(jnp.einsum("nd,edf->enf", x, w_gate)) * jnp.einsum(
            "nd,edf->enf", x, w_up)
        y = jnp.einsum("enf,efd->end", h, w_down)
        return jnp.einsum("end,ne->nd", y, gate.astype(x.dtype))

    def loop(x, live, w_gate, w_up, w_down):
        gate, hit = _gate(x, live)

        def body(e, acc):
            def run(acc):
                h = jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])
                ge = jax.lax.dynamic_slice_in_dim(gate, e, 1, axis=1)
                return acc + (h @ w_down[e]) * ge.astype(x.dtype)
            return jax.lax.cond(hit[e], run, lambda a: a, acc)

        return jax.lax.fori_loop(0, E, body, jnp.zeros_like(x))

    def _sorted(x, live):
        sel, g = route(x, live)
        N = x.shape[0]
        # dead rows sort behind every expert and belong to no group
        e_flat = jnp.where(live[:, None] > 0, sel, E).reshape(-1)
        order = jnp.argsort(e_flat, stable=True)
        tok = order // K
        sizes = jnp.bincount(e_flat, length=E + 1)[:E].astype(jnp.int32)
        return x[tok], sizes, order, tok, g.reshape(-1)[order], N

    def ragged(x, live, w_gate, w_up, w_down):
        xs, sizes, order, tok, gs, N = _sorted(x, live)
        h = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, sizes)) * \
            jax.lax.ragged_dot(xs, w_up, sizes)
        ys = jax.lax.ragged_dot(h, w_down, sizes) * gs[:, None].astype(x.dtype)
        return jnp.zeros_like(x).at[tok].add(ys)

    def gmm_form(x, live, w_gate, w_up, w_down):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        xs, sizes, order, tok, gs, N = _sorted(x, live)
        M = xs.shape[0]
        tm = min(128, M)
        tiling = (tm, min(512, D), min(512, F))
        h = jax.nn.silu(gmm(xs, w_gate, sizes, tiling=tiling)) * gmm(
            xs, w_up, sizes, tiling=tiling)
        ys = gmm(h.astype(x.dtype), w_down, sizes,
                 tiling=(tm, min(512, F), min(512, D)))
        ys = ys * gs[:, None].astype(x.dtype)
        return jnp.zeros_like(x).at[tok].add(ys.astype(x.dtype))

    def stream(tile_f):
        def form(x, live, w_gate, w_up, w_down):
            gate, hit = _gate(x, live)
            return moe_pallas.stream_experts(
                x, gate, hit, w_gate, w_up, w_down, tile_f=tile_f,
                interpret=args.platform == "cpu")
        return form

    def compact(x, live, w_gate, w_up, w_down):
        gate, hit = _gate(x, live)
        n_hit, ids = moe_pallas.hit_list(hit)
        f32 = jnp.float32

        def body(i, acc):
            e = ids[i]
            h = jax.nn.silu(jnp.dot(x, w_gate[e], preferred_element_type=f32)
                            ) * jnp.dot(x, w_up[e], preferred_element_type=f32)
            ge = jax.lax.dynamic_slice_in_dim(gate, e, 1, axis=1)
            return acc + jnp.dot(h.astype(x.dtype), w_down[e],
                                 preferred_element_type=f32) * ge

        return jax.lax.fori_loop(0, n_hit[0], body,
                                 jnp.zeros(x.shape, f32)).astype(x.dtype)

    forms = {"dense": dense, "loop": loop, "ragged": ragged}
    if args.gmm:
        forms["gmm"] = gmm_form
    forms["compact"] = compact
    for tf in (int(t) for t in args.tiles.split(",")):
        if F % tf == 0:
            forms[f"stream_tf{tf}"] = stream(tf)

    def timed(fn, x, live):
        @jax.jit
        def many(x, *w):
            def body(c, _):
                return c + 1e-3 * fn(c, live, *w), None
            return jax.lax.scan(body, x, None, length=args.reps)[0]
        many(x, *weights).block_until_ready()
        t = time.perf_counter()
        many(x, *weights).block_until_ready()
        return (time.perf_counter() - t) / args.reps * 1e3

    shapes = ([("prefill", 16, 16), ("decode", 8, 2)] if args.tiny else
              [("prefill", 512, 512), ("prefill", 256, 256),
               ("prefill", 512, 300), ("decode", 32, 32), ("decode", 32, 8),
               ("decode", 32, 4), ("decode", 32, 1)])
    if args.waves and not args.tiny:
        # no live row: what a layer costs beside its experts' bytes
        shapes += [("decode", 32, 0),
                   ("prefill", 64, 64), ("prefill", 128, 128),
                   ("prefill", 1024, 1024), ("prefill", 2048, 2048),
                   ("prefill", 4096, 4096), ("prefill", 4096, 2500)]
    print(json.dumps({"device": jax.devices()[0].device_kind, "D": D, "F": F,
                      "E": E, "k": K, "reps": args.reps}), flush=True)
    once = {name: jax.jit(fn) for name, fn in forms.items()}
    route_once = jax.jit(route)
    for what, n, n_live in shapes:
        x = jax.random.normal(jax.random.fold_in(ks[5], n), (n, D),
                              jnp.float32).astype(dt)
        live = (jnp.arange(n) < n_live).astype(jnp.float32)
        want = once["dense"](x, live, *weights).astype(jnp.float32)
        sel, _ = route_once(x, live)
        hit = len(set(int(e) for e in jnp.asarray(sel[:n_live]).reshape(-1)))
        row = {"shape": what, "rows": n, "live": n_live, "experts_hit": hit}
        for name, fn in forms.items():
            try:
                got = once[name](x, live, *weights).astype(jnp.float32)
                err = float(jnp.max(jnp.abs(got - want)[:n_live],
                                    initial=0.0))
                row[name] = {"ms": round(timed(fn, x, live), 4),
                             "max_abs_diff": round(err, 5)}
            except Exception as exc:  # a form the compiler refuses
                row[name] = {"refused": f"{type(exc).__name__}: {exc}"[:300]}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
