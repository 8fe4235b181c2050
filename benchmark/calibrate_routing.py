#!/usr/bin/env python3
"""What the largest logit gap of a check separates from a lower precision,
and what could take its place, read where the numbers are real: no cell,
no engine, no entry of ``BENCHMARK.json``, never run by ``run.py``, and no
rule of ``check.py`` rests on it (``PERF.md`` section 7, last bullet).

    python3 benchmark/calibrate_routing.py --shape olmoe --seeds 12 --out chiprun_out/cal

For each seed it draws the weights of one stack from the seed (bf16,
``normal / sqrt(fan_in)``, norms 1: the rule the program draws by) at a
shape's published router widths and runs random tokens through the same
equations several times, teacher-forced, all positions at once:

  f32       float32 at ``highest``: what a reference computes
  bf16      as the program multiplies: a bf16 stream, bf16 operands with
            float32 accumulation, float32 softmax and norm statistics, the
            router's logits in float32 from the bf16 stream
  int8kv    bf16 with keys and values rounded to int8 by page and head
            (16 tokens, scale = largest / 127), as ``SWARMDB_KV_DTYPE=int8``
  bf16acc   bf16 with every weight matmul accumulated in bf16 over blocks
            of 256 of the contraction
  forced    float32 arithmetic with every layer's experts chosen as a
            lower-precision run chose them: what a reference reads that
            follows the program's own choices (``followed_*``), which is
            how ``check.py`` holds a routed configuration since PR 35

The equations are ``reference/moe_decoder.py``'s (rmsnorm, split-half rope,
causal GQA softmax, a router, a SwiGLU an expert, every token reaches every
expert it chose); ``tests/benchmark/test_bench_check.py`` holds the f32 run
to that file at a tiny shape. The router is the shape's: ``softmax_topk``
(Mixtral, OLMoE: top-k of the logits, softmax over the chosen) or
``sigmoid_bias`` (the DeepSeek-V3 block: scores = sigmoid(logits), top-k of
scores + a bias drawn from the seed, gates = the chosen scores normalised
and scaled). The program's own forward is not used: at 64 experts its
capacity drops tokens in most steps (``PERF.md`` section 7).

What it reads, a line of JSON a seed and a summary over the seeds: the
share of positions whose choice differs between f32 and bf16 in a layer;
the gap the check computes (f32 maximum less the f32 logit of the other
run's argmax) for bf16, for the two degraded runs and for the forced one,
and the same gap against the forced run's logits in place of the f32 run's
(``followed_*``). And ``statistics``: what a check could compute in place
of the largest gap (the mean gap, the share of positions over a small gap,
the share whose first token differs), over stretches of as many positions
as a check compares, for the sound bf16 side (its largest reading) against
each degraded side (its smallest): a statistic separates where the second
is three times the first or more.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

# positions a reading of ``statistics``: a check compares some hundreds of
# served tokens (4 records of at most 256), and could compare more
STRETCHES = (256, 512, 1024)
OVER = (0.005, 0.01, 0.02, 0.03, 0.05)
PAGE = 16
ACC_BLOCK = 256

# hidden, heads, kv heads, head size, experts, expert width, chosen, vocab,
# routed layers. ``sigmoid256`` reads the router alone: its experts are cut
# to a width of 256 (256 x 768 does not fit beside a float32 copy) and its
# attention is plain GQA, so its gaps are no statement about that model.
SHAPES = {
    "tiny-moe": dict(D=64, Hq=4, Hkv=2, hd=16, E=4, F=128, k=2, V=512, L=2,
                     theta=1e4, router="softmax_topk", T=512),
    "mixtral": dict(D=4096, Hq=32, Hkv=8, hd=128, E=8, F=14336, k=2,
                    V=32000, L=3, theta=1e6, router="softmax_topk", T=2048),
    "olmoe": dict(D=2048, Hq=16, Hkv=16, hd=128, E=64, F=1024, k=8,
                  V=50304, L=4, theta=1e4, router="softmax_topk", T=2048),
    "sigmoid256": dict(D=2048, Hq=16, Hkv=16, hd=128, E=256, F=256, k=8,
                       V=32000, L=4, theta=1e4, router="sigmoid_bias",
                       scale=2.5, bias_std=0.05, T=2048),
}
EPS = 1e-5


def draw(shape, seed):
    """The stack's weights from the seed, bf16, as the program draws."""
    import jax
    import jax.numpy as jnp

    s = shape
    D, E, F, L = s["D"], s["E"], s["F"], s["L"]
    top = {"embed": ((s["V"], D), D), "lm_head": ((D, s["V"]), D)}
    per_layer = {"wq": ((D, s["Hq"] * s["hd"]), D),
                 "wk": ((D, s["Hkv"] * s["hd"]), D),
                 "wv": ((D, s["Hkv"] * s["hd"]), D),
                 "wo": ((s["Hq"] * s["hd"], D), s["Hq"] * s["hd"]),
                 "router": ((D, E), D), "w_gate": ((E, D, F), D),
                 "w_up": ((E, D, F), D), "w_down": ((E, F, D), F)}

    @functools.partial(jax.jit, static_argnames=("shp", "fan_in"))
    def dense(key, shp, fan_in):
        return (jax.random.normal(key, shp, jnp.float32)
                / jnp.sqrt(fan_in)).astype(jnp.bfloat16)

    def group(key, names):
        return {n: dense(k, shp, fan) for k, (n, (shp, fan)) in zip(
            jax.random.split(key, len(names)), sorted(names.items()))}

    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31 - 1)), L + 3)
    p = group(keys[L], top)
    # a list and not a stacked array: a slice of a stack is a copy, a
    # gigabyte a weight at Mixtral's widths
    p["layers"] = [group(keys[i], per_layer) for i in range(L)]
    for i, lp in enumerate(p["layers"]):
        lp["bias"] = s.get("bias_std", 0.0) * jax.random.normal(
            jax.random.fold_in(keys[L + 1], i), (E,), jnp.float32)
    tokens = jax.random.randint(keys[L + 2], (s["T"],), 3, s["V"])
    return p, tokens


def make_forward(shape, mode):
    """One jitted forward of the whole stack in ``mode``. Returns logits
    [T, V] float32 and the chosen experts [L, T, k]."""
    import jax
    import jax.numpy as jnp

    s = shape
    D, Hq, Hkv, hd, E, k, L = (s[n] for n in ("D", "Hq", "Hkv", "hd", "E",
                                              "k", "L"))
    exact = mode in ("f32", "forced")
    dt = jnp.float32 if exact else jnp.bfloat16

    def mm(a, w):
        """a [T, K] in dt times a weight [K, N]; the result in dt."""
        w = w.astype(dt)
        if mode != "bf16acc":
            return jnp.dot(a, w, preferred_element_type=jnp.float32
                           ).astype(dt)
        K = a.shape[-1]
        blk = min(ACC_BLOCK, K)

        def block(acc, ab):
            return acc + jnp.dot(*ab, preferred_element_type=jnp.float32
                                 ).astype(dt), None

        return jax.lax.scan(
            block, jnp.zeros((a.shape[0], w.shape[-1]), dt),
            (a.reshape(-1, K // blk, blk).swapaxes(0, 1),
             w.reshape(K // blk, blk, -1)))[0]

    def rmsnorm(x, w):
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + EPS)
        return (x32 * inv).astype(dt) * w

    def rope(x, pos):
        inv = 1.0 / (s["theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                    / hd))
        ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                               axis=-1).astype(dt)

    def int8_pages(x):
        """[T, Hkv, hd] rounded to int8 by page of PAGE tokens and head."""
        T = x.shape[0]
        v = x.astype(jnp.float32).reshape(T // PAGE, PAGE, Hkv, hd)
        scale = jnp.maximum(jnp.max(jnp.abs(v), axis=(1, 3), keepdims=True),
                            1e-30) / 127.0
        q = jnp.clip(jnp.round(v / scale), -127, 127)
        return (q * scale).reshape(T, Hkv, hd).astype(dt)

    def route(r, bias):
        """Router logits [T, E] float32 -> chosen [T, k] and the scores
        [T, E] the gates are made from."""
        if s["router"] == "softmax_topk":
            select = score = r
        else:
            score = jax.nn.sigmoid(r)
            select = score + bias
        return jax.lax.top_k(select, k)[1], score

    def gates_of(score, idx):
        chosen = jnp.take_along_axis(score, idx, axis=-1)
        if s["router"] == "softmax_topk":
            return jax.nn.softmax(chosen, axis=-1)
        return s["scale"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)

    def layer(x, lp, forced_idx):
        T = x.shape[0]
        pos = jnp.arange(T)
        h = rmsnorm(x, lp["attn_norm"])
        q = rope(mm(h, lp["wq"]).reshape(T, Hq, hd), pos)
        kk = rope(mm(h, lp["wk"]).reshape(T, Hkv, hd), pos)
        v = mm(h, lp["wv"]).reshape(T, Hkv, hd)
        if mode == "int8kv":
            kk, v = int8_pages(kk), int8_pages(v)
        sc = jnp.einsum("tkgd,skd->kgts", q.reshape(T, Hkv, Hq // Hkv, hd),
                        kk, preferred_element_type=jnp.float32
                        ) / jnp.sqrt(jnp.float32(hd))
        sc = jnp.where(pos[None, None, None, :] <= pos[None, None, :, None],
                       sc, -jnp.inf)
        att = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(sc, axis=-1
                                                          ).astype(dt), v,
                         preferred_element_type=jnp.float32).astype(dt)
        x = x + mm(att.reshape(T, Hq * hd), lp["wo"])

        h2 = rmsnorm(x, lp["mlp_norm"])
        r = jnp.dot(h2.astype(jnp.float32), lp["router"].astype(jnp.float32))
        idx, score = route(r, lp["bias"])
        use = idx if forced_idx is None else forced_idx
        # [T, E]: a chosen expert's gate, 0 for the others; every expert is
        # computed for every token, one expert at a time
        gate = jnp.sum(jax.nn.one_hot(use, E) * gates_of(score, use)[..., None],
                       axis=1).astype(dt)

        def expert(acc, w):
            wg, wu, wd, g = w
            y = mm(jax.nn.silu(mm(h2, wg)) * mm(h2, wu), wd)
            return acc + g[:, None] * y, None

        out = jax.lax.scan(expert, jnp.zeros_like(x), (
            lp["w_gate"], lp["w_up"], lp["w_down"], gate.T))[0]
        return x + out, idx

    @jax.jit
    def forward(p, tokens, forced):
        with jax.default_matmul_precision("highest" if exact else "default"):
            x = p["embed"][tokens].astype(dt)
            ones = jnp.ones((D,), dt)
            idxs = []
            for i, lp in enumerate(p["layers"]):
                lp = dict(lp, attn_norm=ones, mlp_norm=ones)
                x, idx = layer(x, lp, None if forced is None
                               else forced[i])
                idxs.append(idx)
            logits = jnp.dot(rmsnorm(x, ones), p["lm_head"].astype(dt),
                             preferred_element_type=jnp.float32)
            return logits, jnp.stack(idxs)

    return forward


def read_seed(shape, seed, forwards):
    """Every run of one seed, reduced on the host to what the summary
    needs: small arrays, one entry a position."""
    import numpy as np

    p, tokens = draw(shape, seed)
    ref, idx32 = (np.asarray(a) for a in forwards["f32"](p, tokens, None))
    T = ref.shape[0]

    def gap(logits, of=ref):
        """What the check computes: ``of``'s maximum less ``of``'s logit
        of the token ``logits`` puts first."""
        first = np.asarray(logits).argmax(axis=-1)
        return of.max(axis=-1) - of[np.arange(T), first]

    out = {}
    for mode in ("bf16", "int8kv", "bf16acc"):
        lg, idx = forwards[mode](p, tokens, None)
        lg = np.asarray(lg)
        out["gap_" + mode] = gap(lg)
        forced = np.asarray(forwards["forced"](p, tokens, idx)[0])
        out["followed_" + mode] = gap(lg, forced)
        if mode == "bf16":
            same = np.sort(idx32, axis=-1) == np.sort(np.asarray(idx),
                                                      axis=-1)
            out["flip"] = ~same.all(axis=-1)               # [L, T]
            out["gap_forced"] = gap(forced)
            out["move_forced"] = np.abs(forced - ref).max(axis=-1)
            out["move_bf16"] = np.abs(lg - ref).max(axis=-1)
    return out


READINGS = ("gap_bf16", "gap_forced", "move_forced", "move_bf16",
            "gap_int8kv", "gap_bf16acc", "followed_bf16", "followed_int8kv",
            "followed_bf16acc")


def over(g, d):
    """Of a reading ``g`` [S, T] on the positions ``d``: the largest of
    each seed and what the limits of a check are set from."""
    import numpy as np

    per_seed = [float(g[i][d[i]].max(initial=0.0)) for i in range(len(g))]
    return {"max_by_seed": per_seed, "max": max(per_seed),
            "min_of_seed_max": min(per_seed),
            "q999": float(np.quantile(g[d], 0.999)) if d.any() else None,
            "over_0.1": int((g[d] > 0.1).sum()),
            "seeds_over_0.1": sum(m > 0.1 for m in per_seed)}


def statistics(readings, lo):
    """For each gap reading [S, T] and each stretch of W positions from
    ``lo`` on (every seed cut into whole stretches, and the seed whole):
    the least, the median and the largest over the stretches of each
    statistic of a stretch; then, for the degraded sides, the least of
    theirs over the largest of the sound side's."""
    import numpy as np

    stats = {"mean": lambda w: w.mean(axis=-1),
             "differs": lambda w: (w > 0).mean(axis=-1),
             "max": lambda w: w.max(axis=-1)}
    stats.update({f"over_{g}": (lambda w, g=g: (w > g).mean(axis=-1))
                  for g in OVER})
    gaps = {n: g[:, lo:] for n, g in readings.items()
            if n.startswith(("gap_", "followed_")) and n != "gap_forced"}
    seeds, whole = next(iter(gaps.values())).shape
    out = {}
    for W in tuple(w for w in STRETCHES if w < whole) + (whole,):
        rows = {}
        for n, g in gaps.items():
            w = g[:, :whole // W * W].reshape(-1, W)
            rows[n] = {st: [float(np.quantile(f(w), q)) for q in (0, .5, 1)]
                       for st, f in stats.items()}
        for kind in ("gap_", "followed_"):
            sound = rows[kind + "bf16"]
            for side in ("int8kv", "bf16acc"):
                rows[f"{kind}{side}_least_over_sound_largest"] = {
                    st: (rows[kind + side][st][0] / sound[st][2]
                         if sound[st][2] else None) for st in stats}
        out[str(W)] = {"stretches": whole // W * seeds, **rows}
    return out


def summary(shape, reads, lo):
    """Over the seeds: what ``PERF.md`` quotes. Positions under ``lo`` are
    left out of the gaps (a benchmark's checked positions follow a prompt)."""
    import numpy as np

    flip = np.stack([r["flip"] for r in reads])            # [S, L, T]
    pos = np.broadcast_to(np.arange(flip.shape[2]) >= lo,
                          (len(reads), flip.shape[2]))
    readings = {n: np.stack([r[n] for r in reads]) for n in READINGS}
    return {
        "seeds": len(reads),
        "positions": int(flip.shape[0] * flip.shape[2]),
        "flip_share_by_layer": flip.mean(axis=(0, 2)).tolist(),
        "flip_share_any_layer": float(flip.any(axis=1).mean()),
        "all_positions": {n: over(g, pos) for n, g in readings.items()},
        "statistics": statistics(readings, lo)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", required=True,
                    help=f"one of {sorted(SHAPES)}, or a JSON file that "
                         f"holds a shape with their keys")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2900000001)
    ap.add_argument("--layers", type=int, default=None,
                    help="fewer routed layers than the shape's")
    ap.add_argument("--lo", type=int, default=32,
                    help="gaps are read from this position on")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    dev = jax.devices()[0]
    if dev.platform != args.platform:
        print(f"calibrate_routing: jax found {dev.platform!r}, not "
              f"{args.platform!r}", file=sys.stderr)
        return 2
    if args.shape in SHAPES:
        shape = dict(SHAPES[args.shape])
    else:
        with open(args.shape) as f:
            shape = json.load(f)
        args.shape = os.path.splitext(os.path.basename(args.shape))[0]
    if args.layers:
        shape["L"] = args.layers
    forwards = {m: make_forward(shape, m) for m in (
        "f32", "bf16", "int8kv", "bf16acc", "forced")}
    reads, t0 = [], time.time()
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        reads.append(read_seed(shape, seed, forwards))
        line = summary(shape, reads[-1:], args.lo)
        print(json.dumps({"shape": args.shape, "seed": seed,
                          "s": round(time.time() - t0, 1),
                          "flip_share_by_layer": line["flip_share_by_layer"],
                          "largest": {n: v["max"] for n, v in
                                      line["all_positions"].items()}}),
              flush=True)
    result = {"shape": args.shape, **{k: shape[k] for k in (
        "D", "E", "F", "k", "L", "T", "router")},
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "lo": args.lo, "first_seed": args.first_seed,
        **summary(shape, reads, args.lo)}
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"{args.shape}.json"), "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
