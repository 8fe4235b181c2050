"""The family with a mixer a layer (LFM2-MoE style) — functional JAX.

The decoder is ``models/llama.py``'s: one layer skeleton (``layer_step``:
norm, mixer, residual, norm, FFN, residual), one head, every forward. This
family brings what a configuration with ``layer_types`` has of its own:

- **its parameters**, which no single ``[L, ...]`` stack describes: a layer
  is a gated short convolution or GQA attention, its FFN dense or routed.
  ``layer_plan`` cuts the layers into SEGMENTS, a pattern of layers and how
  often it repeats, choosing the period that leaves the fewest distinct
  layer bodies to compile; ``params["segments"][s][j]`` holds the weights
  of the pattern's ``j``-th layer stacked over the segment's repeats, and
  ``run_layers`` scans a segment that repeats and unrolls one that does
  not. At the published 16-layer cut that is one unrolled period (its two
  leading layers have the dense FFNs) and a scan over the three that
  follow.
- **its conv mixer** (``conv_token_mixer``): ``[B, C, X] = split3(u W_in)``,
  ``z = B * X``, ``c_t = sum_j w_j * z_{t-(taps-1)+j}`` (depthwise, causal,
  no bias), ``out = (C * c) W_out``. What a sequence carries from one call
  to the next is the last ``taps - 1`` rows of ``z``, its STATE,
  ``[taps - 1, D]`` a conv layer, oldest first. Each forward says where a
  token's earlier ``z`` come from (its ``history``): the call's own rows,
  or the state the caller seeds it with.
- **its router and FFN** (``moe_block``): float32 sigmoid scores, top-k of
  scores + ``expert_bias`` (the bias chooses and never gates), gates the
  chosen scores renormalised (``/ (sum + 1e-6)``), and every chosen expert
  computed for every token: no capacity, no dropped token, so a token's
  result does not depend on what shares its call. Either way a call reads
  the weights of the experts its live rows chose and no others: a decode
  step's rows and a wave's up to 512 through one Pallas kernel that
  streams the hit experts back to back (``ops/moe_pallas``: bf16,
  lane-multiple widths, a TPU), every other call through a loop over
  experts that skips, by ``lax.cond``, an expert no live row chose
  (``scripts/race_moe_dispatch.py`` has the forms they were raced
  against, ``PERF.md`` section 6 the readings). ``live`` marks the rows that
  take part; a padded token of a wave or an empty lane of a step has its
  gates zeroed and reads no expert.

Weights are drawn through ``llama.random_dense`` looked up when called, so
that a caller that plans memory without drawing weights replaces it for
this family too.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import moe_pallas
from . import llama
from .configs import ModelConfig
from .mixtral import encode_routing

Params = Dict[str, Any]
Signature = Tuple[str, str]            # (mixer, ffn) of one layer

EXPERT_MATRICES = ("w_gate", "w_up", "w_down")
GATE_EPS = 1e-6                        # in the gates' renormalisation
EXPERT_BIAS_STD = 0.1                  # random weights: a zero bias hides it


# ------------------------------------------------------------------ the plan


def layer_plan(cfg: ModelConfig) -> List[Tuple[Tuple[Signature, ...], int]]:
    """``[(pattern, repeats)]``: the layers in order, cut into groups of one
    period and consecutive equal groups merged. The period is the one that
    leaves the fewest layer bodies (sum of pattern lengths): 4 for
    ``conv conv full_attention conv``, whose first group differs by its
    dense FFNs and stands alone."""
    return plan_periods([
        (mixer, "moe" if cfg.is_moe and l >= cfg.n_dense_layers else "dense")
        for l, mixer in enumerate(cfg.mixers)])


def plan_periods(sigs: List[Any]) -> List[Tuple[Tuple[Any, ...], int]]:
    """``layer_plan``'s rule over any list of layer signatures."""
    best = None
    for period in range(1, len(sigs) + 1):
        segs: List[List[Any]] = []
        for i in range(0, len(sigs), period):
            group = tuple(sigs[i:i + period])
            if segs and segs[-1][0] == group:
                segs[-1][1] += 1
            else:
                segs.append([group, 1])
        cost = sum(len(g) for g, _ in segs)
        if best is None or cost < best[0]:
            best = (cost, segs)
    return [(g, n) for g, n in best[1]]


def routing_shape(cfg: ModelConfig) -> Optional[Tuple[int, int, int]]:
    """``(L_routed, k, E)`` of what the forwards report a position: the
    layers after the leading dense ones."""
    if not cfg.is_moe:
        return None
    return cfg.n_routed_layers, cfg.experts_per_token, cfg.n_experts


# ---------------------------------------------------------------------- init


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> Params:
    D, F, E = cfg.dim, cfg.ffn_dim, cfg.n_experts
    Fe = cfg.expert_ffn_dim or F
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    taps = cfg.conv_taps
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense(key, shape, fan_in):
        return llama.random_dense(key, shape, fan_in, dtype)

    # the conv mixer's ``out_proj`` is drawn at 1/16 of the plain scale.
    # Drawn plainly the gated convolution, cubic in its input and not
    # averaged over a context as attention is, is most of the residual
    # stream from the first layer on, and the stack amplifies its own
    # bf16 rounding to 2.6 times a dense stack's: a bf16 program was then
    # not held to a float32 reference at the benchmark's tolerance (3 of 3
    # runs read 0.12-0.14; PERF.md section 6, PR 36). A trained model's
    # branches are small beside its stream; a conv branch that loses its
    # history still reads 5 logits off at this scale. The FFNs are drawn
    # plainly: at a quarter of it the greedy continuation collapses to
    # one token and the rows of a step to a third of the experts
    conv_back = 256

    def near_one(key, shape):
        # a norm weight of all ones would hide a norm applied to the
        # wrong tensor
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    def layer(key, sig: Signature, n: int) -> Params:
        mixer, ffn = sig
        ks = jax.random.split(key, 12)
        lp: Params = {"attn_norm": jnp.ones((n, D), dtype),
                      "mlp_norm": jnp.ones((n, D), dtype)}
        if mixer == "conv":
            lp["in_proj"] = dense(ks[0], (n, D, 3 * D), D)
            lp["conv_w"] = dense(ks[1], (n, taps, D), taps)
            lp["out_proj"] = dense(ks[2], (n, D, D), D * conv_back)
        else:
            lp["wq"] = dense(ks[0], (n, D, Hq * hd), D)
            lp["wk"] = dense(ks[1], (n, D, Hkv * hd), D)
            lp["wv"] = dense(ks[2], (n, D, Hkv * hd), D)
            lp["wo"] = dense(ks[3], (n, Hq * hd, D), Hq * hd)
            if cfg.qk_norm:
                lp["q_norm"] = near_one(ks[4], (n, hd))
                lp["k_norm"] = near_one(ks[5], (n, hd))
        if ffn == "dense":
            lp["w_gate"] = dense(ks[6], (n, D, F), D)
            lp["w_up"] = dense(ks[7], (n, D, F), D)
            lp["w_down"] = dense(ks[8], (n, F, D), F)
        else:
            lp["router"] = dense(ks[6], (n, D, E), D)
            lp["expert_bias"] = EXPERT_BIAS_STD * jax.random.normal(
                ks[7], (n, E), jnp.float32)
            lp["w_gate"] = dense(ks[8], (n, E, D, Fe), D)
            lp["w_up"] = dense(ks[9], (n, E, D, Fe), D)
            lp["w_down"] = dense(ks[10], (n, E, Fe, D), Fe)
        return lp

    plan = layer_plan(cfg)
    seg_keys = jax.random.split(k_layers, len(plan))
    segments = []
    for (pattern, n), sk in zip(plan, seg_keys):
        lks = jax.random.split(sk, len(pattern))
        segments.append([layer(lk, sig, n) for lk, sig in zip(lks, pattern)])
    params: Params = {"embed": dense(k_embed, (cfg.vocab_size, D), D),
                      "segments": segments,
                      "final_norm": jnp.ones((D,), dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k_head, (D, cfg.vocab_size), D)
    return params


def init_state(cfg: ModelConfig, rows: int,
               dtype: jnp.dtype = jnp.bfloat16) -> jnp.ndarray:
    """Zeroed conv state ``[L_conv, rows, taps - 1, D]``: a row a slot (the
    state a live sequence carries) or a row a page (the state at that
    page's end, beside its keys and values)."""
    return jnp.zeros((cfg.n_conv_layers, rows, cfg.conv_taps - 1, cfg.dim),
                     dtype)


# ------------------------------------------------------------ the conv mixer


def conv_token_mixer(history):
    """The gated short convolution as a token mixer of
    ``llama.layer_step``. ``history(z, ops) -> (earlier, out)`` is the
    forward's: ``earlier[i]`` is ``z`` of ``i + 1`` positions before each
    token ([B, T, D]), ``out`` what the forward keeps of the layer."""

    def token_mixer(h, lp, ops):
        # everything between the two matmuls in float32, rounded once
        # for ``out_proj``: elementwise work, and three gates each rounded
        # to bf16 and a bf16 sum of bf16 products a layer show in the
        # logits (PERF.md section 6, PR 36). ``z`` is rounded to the
        # stream's dtype where it is made: it is what the state holds,
        # and a token reads the same ``z`` from its call and from a state
        bcx = jnp.einsum("btd,de->bte", h, lp["in_proj"],
                         preferred_element_type=jnp.float32)
        b_gate, c_gate, x = jnp.split(bcx, 3, axis=-1)
        z = (b_gate * x).astype(h.dtype)
        earlier, out = history(z, ops)
        w = lp["conv_w"].astype(jnp.float32)   # [taps, D]; w[-1] takes z_t
        c = w[-1] * z
        for i, zi in enumerate(earlier):
            c = c + w[-2 - i] * zi
        gated = (c_gate * c).astype(h.dtype)
        return jnp.einsum("btd,de->bte", gated, lp["out_proj"]), out

    return token_mixer


def history_whole(n_state: int):
    """A whole sequence from position 0: nothing before it. Keeps the
    state after the last position of the call."""

    def history(z, _ops):
        T = z.shape[1]
        earlier = [jnp.pad(z, ((0, 0), (i + 1, 0), (0, 0)))[:, :T]
                   for i in range(n_state)]
        return earlier, z[:, T - n_state:]

    return history


def history_stream(n_state: int, tok_row, starts, lens, page_end):
    """A packed stream of rows back to back (``forward_ragged_prefill``).
    A token ``o`` positions into its row takes ``z`` of its own row from
    the stream and, for what lies before the row, from the row's seed
    (``ops`` [R, n_state, D], oldest first; zeros for a cold row): it
    never reads its neighbour. Keeps ``(row_state [R, n_state, D],
    page_state [len(page_end), n_state, D])``: the state after each row's
    last token of this call, and after each stream index of ``page_end``
    (the tokens that end a page)."""
    R = starts.shape[0]
    row = jnp.clip(tok_row, 0, R - 1)
    W = tok_row.shape[0]
    o = jnp.arange(W, dtype=jnp.int32) - starts[row]       # [W]
    last = starts + jnp.maximum(lens - 1, 0)               # dead rows -> 0

    def history(z, seed):
        zs = z[0]                                          # [W, D]
        earlier = []
        for i in range(n_state):
            own = jnp.pad(zs, ((i + 1, 0), (0, 0)))[:W]
            idx = jnp.clip(n_state - 1 - i + o, 0, n_state - 1)
            earlier.append(jnp.where((o > i)[:, None], own, seed[row, idx]))
        # the state after token w, oldest first
        after = jnp.stack(earlier[n_state - 2::-1] + [zs], axis=1) \
            if n_state > 1 else zs[:, None]
        return [e[None] for e in earlier], (after[last], after[page_end])

    return history


def history_chunk(n_state: int, step):
    """One decode step of a chunk: ``ops = (state [B, n_state, D], hz
    [B, K, D])``, the slots' state as the chunk began and the chunk's own
    ``z`` so far. Keeps ``hz`` with this step's ``z`` at ``step``."""

    def history(z, ops):
        state, hz = ops
        hist = jnp.concatenate([state.astype(z.dtype), hz], axis=1)
        win = jax.lax.dynamic_slice_in_dim(hist, step, n_state, axis=1)
        earlier = [win[:, n_state - 1 - i][:, None] for i in range(n_state)]
        hz = jax.lax.dynamic_update_slice_in_dim(hz, z.astype(hz.dtype),
                                                 step, axis=1)
        return earlier, hz

    return history


def seed_state(src, slots, slot_state: jnp.ndarray,
               page_state: jnp.ndarray) -> jnp.ndarray:
    """Each wave row's state before its first token of the call,
    ``[L_conv, R, n_state, D]``: page ``src``'s (``src > 0``: a prefix
    hit resumes behind its last hit page), its own slot's (``src < 0``:
    a later chunk of a split prompt) or zeros (``src == 0``: a cold row).
    ``slots`` [R] may hold one past the last slot for a padding row."""
    pick = lambda c: c[None, :, None, None]
    own = slot_state[:, jnp.clip(slots, 0, slot_state.shape[1] - 1)]
    return jnp.where(pick(src > 0), page_state[:, jnp.maximum(src, 0)],
                     jnp.where(pick(src < 0), own, 0))


def merge_state(state: jnp.ndarray, hz: jnp.ndarray) -> jnp.ndarray:
    """The slots' state after a chunk: the last ``n_state`` rows of what
    they held and the chunk's ``z`` ([L_conv, B, n_state | K, D])."""
    n_state = state.shape[2]
    both = jnp.concatenate([state, hz.astype(state.dtype)], axis=2)
    return both[:, :, both.shape[2] - n_state:]


# ------------------------------------------------------------ the expert FFN


def route(h: jnp.ndarray, router_w: jnp.ndarray, bias: jnp.ndarray,
          top_k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(chosen [N, k] int32, gates [N, k] float32)`` of ``h`` [N, D]:
    float32 sigmoid scores, top-k of scores + bias, the chosen scores
    renormalised. The bias chooses and does not gate."""
    s = jax.nn.sigmoid(jnp.einsum("nd,de->ne", h.astype(jnp.float32),
                                  router_w.astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    g = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, g / (jnp.sum(g, axis=-1, keepdims=True) + GATE_EPS)


def _swiglu(x, w_gate, w_up, w_down):
    """``W_2(silu(W_1 x) * W_3 x)`` in float32 but for the matmuls'
    inputs: the gate and the product are elementwise work, and rounded to
    bf16 one by one, a layer after the other, they show in the logits
    (PERF.md section 6, PR 36). Returns float32."""
    f32 = jnp.float32
    h = jax.nn.silu(jnp.dot(x, w_gate, preferred_element_type=f32)) \
        * jnp.dot(x, w_up, preferred_element_type=f32)
    return jnp.dot(h.astype(x.dtype), w_down, preferred_element_type=f32)


def _relu2(x, w_up, w_down):
    """The un-gated expert ``W_2 relu(W_1 x)^2``, float32 between its two
    matmuls as ``_swiglu`` is. Returns float32."""
    f32 = jnp.float32
    h = jnp.square(jax.nn.relu(jnp.dot(x, w_up, preferred_element_type=f32)))
    return jnp.dot(h.astype(x.dtype), w_down, preferred_element_type=f32)


def expert_ffn(x, w_gate, w_up, w_down):
    """One expert by what its layer holds: ``_swiglu``, or ``_relu2`` for a
    layer without gate matrices (``w_gate`` None)."""
    return (_relu2(x, w_up, w_down) if w_gate is None
            else _swiglu(x, w_gate, w_up, w_down))


def moe_block(x: jnp.ndarray, lp: Params, top_k: int,
              live: Optional[jnp.ndarray] = None, base=0,
              chosen_gates=None, held: Optional[Tuple[int, int]] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless routed expert FFN. ``x`` [B, T, D]; ``live`` [B, T] bool,
    the rows that take part (None: all). ``lp``'s expert matrices are one
    layer's ``[E, ...]`` or, with ``base``, the flat ``[n * E, ...]`` stack
    of a scanned segment in which this layer's expert ``e`` is row
    ``base + e`` (``run_layers``). ``chosen_gates(xf, lp) -> (chosen
    [N, k], gates [N, k])`` is another family's router (default: this
    one's ``route``). ``held = (first, count)``: the weights hold experts
    ``first .. first + count`` of those the router scores, expert ``e`` at
    row ``base + e - first``; a choice outside them takes no part here.
    Returns (output [B, T, D], routing [B, T, k] int16: ``e``, or ``~e``
    for a choice that is not held; nothing is dropped)."""
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    chosen, gates = (chosen_gates(xf, lp) if chosen_gates else route(
        xf, lp["router"], lp["expert_bias"], top_k))
    if held is None:
        E, local, kept = lp["router"].shape[-1], chosen, None
    else:
        first, E = held
        local = chosen - first
        kept = (local >= 0) & (local < E)
        gates = jnp.where(kept, gates, 0.0)
    if live is not None:
        gates = gates * live.reshape(B * T, 1)
    # [N, E]: a chosen expert's gate, 0 for the others and for dead rows
    # (``one_hot`` of an index outside the held ones is all zeros)
    gate = jnp.sum(jax.nn.one_hot(local, E, dtype=jnp.float32)
                   * gates[..., None], axis=1)
    hit = jnp.any(gate > 0, axis=0)
    w_gate, w_up, w_down = lp.get("w_gate"), lp["w_up"], lp["w_down"]

    def expert(e, acc):
        def run(acc):
            ge = jax.lax.dynamic_slice_in_dim(gate, e, 1, axis=1)
            return acc + expert_ffn(
                xf, None if w_gate is None else w_gate[base + e],
                w_up[base + e], w_down[base + e]) * ge

        return jax.lax.cond(hit[e], run, lambda a: a, acc)

    # an expert in float32 between its matmuls (``_swiglu``), gated and
    # summed in float32 and rounded once: a bf16 sum over the chosen
    # experts, a routed layer after the other, shows in the logits.
    # One algorithm, realized by the rows of the call: while the hit
    # experts' bytes are the cost (a decode step, a wave up to 512 rows) one
    # kernel streams them back to back; a wider wave keeps the loop
    if moe_pallas.takes(B * T, xf.dtype, w_up):
        y = moe_pallas.stream_experts(
            xf, gate, hit, w_gate, w_up, w_down, base,
            interpret=jax.default_backend() != "tpu")
    else:
        y = jax.lax.fori_loop(0, E, expert, jnp.zeros(xf.shape, jnp.float32)
                              ).astype(x.dtype)
    routing = encode_routing(
        chosen, jnp.ones(chosen.shape, bool) if kept is None else kept)
    return y.reshape(B, T, D), routing.reshape(B, T, top_k)


# ------------------------------------------------------------------ the stack


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees) if trees else None


def _concat(trees):
    trees = [t for t in trees if t is not None]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *trees) \
        if trees else None


def run_layers(params: Params, cfg: ModelConfig, x: jnp.ndarray, cos, sin,
               mixer, attn_ops, history, conv_ops,
               live: Optional[jnp.ndarray] = None, attn_tm=None,
               routed_ffn=None):
    """``x`` through every layer. ``mixer`` and ``attn_ops`` are an
    attention layer's, as ``llama.decoder_layer`` takes them, ``attn_ops``
    with a leading axis over the layers that attend; ``history`` and
    ``conv_ops`` a conv layer's (``conv_token_mixer``), over the conv
    layers. Returns ``(x, attention outs, conv outs, routing)``: the
    mixers' outputs stacked over their layers, and the routing
    ``[B, T, L_routed, k]`` of the layers that route. Another family on
    these segments brings its own token mixer for the layers that attend
    (``attn_tm``) and its routed layers' FFN (``routed_ffn(h, lp, repeat)
    -> (y, routing)``): models/deepseek.py."""
    attn_tm = attn_tm or llama.attention_token_mixer(cfg, cos, sin, mixer)
    conv_tm = conv_token_mixer(history)

    def dense_ffn(h, lp):
        return _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]
                       ).astype(h.dtype)

    a0 = c0 = 0
    outs_a, outs_c, outs_r = [], [], []
    for (pattern, n), seg in zip(layer_plan(cfg), params["segments"]):
        na = sum(m == "attention" for m, _ in pattern)
        nc = len(pattern) - na
        # a routed layer's expert matrices stay out of the scan: what a
        # scan slices it copies, a layer's every expert a step (0.7 ms a
        # matrix on the chip, 25 ms a step at the published cut: PERF.md
        # section 6, PR 36). The kernel and the loop over experts index the
        # flat [n * E, ...] stack itself, at repeat * E + expert
        routed = [ffn == "moe" for _m, ffn in pattern]
        experts = [{k: lp[k].reshape((-1,) + lp[k].shape[2:])
                    for k in EXPERT_MATRICES} if moe else {}
                   for moe, lp in zip(routed, seg)]
        seg = [{k: v for k, v in lp.items()
                if not (moe and k in EXPERT_MATRICES)}
               for moe, lp in zip(routed, seg)]

        def of_segment(ops, lo, per):
            # [n * per, ...] of the layers' ops -> [n, per, ...]
            return jax.tree.map(
                lambda a: a[lo:lo + n * per].reshape((n, per) + a.shape[1:]),
                ops)

        def body(x, scanned, pattern=pattern, experts=experts):
            lps, a_r, c_r, repeat = scanned

            def moe_ffn(h, lp):
                if routed_ffn is not None:
                    return routed_ffn(h, lp, repeat)
                return moe_block(h, lp, cfg.experts_per_token, live,
                                 repeat * cfg.n_experts)

            ja = jc = 0
            a_out, c_out, r_out = [], [], []
            for (m, ffn), lp, big in zip(pattern, lps, experts):
                lp = {**lp, **big}
                if m == "attention":
                    ops = jax.tree.map(lambda a, j=ja: a[j], a_r)
                    ja += 1
                else:
                    ops = jax.tree.map(lambda a, j=jc: a[j], c_r)
                    jc += 1
                x, out, routing = llama.layer_step(
                    cfg, attn_tm if m == "attention" else conv_tm,
                    moe_ffn if ffn == "moe" else dense_ffn,
                    ffn == "moe")(x, lp, ops)
                (a_out if m == "attention" else c_out).append(out)
                if routing is not None:
                    r_out.append(routing)
            return x, (_stack(a_out), _stack(c_out), _stack(r_out))

        scanned = (seg, of_segment(attn_ops, a0, na),
                   of_segment(conv_ops, c0, nc),
                   jnp.arange(n, dtype=jnp.int32))
        if n == 1:
            x, outs = body(x, jax.tree.map(lambda a: a[0], scanned))
            outs = jax.tree.map(lambda a: a[None], outs)
        else:
            x, outs = jax.lax.scan(body, x, scanned)
        # [n, per, ...] -> [n * per, ...], layer order
        flat = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
            outs)
        outs_a.append(flat[0])
        outs_c.append(flat[1])
        outs_r.append(flat[2])
        a0, c0 = a0 + n * na, c0 + n * nc
    routing = _concat(outs_r)
    return (x, _concat(outs_a), _concat(outs_c),
            () if routing is None else (jnp.moveaxis(routing, 0, 2),))
