"""The family with a sublayer a layer (Nemotron-H style) — functional JAX.

The decoder's forwards and head are ``models/llama.py``'s; the segments (a
pattern of layers stacked over its repeats, scanned where it repeats) are
``models/lfm2.py``'s idea, planned here over this family's kinds. A layer
``i`` is ONE sublayer, ``x <- x + f_i(rmsnorm_i(x))``, and ``layer_types``
says which:

- ``"mamba"``: a Mamba-2 mixer and no FFN. ``[z | xBC | dt] = u W_in``;
  ``xBC <- silu(causal depthwise conv of conv_taps taps over xBC + b)``;
  ``xBC = x [H, P] | B [G, N] | C [G, N]``; ``dt = softplus(dt + dt_bias)``
  [H]; ``a = -exp(A_log)`` [H]; a head ``h`` of group ``g = h // (H / G)``:
  ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_{g,t}`` (``S`` [P, N]),
  ``y_t = S_t C_{g,t} + D x_t``; ``y <- rmsnorm over each group of H P / G
  of (y * silu(z))`` with a weight; ``out = y W_out``. What a sequence
  carries from one call to the next, a layer: ``S`` [H P, N] and the last
  ``conv_taps - 1`` rows of the un-convolved ``xBC``, its STATE
  (``init_state``: ``{"ssm", "conv"}``). ``dt``, ``exp(dt a)``, the state
  update and everything between the two matmuls are float32; the state is
  STORED in the pool's dtype and rounded once where it is stored: a wave's
  end, a snapshot, a decode chunk's end.
  The recurrence comes in three forms that agree (tests/test_nemotron_h.py):
  ``ssm_recurrence`` (a ``lax.scan`` over positions: the plain forward's),
  ``ssm_segments`` (a ragged wave: each row cut into segments of up to
  ``SCAN_CHUNK`` tokens, a segment in its attention-like dual form, the
  state handed from a row's segment to its next, starting from the row's
  seed and kept at the row's last page end and last token; the same walk
  is ONE kernel a layer, ``ops/ssm_pallas.ssm_wave_scan``, where the call
  shows bf16 pools, lane-multiple widths and a TPU: ``stream_mixers``) and
  ``ssm_chunk_step`` (a decode step against the slots' state FROZEN for the
  chunk, as the pool is: ``ssm_state_read`` reads ``S_0`` of the slots
  that hold a sequence, a block ``(layer, slot)`` of the pool each, once a
  step, and of no other slot; the step adds what the chunk's own earlier
  steps put on top from buffers that the stack carries whole over the
  layers; and ``merge_state`` reads and writes ``S_K`` of the same slots
  once a chunk, in place. A dead slot's state is neither read nor
  written: 23 of 32 slots in ``nemotron3-nano.chat``'s mean step).
- ``"full_attention"``: GQA attention alone, heads of ``attn_head_dim``
  (wider than ``dim / n_heads``), no RoPE (``cfg.rope`` False:
  ``llama.rope_terms`` gives the identity), through the paged kernels every
  family shares.
- ``"moe"``: a routed FFN alone (``moe_ffn``): float32 sigmoid scores over
  ``n_experts``, top-k of scores + ``expert_bias``, gates the chosen
  scores over their sum (+ 1e-20) times ``routed_scaling_factor``;
  un-gated experts ``W_2 relu(W_1 u)^2`` of which the chip holds
  ``n_experts_held`` (``lfm2.moe_block(held=)``: the stream kernel or the
  loop), and one shared expert of ``shared_ffn_dim`` of the same form.

Random weights follow ``llama.random_dense`` but for: ``A_log = log U[1,
16]``, ``dt_bias`` the inverse softplus of ``exp U[log 1e-3, log 1e-1]``
(the published initialisers' ranges, so a step's decay ``exp(dt a)`` lies
strictly inside (0, 1) and a state remembers one to a thousand tokens),
``D`` and the gated norm's weight ``1 + 0.1 normal``, the conv bias
``0.1 normal`` (none a no-op), and a Mamba-2 layer's ``out_proj`` at 1/16
of the plain scale, for ``lfm2``'s reason: the mixer is cubic in its input
(``x B C``) and is not averaged over a context, and drawn plainly the
stack amplifies its own bf16 rounding past what a float32 reference is
held to. ``expert_bias`` is not drawn but FITTED where the weights are
made (``balance_expert_bias``), as the published one is trained: under
random weights a token's hidden state is mostly a part common to all
tokens (a relu² expert's output has a mean), so without it a layer sends
nearly every token to the same few experts, and how many of those the
chip holds (16 of 128) is the seed's.

**As laid out.** The chip keeps an array's last dimension in whole lanes
of 128, and its compiler copied every weight whose last dimension is none
(an expert's ``w_up`` [D, 1856], ``in_proj`` [D, 10304]: 6 GB of copies in
a decode program, ``benchmark/aot_rehearsal.py``, PR 50). So a last
dimension over 128 that is no lane multiple is kept at the next one
(``lanes_up``: 1920, 10368), the upper columns zero, and ``w_down``'s rows
with them: the same bytes in the chip's memory, and ``relu(0)^2 = 0``
changes no result.

Refused by name (``llama.refuse_state``): every path that cannot carry
this state.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..ops import ssm_pallas
from ..ops.layers import gqa_attention, rms_norm, write_kv_cache
from . import lfm2, llama
from .configs import ModelConfig

Params = Dict[str, Any]

EXPERT_MATRICES = ("w_up", "w_down")
GATE_EPS = 1e-20
SMALL_STD = 0.1
OUT_PROJ_BACK = 256          # fan-in factor of a Mamba-2 ``out_proj``
# tokens a segment of a ragged wave's scan: the published ``chunk_size``
SCAN_CHUNK = 128
HI = jax.lax.Precision.HIGHEST
f32 = jnp.float32


# ------------------------------------------------------------------ the plan


def layer_plan(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """``[(pattern of kinds, repeats)]``: ``lfm2.layer_plan``'s rule over
    this family's kinds (the period that leaves the fewest layer bodies).
    As published: ``M E M E M * E`` five times and a tail."""
    return lfm2.plan_periods(list(cfg.layer_types))


def lanes_up(n: int) -> int:
    """``n`` as the weights keep it in a last dimension: the next lane
    multiple where ``n`` is over a lane and is none."""
    return -(-n // llama.LANES) * llama.LANES if n > llama.LANES else n


routing_shape = lfm2.routing_shape


# ---------------------------------------------------------------------- init


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> Params:
    D, E, Eh = cfg.dim, cfg.n_experts, cfg.experts_held
    Fe, Fs = cfg.expert_ffn_dim or cfg.ffn_dim, cfg.shared_ffn_dim
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    H, Di, Cd = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim
    taps = cfg.conv_taps
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense(key, shape, fan_in):
        return llama.random_dense(key, shape, fan_in, dtype)

    def normal(key, shape, mean, std, dt=jnp.float32):
        return (mean + std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dt)

    def widened(w, axis: int):
        # zeros up to the lane multiple (module docstring, "as laid out")
        pad = lanes_up(w.shape[axis]) - w.shape[axis]
        return w if not pad else jnp.pad(
            w, [(0, pad if a == axis % w.ndim else 0)
                for a in range(w.ndim)])

    def layer(key, kind: str, n: int) -> Params:
        ks = jax.random.split(key, 10)
        lp: Params = {"norm": jnp.ones((n, D), dtype)}
        if kind == "mamba":
            lp["in_proj"] = widened(dense(ks[0], (n, D, Di + Cd + H), D), -1)
            lp["conv_w"] = dense(ks[1], (n, taps, Cd), taps)
            if cfg.conv_bias:
                lp["conv_b"] = normal(ks[2], (n, Cd), 0.0, SMALL_STD)
            lp["A_log"] = jnp.log(jax.random.uniform(
                ks[3], (n, H), jnp.float32, 1.0, 16.0))
            dt0 = jnp.exp(jax.random.uniform(
                ks[4], (n, H), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            lp["dt_bias"] = dt0 + jnp.log(-jnp.expm1(-dt0))
            lp["D"] = normal(ks[5], (n, H), 1.0, SMALL_STD)
            lp["gate_norm"] = normal(ks[6], (n, Di), 1.0, SMALL_STD, dtype)
            lp["out_proj"] = dense(ks[7], (n, Di, D), Di * OUT_PROJ_BACK)
        elif kind == "full_attention":
            lp["wq"] = dense(ks[0], (n, D, Hq * hd), D)
            lp["wk"] = dense(ks[1], (n, D, Hkv * hd), D)
            lp["wv"] = dense(ks[2], (n, D, Hkv * hd), D)
            lp["wo"] = dense(ks[3], (n, Hq * hd, D), Hq * hd)
        else:
            lp["router"] = dense(ks[0], (n, D, E), D)
            lp["expert_bias"] = jnp.zeros((n, E), jnp.float32)
            lp["w_up"] = widened(dense(ks[2], (n, Eh, D, Fe), D), -1)
            lp["w_down"] = widened(dense(ks[3], (n, Eh, Fe, D), Fe), -2)
            if Fs:
                lp["ws_up"] = widened(dense(ks[4], (n, D, Fs), D), -1)
                lp["ws_down"] = widened(dense(ks[5], (n, Fs, D), Fs), -2)
        return lp

    plan = layer_plan(cfg)
    seg_keys = jax.random.split(k_layers, len(plan))
    segments = []
    for (pattern, n), sk in zip(plan, seg_keys):
        lks = jax.random.split(sk, len(pattern))
        segments.append([layer(lk, kind, n)
                         for lk, kind in zip(lks, pattern)])
    params: Params = {"embed": dense(k_embed, (cfg.vocab_size, D), D),
                      "segments": segments,
                      "final_norm": jnp.ones((D,), dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k_head, (D, cfg.vocab_size), D)
    if cfg.n_routed_layers:
        params = balance_expert_bias(params, cfg, jax.random.randint(
            jax.random.fold_in(key, 1), (BALANCE_ROWS, BALANCE_LEN), 0,
            cfg.vocab_size))
    return params


def init_state(cfg: ModelConfig, rows: int,
               dtype: jnp.dtype = jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Zeroed Mamba-2 state, a row a slot or a row a snapshot:
    ``{"ssm": [L_m, rows, H P, N], "conv": [L_m, rows, taps - 1, conv
    dim]}``."""
    L = cfg.n_ssm_layers
    return {"ssm": jnp.zeros((L, rows, cfg.ssm_inner, cfg.ssm_state), dtype),
            "conv": jnp.zeros((L, rows, cfg.conv_taps - 1, cfg.ssm_conv_dim),
                              dtype)}


# ------------------------------------------------------- the Mamba-2 mixer


def _heads_of_groups(cfg: ModelConfig, a: jnp.ndarray) -> jnp.ndarray:
    """``a`` [..., G, N] -> [..., H, N]: each head its group's."""
    return jnp.repeat(a, cfg.ssm_heads // cfg.ssm_groups, axis=-2)


def ssm_recurrence(cfg: ModelConfig, xd, la, Bm, Cm, S0):
    """The plain recurrence over one call's positions. ``xd`` [B, T, H, P]
    (``dt x``), ``la`` [B, T, H] (``dt a``), ``Bm``, ``Cm`` [B, T, G, N],
    ``S0`` [B, H, P, N], all float32. Returns ``(y [B, T, H, P], S_T)``,
    ``y`` without the ``D x`` term."""
    Bh, Ch = _heads_of_groups(cfg, Bm), _heads_of_groups(cfg, Cm)

    def step(S, t):
        xd_t, la_t, b_t, c_t = t
        S = (jnp.exp(la_t)[..., None, None] * S
             + xd_t[..., None] * b_t[..., None, :])
        return S, jnp.sum(S * c_t[..., None, :], axis=-1)

    S, y = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (xd, la, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1), S


def _segment(cfg: ModelConfig, xd, la, Bm, Cm, S_in):
    """One segment of up to Q tokens of ONE row in the dual form. ``xd``
    [Q, H, P] and ``la`` [Q, H] are zero past the segment's live tokens,
    so the state stands still there. ``S_in`` [H, P, N]. Returns ``(y
    [Q, H, P], S_out)``: ``S_out`` the state after the last live token."""
    Q, H, P = xd.shape
    G, N = Bm.shape[1:]
    per = H // G
    cs = jnp.cumsum(la, axis=0)                            # [Q, H], <= 0
    j = jnp.arange(Q)
    # exp of a masked difference, never a masked exp: above the diagonal
    # the difference is positive and may overflow
    diff = cs[:, None, :] - cs[None, :, :]                 # [j, i, H]
    decay = jnp.exp(jnp.where((j[:, None] >= j[None, :])[..., None],
                              diff, -jnp.inf))
    scores = jnp.einsum("jgn,ign->jig", Cm, Bm, precision=HI)
    m = decay * jnp.repeat(scores, per, axis=-1)           # [j, i, H]
    y = jnp.einsum("jih,ihp->jhp", m, xd, precision=HI)
    Sg = S_in.reshape(G, per * P, N)
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "jgn,gmn->jgm", Cm, Sg, precision=HI).reshape(Q, H, P)
    to_end = jnp.exp(cs[-1][None] - cs)                    # [Q, H]
    built = jnp.einsum("igm,ign->gmn",
                       (xd * to_end[..., None]).reshape(Q, G, per * P), Bm,
                       precision=HI)
    S_out = jnp.exp(cs[-1])[:, None, None] * S_in + built.reshape(H, P, N)
    return y, S_out


def ssm_segments(cfg: ModelConfig, xd, la, Bm, Cm, starts, lens, end_lens,
                 layer, src, slots, dst, pools):
    """The scan over a ragged wave's packed stream, the rows' states read
    from and written to the pools IN PLACE. ``xd`` [W, H, P], ``la``
    [W, H], ``Bm``, ``Cm`` [W, G, N] float32, stream order; row ``r`` is
    the ``lens[r]`` tokens from ``starts[r]`` (0: a dead row).
    ``end_lens[r]`` (0: none) is how many of the row's tokens of this call
    lie up to and with its last page end. ``pools = (slot [L_m, B, H P,
    N], snap [L_m, 1 + S, H P, N])``, of which this is layer ``layer``:
    row ``r`` starts from snapshot ``src[r]`` (> 0), from its slot's own
    state (< 0) or from zeros (0); its state after its last token goes to
    slot ``slots[r]`` (``B``: nowhere) and its state after its last page
    end to snapshot ``dst[r]`` (0, the bin: nowhere). Returns ``(y [W, H,
    P], pools)``. A wave's states are as large as its weights' layer: they
    are never stacked over rows or layers beside the pools.

    A row is cut at its last page end and each part into segments of up to
    ``SCAN_CHUNK`` tokens; the live segments are walked in stream order by
    one loop that carries the running row's state, so the walk costs what
    the wave's rows cost and nothing for the dead ones."""
    W, H, P = xd.shape
    N = Bm.shape[-1]
    Q = SCAN_CHUNK
    n_slots = pools[0].shape[1]
    len1 = jnp.minimum(end_lens, lens).astype(jnp.int32)
    len2 = lens.astype(jnp.int32) - len1
    n1, n2 = -(-len1 // Q), -(-len2 // Q)
    upto = jnp.cumsum(n1 + n2)                             # [R]
    pad = lambda a: jnp.pad(a, ((0, Q),) + ((0, 0),) * (a.ndim - 1))
    xd_p, la_p, B_p, C_p = pad(xd), pad(la), pad(Bm), pad(Cm)
    to = jnp.where(end_lens > 0, dst, 0)

    def row_of(pool, i):
        return jax.lax.dynamic_slice(pool, (layer, i, 0, 0),
                                     (1, 1, H * P, N))

    def put(pool, row, i):
        return jax.lax.dynamic_update_slice(pool, row, (layer, i, 0, 0))

    def body(s, carry):
        S, y, slot, snap = carry
        r = jnp.searchsorted(upto, s, side="right").astype(jnp.int32)
        k = s - (upto[r] - n1[r] - n2[r])
        in1 = k < n1[r]
        off = jnp.where(in1, k * Q, len1[r] + (k - n1[r]) * Q)
        live = jnp.minimum(Q, jnp.where(in1, len1[r], lens[r]) - off)
        at = starts[r] + off
        mask = (jnp.arange(Q) < live).astype(f32)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at, Q, axis=0)
        mine = jnp.clip(slots[r], 0, n_slots - 1)
        seed = jnp.where(
            src[r] > 0, row_of(snap, jnp.maximum(src[r], 0)),
            jnp.where(src[r] < 0, row_of(slot, mine), 0)).astype(f32)
        S_in = jnp.where(k == 0, seed.reshape(H, P, N), S)
        y_seg, S = _segment(cfg, cut(xd_p) * mask[:, None, None],
                            cut(la_p) * mask[:, None], cut(B_p), cut(C_p),
                            S_in)
        # whole: what lies past the live tokens is the next segments', and
        # they are written after this one
        y = jax.lax.dynamic_update_slice_in_dim(y, y_seg, at, axis=0)
        kept = S.reshape(1, 1, H * P, N).astype(slot.dtype)
        ends_part = in1 & (k == n1[r] - 1)
        # a row that ends AT its last page end has no second part
        ends_row = (k == n1[r] + n2[r] - 1) & (slots[r] < n_slots)
        slot = put(slot, jnp.where(ends_row, kept, row_of(slot, mine)), mine)
        snap = put(snap, kept, jnp.where(ends_part, to[r], 0))
        return S, y, slot, snap

    _, y, slot, snap = jax.lax.fori_loop(
        0, upto[-1], body,
        (jnp.zeros((H, P, N), f32), jnp.zeros((W + Q, H, P), f32), *pools))
    return y[:W], (slot, snap)


def wave_segments(first: int, tokens: int, page_size: int) -> int:
    """How many live segments a wave's scan walks, a layer, for a row of
    ``tokens`` new tokens from position ``first``: the row cut at its last
    page end and each part into segments of ``SCAN_CHUNK``
    (``ssm_segments``' rule, on the host's integers: the engine counts a
    wave's walk from its plan, ``ssm_wave_segments``)."""
    to_end = max((first + tokens) // page_size * page_size - first, 0)
    return -(-to_end // SCAN_CHUNK) + -(-(tokens - to_end) // SCAN_CHUNK)


def ssm_state_read(cfg: ModelConfig, pool, layer, Cm, rows, n_live):
    """The frozen state's part of a decode step: ``y0`` [B, H, P] float32,
    ``y0[b] = S_0[layer, b] C[b]`` for the slots ``rows[:n_live]``
    (``paged_kv.live_row_list``: the slots that hold a sequence) and ZEROS
    for every other slot, whose row of the step is masked downstream as
    every dead row's is. ``pool`` [L_m, B, H P, N] is the slots' state
    where it lies, ``Cm`` [B, G, N] float32. The live rows' blocks of
    layer ``layer`` are read and nothing else of the pool: by the kernel
    ``ops/ssm_pallas.state_read`` where it takes the call (bf16 state,
    lane-multiple widths, a TPU), else by a loop of one block a live row
    with the same contract. No form gathers the rows into a new array."""
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    B_, N = pool.shape[1], pool.shape[3]
    if ssm_pallas.takes(pool, cfg.ssm_groups):
        return ssm_pallas.state_read(
            pool, layer, Cm, rows, n_live,
            interpret=jax.default_backend() != "tpu").reshape(B_, H, P)
    Ch = _heads_of_groups(cfg, Cm)                         # [B, H, N]

    def row(i, y0):
        b = rows[i]
        S = jax.lax.dynamic_slice(pool, (layer, b, 0, 0), (1, 1, H * P, N))
        y = jnp.sum(S.reshape(H, P, N).astype(f32) * Ch[b][:, None, :],
                    axis=-1)
        return jax.lax.dynamic_update_slice(y0, y[None], (b, 0, 0))

    return jax.lax.fori_loop(0, n_live, row, jnp.zeros((B_, H, P), f32))


# SWARMDB_KERNCHECK=1 (obs/kerncheck.py; off in every cell): every concrete
# call holds the state-read kernel to the batch-wide form, as ``ops/layers``
# has the paged decode kernel held. Flag off, the plain function.
if os.environ.get("SWARMDB_KERNCHECK", "0") == "1":
    from ..obs.kerncheck import checked_ssm_state_read

    ssm_state_read = checked_ssm_state_read(ssm_state_read)


def ssm_chunk_step(cfg: ModelConfig, xd, la, Bm, Cm, y0, bufs, layer, step):
    """One decode step of a chunk against the slots' FROZEN state. ``xd``
    [B, H, P], ``la`` [B, H], ``Bm``, ``Cm`` [B, G, N] float32, this
    step's; ``y0`` [B, H, P] the state's part ``S_0 C_j`` (``ssm_state_
    read``); ``bufs = (hxd [L_m, B, K, H, P], hB [L_m, B, K, G, N], hcs
    [L_m, B, K, H])`` the chunk's own ``dt x``, ``B`` and running sum of
    ``dt a`` so far, WHOLE over the Mamba-2 layers, of which this is layer
    ``layer``. Returns ``(y [B, H, P], bufs)`` with this step written at
    ``(layer, :, step)`` and nothing else of the buffers touched: ``y_j =
    exp(cs_j) S_0 C_j + sum_{i <= j} exp(cs_j - cs_i) (C_j . B_i) dt_i
    x_i``. The layer's ``[B, K, ...]`` is read through an index."""
    hxd, hB, hcs = bufs
    K, H = hcs.shape[2:]
    prev = jnp.where(step > 0, jax.lax.dynamic_slice(
        hcs, (layer, 0, jnp.maximum(step - 1, 0), 0),
        (1, la.shape[0], 1, H))[0, :, 0], 0.0)
    cs = prev + la                                         # [B, H]
    put = lambda h, v: jax.lax.dynamic_update_slice(
        h, v[None, :, None].astype(h.dtype),
        (layer, 0, step) + (0,) * (v.ndim - 1))
    hxd, hB, hcs = put(hxd, xd), put(hB, Bm), put(hcs, cs)
    mine = lambda h: jax.lax.dynamic_index_in_dim(h, layer, keepdims=False)
    seen = jnp.arange(K) <= step
    w = jnp.exp(jnp.where(seen[None, :, None], cs[:, None] - mine(hcs),
                          -jnp.inf))
    cb = jnp.einsum("bgn,bkgn->bkg", Cm, mine(hB), precision=HI)
    w = w * jnp.repeat(cb, H // cfg.ssm_groups, axis=-1)   # [B, K, H]
    y = jnp.exp(cs)[..., None] * y0 + jnp.einsum(
        "bkh,bkhp->bhp", w, mine(hxd), precision=HI)
    return y, (hxd, hB, hcs)


def merge_state(state, bufs, rows, n_live):
    """The slots' state after a chunk, ``{"ssm", "conv"}`` over the
    Mamba-2 layers, from the chunk's buffers ``bufs = (hz, hxd, hB, hcs)``
    (``init_chunk_state``). Only the slots ``rows[:n_live]``
    (``paged_kv.live_row_list`` of the chunk's table) are read and
    written, IN PLACE, every layer of them: ``S_K = exp(cs_K) S_0 + sum_i
    exp(cs_K - cs_i) dt_i x_i (x) B_i``, float32, rounded once, and the
    last ``taps - 1`` rows of the un-convolved ``xBC``
    (``lfm2.merge_state``). Every other slot's state stays bit for bit
    what it was: nobody reads it before an admission's wave writes it
    (``ssm_segments``). The kernel ``ops/ssm_pallas.state_merge`` where it
    takes the pool (as ``ssm_state_read``), else a loop of one live row's
    layers a trip."""
    hz, hxd, hB, hcs = bufs                                # [L, B, K, ...]
    L, B_, K, H, P = hxd.shape
    G, N = hB.shape[-2:]
    ssm, conv = state["ssm"], state["conv"]
    last = hcs[:, :, -1]                                   # [L, B, H]
    # what the chunk's steps put on top, each carried to the chunk's end
    w = hxd.reshape(L, B_, K, H * P) * jnp.repeat(
        jnp.exp(last[:, :, None] - hcs), P, axis=-1)
    if ssm_pallas.takes(ssm, G, K):
        ssm = ssm_pallas.state_merge(
            ssm, jnp.exp(last), w, hB, rows, n_live,
            interpret=jax.default_backend() != "tpu")
    else:
        def row(i, ssm):
            b = rows[i]
            of = lambda a: jax.lax.dynamic_index_in_dim(a, b, 1,
                                                        keepdims=False)
            built = jnp.einsum(
                "lkgm,lkgn->lgmn", of(w).reshape(L, K, G, (H // G) * P),
                of(hB), precision=HI).reshape(L, H * P, N)
            new = (jnp.repeat(jnp.exp(of(last)), P, axis=-1)[..., None]
                   * of(ssm).astype(f32) + built)
            return jax.lax.dynamic_update_slice(
                ssm, new[:, None].astype(ssm.dtype), (0, b, 0, 0))

        ssm = jax.lax.fori_loop(0, n_live, row, ssm)
    live = jnp.zeros((B_,), bool).at[rows].set(jnp.arange(B_) < n_live)
    return {"ssm": ssm,
            "conv": jnp.where(live[None, :, None, None],
                              lfm2.merge_state(conv, hz), conv)}


def mamba_token_mixer(cfg: ModelConfig, history, recurrence, flat=False):
    """The Mamba-2 mixer as a token mixer: ``token_mixer(h [B, T, D], lp,
    layer, aux) -> (out [B, T, D], kept, aux)``. ``layer`` is the layer's
    index among the Mamba-2 layers and all a layer is handed: what a
    forward keeps a layer (a wave's seed rows, the slots' state, a
    chunk's buffers) it keeps WHOLE over the layers and reads at
    ``layer``. ``aux`` is what the stack carries from layer to layer
    beside ``x`` for the parts that are written in place
    (``ssm_segments``' pools, a chunk's buffers). ``history(xBC, layer,
    aux) -> (earlier, conv_out, aux)`` is the forward's, where a token's
    earlier ``xBC`` come from (``lfm2.conv_token_mixer``'s, with the
    carry); ``recurrence(xd, la, Bm, Cm, layer, aux) -> (y [B, T, H, P],
    ssm_out, aux)`` its form of the scan; a ``flat`` one takes ``(the
    conv's output [B, T, x | B | C], dt [B, T, H])`` in ``xd``'s place
    and gives ``y`` as ``[B, T, H P]``, the heads side by side in the
    lanes as the projections have them (``ssm_pallas.ssm_wave_scan``,
    which cuts its windows out of the conv's rows and makes ``dt x``
    itself: nothing is sliced or laid out anew for it or after it).
    ``kept = (conv_out, ssm_out)``, stacked over the layers by the
    stack."""
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    Di, Cd = cfg.ssm_inner, cfg.ssm_conv_dim

    def token_mixer(h, lp, layer, aux):
        B_, T = h.shape[0], h.shape[1]
        zxd = jnp.einsum("btd,de->bte", h, lp["in_proj"],
                         preferred_element_type=f32)
        if flat:
            # made once: with no loop between its readers the chip's
            # compiler kept it in fast memory and made the whole matmul
            # again for each of its four readers (PERF.md section 6, PR 53)
            zxd = jax.lax.optimization_barrier(zxd)
        z, dt = zxd[..., :Di], zxd[..., Di + Cd:Di + Cd + H]
        # rounded to the stream's dtype where it is made: it is what the
        # state holds, and a token reads the same rows from its call and
        # from a state
        xbc = zxd[..., Di:Di + Cd].astype(h.dtype)
        earlier, conv_out, aux = history(xbc, layer, aux)
        w = lp["conv_w"].astype(f32)          # [taps, Cd]; w[-1] takes t
        c = w[-1] * xbc
        for i, e in enumerate(earlier):
            c = c + w[-2 - i] * e
        if "conv_b" in lp:
            c = c + lp["conv_b"].astype(f32)
        c = jax.nn.silu(c)
        x = c[..., :Di].reshape(B_, T, H, P)
        Bm = c[..., Di:Di + G * N].reshape(B_, T, G, N)
        Cm = c[..., Di + G * N:].reshape(B_, T, G, N)
        dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))   # [B, T, H]
        la = -jnp.exp(lp["A_log"].astype(f32)) * dt
        if flat:
            y, ssm_out, aux = recurrence((c, dt), la, Bm, Cm, layer, aux)
            y = y + jnp.repeat(lp["D"].astype(f32), P) * c[..., :Di]
        else:
            y, ssm_out, aux = recurrence(x * dt[..., None], la, Bm, Cm,
                                         layer, aux)
            y = y + lp["D"].astype(f32)[:, None] * x
        y = (y.reshape(B_, T, Di) * jax.nn.silu(z)).reshape(
            B_, T, G, Di // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.norm_eps)
        y = (y.reshape(B_, T, Di) * lp["gate_norm"].astype(f32)
             ).astype(h.dtype)
        return (jnp.einsum("bte,ed->btd", y, lp["out_proj"]),
                (conv_out, ssm_out), aux)

    return token_mixer


# ------------------------------------------------------------ the routed FFN


def scores(h: jnp.ndarray, router_w: jnp.ndarray) -> jnp.ndarray:
    """The router's float32 sigmoid scores ``[N, n_experts]``."""
    return jax.nn.sigmoid(jnp.einsum("nd,de->ne", h.astype(f32),
                                     router_w.astype(f32), precision=HI))


def route(cfg: ModelConfig, h: jnp.ndarray, router_w: jnp.ndarray,
          bias: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(chosen [N, k] int32, gates [N, k] float32)``: float32 sigmoid
    scores, top-k of scores + bias (the bias chooses and does not gate),
    the chosen scores over their sum, times ``routed_scaling_factor``."""
    s = scores(h, router_w)
    _, chosen = jax.lax.top_k(s + bias.astype(f32), cfg.experts_per_token)
    g = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, (g / (jnp.sum(g, axis=-1, keepdims=True) + GATE_EPS)
                    * cfg.routed_scaling_factor)


def moe_ffn(cfg: ModelConfig, live):
    """``ffn(h, lp, repeat) -> (y, routing)``: the shared expert plus the
    dropless sum over the chosen experts this chip holds."""
    held = (cfg.first_held_expert, cfg.experts_held)

    def ffn(h, lp, repeat):
        y, routing = lfm2.moe_block(
            h, lp, cfg.experts_per_token, live, repeat * cfg.experts_held,
            chosen_gates=lambda xf, lp: route(cfg, xf, lp["router"],
                                              lp["expert_bias"]),
            held=held)
        if "ws_up" in lp:
            y = (y.astype(f32) + lfm2.expert_ffn(
                h, None, lp["ws_up"], lp["ws_down"])).astype(h.dtype)
        return y, routing

    return ffn


# what ``balance_expert_bias`` fits over: sequences of tokens drawn from the
# vocabulary, and the rounds of ``fit_bias`` with its first and last step
BALANCE_ROWS, BALANCE_LEN = 32, 128
BALANCE_ROUNDS, BALANCE_STEPS = 256, (0.1, 0.0005)


def fit_bias(cfg: ModelConfig, s: jnp.ndarray) -> jnp.ndarray:
    """The selection bias ``[n_experts]`` under which the top-k of ``s +
    bias`` takes every expert equally often over the rows of ``s`` [N,
    n_experts]: the rule the published ``e_score_correction_bias`` is
    trained by (an expert chosen less often than its share is raised, one
    chosen more often lowered), here by the share it is off and with a
    step that shrinks."""
    N, E = s.shape
    even = N * cfg.experts_per_token / E
    first, last = BALANCE_STEPS
    shrink = (last / first) ** (1.0 / (BALANCE_ROUNDS - 1))

    def round_(i, b):
        _, chosen = jax.lax.top_k(s + b, cfg.experts_per_token)
        load = jnp.zeros((E,), f32).at[chosen.reshape(-1)].add(1.0)
        return b + first * shrink ** i * jnp.clip((even - load) / even,
                                                  -1.0, 1.0)

    return jax.lax.fori_loop(0, BALANCE_ROUNDS, round_, jnp.zeros((E,), f32))


def fitted_bias(params: Params, cfg: ModelConfig,
                tokens: jnp.ndarray) -> jnp.ndarray:
    """Every routed layer's fitted bias ``[L_routed, n_experts]`` over the
    sequences ``tokens`` [B, T]: ONE plain forward in which each routed
    layer fits its bias (``fit_bias``) on the rows as the layers before
    it, already balanced, left them, and routes by it."""
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
    routed = moe_ffn(cfg, None)

    def fitting(h, lp, repeat):
        bias = fit_bias(cfg, scores(h.reshape(-1, h.shape[-1]),
                                    lp["router"]))
        y, _routing = routed(h, {**lp, "expert_bias": bias}, repeat)
        # in the routing's place: ``run_layers`` stacks it over the layers
        return y, bias[None, None]

    def mixer(q, k, v, kv):
        ck, cv = write_kv_cache(*kv, k, v, positions)
        return gqa_attention(q, ck, cv, positions), (ck, cv)

    x = params["embed"][tokens]
    cos, sin = llama.rope_terms(cfg, positions)
    mamba_tm, aux = whole_mixers(cfg)
    out = run_layers(
        params, cfg, x, llama.attention_token_mixer(cfg, cos, sin, mixer),
        llama.init_kv_cache(cfg, *tokens.shape, x.dtype), mamba_tm,
        aux=aux, ffn=fitting)
    return out[3][0][0, 0]


def balance_expert_bias(params: Params, cfg: ModelConfig,
                        tokens: jnp.ndarray) -> Params:
    """``params`` with every routed layer's ``expert_bias`` set to
    ``fitted_bias`` over ``tokens``."""
    bias = jax.jit(fitted_bias, static_argnums=1)(params, cfg, tokens)
    at, segments = 0, []
    for (pattern, n), seg in zip(layer_plan(cfg), params["segments"]):
        routed = [i for i, kind in enumerate(pattern) if kind == "moe"]
        mine = bias[at:at + n * len(routed)].reshape(n, len(routed),
                                                     cfg.n_experts)
        at += n * len(routed)
        seg = list(seg)
        for j, i in enumerate(routed):
            seg[i] = {**seg[i], "expert_bias": mine[:, j]}
        segments.append(seg)
    return {**params, "segments": segments}


# ------------------------------------------------------------------ the stack


def run_layers(params: Params, cfg: ModelConfig, x: jnp.ndarray, attn_tm,
               attn_ops, mamba_tm, live=None, aux=None, ffn=None):
    """``x`` through every layer. ``attn_tm`` and ``mamba_tm`` are the
    forward's token mixers (``llama.attention_token_mixer``,
    ``mamba_token_mixer``). ``attn_ops`` is what attention takes a layer,
    with a leading axis over the layers that attend: a segment's share is
    sliced out and scanned. A Mamba-2 layer is handed its INDEX among the
    Mamba-2 layers and nothing sliced: ``aux`` is carried through every
    layer, scanned or not, beside ``x``, and the mixer reads and writes
    its layer's part of it in place (``mamba_token_mixer``), so nothing
    of a Mamba-2 layer's is sliced in, stacked or concatenated a call
    but what the mixer itself keeps (a wave's few conv rows). Returns
    ``(x, attention outs, (mamba outs, aux), (routing [B, T, L_routed,
    k],))``, the outs stacked over their layers; ``ffn`` takes
    ``moe_ffn``'s place (``balance_expert_bias``)."""
    ffn = ffn or moe_ffn(cfg, live)
    a0 = m0 = 0
    outs_a, outs_m, outs_r = [], [], []
    for (pattern, n), seg in zip(layer_plan(cfg), params["segments"]):
        na, nm = pattern.count("full_attention"), pattern.count("mamba")
        # a routed layer's expert matrices stay out of the scan, as in
        # ``lfm2.run_layers``: what a scan slices it copies
        experts = [{k: lp[k].reshape((-1,) + lp[k].shape[2:])
                    for k in EXPERT_MATRICES} if kind == "moe" else {}
                   for kind, lp in zip(pattern, seg)]
        seg = [{k: v for k, v in lp.items()
                if not (kind == "moe" and k in EXPERT_MATRICES)}
               for kind, lp in zip(pattern, seg)]

        def of_segment(ops, lo, per):
            return jax.tree.map(
                lambda a: a[lo:lo + n * per].reshape((n, per) + a.shape[1:]),
                ops)

        def body(carry, scanned, pattern=pattern, experts=experts, m0=m0,
                 nm=nm):
            x, aux = carry
            lps, a_r, repeat = scanned
            ja = jm = 0
            a_out, m_out, r_out = [], [], []
            for kind, lp, big in zip(pattern, lps, experts):
                h = rms_norm(x, lp["norm"], cfg.norm_eps)
                if kind == "moe":
                    y, routing = ffn(h, {**lp, **big}, repeat)
                    r_out.append(routing)
                elif kind == "mamba":
                    y, out, aux = mamba_tm(h, lp, m0 + repeat * nm + jm, aux)
                    m_out.append(out)
                    jm += 1
                else:
                    y, out = attn_tm(
                        h, lp, jax.tree.map(lambda a, j=ja: a[j], a_r))
                    a_out.append(out)
                    ja += 1
                x = x + y
            return (x, aux), (lfm2._stack(a_out), lfm2._stack(m_out),
                              lfm2._stack(r_out))

        scanned = (seg, of_segment(attn_ops, a0, na),
                   jnp.arange(n, dtype=jnp.int32))
        if n == 1:
            (x, aux), outs = body((x, aux),
                                  jax.tree.map(lambda a: a[0], scanned))
            outs = jax.tree.map(lambda a: a[None], outs)
        else:
            (x, aux), outs = jax.lax.scan(body, (x, aux), scanned)
        flat = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
            outs)
        outs_a.append(flat[0])
        outs_m.append(flat[1])
        outs_r.append(flat[2])
        a0, m0 = a0 + n * na, m0 + n * nm
    routing = lfm2._concat(outs_r)
    return (x, lfm2._concat(outs_a), (lfm2._concat(outs_m), aux),
            () if routing is None else (jnp.moveaxis(routing, 0, 2),))


# ------------------------------------------------- what each forward brings


def _carried(history, ops_at):
    """An ``lfm2`` history, which keeps nothing in the stack's carry, as
    ``mamba_token_mixer`` calls one; ``ops_at(layer)`` is what it takes a
    layer."""
    return lambda z, layer, aux: (*history(z, ops_at(layer)), aux)


def whole_mixers(cfg: ModelConfig):
    """``llama.forward``'s ``(Mamba-2 token mixer, aux)``
    (``llama.run_stack``'s ``mamba``): a whole sequence from position 0,
    nothing before it, the plain recurrence."""
    def recurrence(xd, la, Bm, Cm, _layer, aux):
        S0 = jnp.zeros((xd.shape[0], cfg.ssm_heads, cfg.ssm_head_dim,
                        cfg.ssm_state), f32)
        return (*ssm_recurrence(cfg, xd, la, Bm, Cm, S0), aux)

    history = _carried(lfm2.history_whole(cfg.conv_taps - 1),
                       lambda _layer: None)
    return mamba_token_mixer(cfg, history, recurrence), None


def stream_mixers(cfg: ModelConfig, seed, tok_row, tok_pos, starts, lens,
                  live, page_size: int):
    """The Mamba-2 mixer of a ragged wave and what the stack carries for
    it. ``seed = {"conv": [L_m, R, taps - 1, conv dim], "ssm": (src,
    slots, dst, slot pool, snapshot pool)}``: each row's conv rows before
    its first token of the call (read at the layer's index), and for the
    large part of the state where to read it from and write it to
    (``ssm_segments``; the pools are carried through the layers and
    written in place). Keeps, a layer, the conv rows ``(row, end)`` after
    each row's last token and after its last page end of this call.
    Returns ``((token mixer, pools), end_lens [R])``:
    ``llama.run_stack``'s ``mamba`` and what the forward reports."""
    R = starts.shape[0]
    row = jnp.clip(tok_row, 0, R - 1)
    ends = live & ((tok_pos + 1) % page_size == 0)
    # a row's tokens of this call up to and with its last page end
    o = jnp.arange(tok_row.shape[0], dtype=jnp.int32) - starts[row]
    end_lens = jnp.zeros((R + 1,), jnp.int32).at[
        jnp.where(ends, row, R)].max(o + 1)[:R]
    at = starts + jnp.maximum(end_lens - 1, 0)
    history = _carried(
        lfm2.history_stream(cfg.conv_taps - 1, tok_row, starts, lens, at),
        lambda layer: jax.lax.dynamic_index_in_dim(seed["conv"], layer,
                                                   keepdims=False))
    src, slots, dst, *pools = seed["ssm"]
    W = tok_row.shape[0]
    # which form scans the wave is read from the call, as the decode's
    # state passes are (``ssm_state_read``): the kernel ``ops/ssm_pallas.
    # ssm_wave_scan`` where it takes the pools and the stream (bf16 state,
    # lane-multiple widths, a TPU), over a table of the live segments made
    # HERE, once a wave, for all the layers; else ``ssm_segments``
    by_kernel = ssm_pallas.takes_wave(
        pools, (W, cfg.ssm_heads, cfg.ssm_head_dim), cfg.ssm_groups,
        SCAN_CHUNK)
    if by_kernel:
        table, n_live = ssm_pallas.wave_segment_table(
            starts, lens, end_lens, src, slots, dst, pools[0].shape[1], W)

        def recurrence(xbc_dt, la, _Bm, _Cm, layer, pools):
            xbc, dt = xbc_dt
            y, *pools = ssm_pallas.ssm_wave_scan(
                xbc[0], dt[0], la[0], table, n_live, layer, *pools,
                interpret=jax.default_backend() != "tpu")
            return y[None], None, tuple(pools)
    else:
        def recurrence(xd, la, Bm, Cm, layer, pools):
            y, pools = ssm_segments(cfg, xd[0], la[0], Bm[0], Cm[0], starts,
                                    lens, end_lens, layer, src, slots, dst,
                                    pools)
            return y[None], None, pools

    return ((mamba_token_mixer(cfg, history, recurrence, flat=by_kernel),
             tuple(pools)), end_lens)


def chunk_mixers(cfg: ModelConfig, state, bufs, step, live_rows):
    """One decode step of a chunk as ``llama.run_stack``'s ``mamba``:
    ``(token mixer, bufs)``. ``state`` is the slots' ``{"ssm", "conv"}``,
    FROZEN for the chunk and left where it lies: of ``state["ssm"]`` a
    layer reads the blocks ``(layer, b)`` of the live slots ``b`` alone
    (``live_rows = (rows, n_live)``, ``paged_kv.live_row_list`` of the
    step's table; ``ssm_state_read``), of ``state["conv"]`` its own three
    rows a slot. ``bufs = (hz, hxd, hB, hcs)`` are the chunk's own so far
    (``init_chunk_state``), each WHOLE over the Mamba-2 layers: they are
    the stack's carry, a layer writes this step's row at ``(layer, :,
    step)`` and reads its ``[B, K, ...]`` through an index, and nothing
    of them is sliced in or stacked out a step. A dead slot's row of the
    step is computed from zeros in the state's place."""
    n_state = cfg.conv_taps - 1
    rows, n_live = live_rows

    def history(z, layer, bufs):
        hz = bufs[0]
        B_, _, Cd = z.shape

        def tap(i):
            # the un-convolved row ``i + 1`` positions back: the chunk's
            # own where it reaches that far, else the slot's state's
            p = step - 1 - i
            own = jax.lax.dynamic_slice(
                hz, (layer, 0, jnp.maximum(p, 0), 0), (1, B_, 1, Cd))[0]
            old = jax.lax.dynamic_slice(
                state["conv"], (layer, 0, jnp.clip(n_state + p, 0,
                                                   n_state - 1), 0),
                (1, B_, 1, Cd))[0]
            return jnp.where(p >= 0, own.astype(z.dtype),
                             old.astype(z.dtype))

        earlier = [tap(i) for i in range(n_state)]
        hz = jax.lax.dynamic_update_slice(hz, z[None].astype(hz.dtype),
                                          (layer, 0, step, 0))
        return earlier, None, (hz, *bufs[1:])

    def recurrence(xd, la, Bm, Cm, layer, bufs):
        y0 = ssm_state_read(cfg, state["ssm"], layer, Cm[:, 0], rows, n_live)
        y, ssm_bufs = ssm_chunk_step(cfg, xd[:, 0], la[:, 0], Bm[:, 0],
                                     Cm[:, 0], y0, bufs[1:], layer, step)
        return y[:, None], None, (bufs[0], *ssm_bufs)

    return mamba_token_mixer(cfg, history, recurrence), tuple(bufs)


def init_chunk_state(cfg: ModelConfig, batch: int, chunk: int, dtype):
    """A chunk's own buffers over the Mamba-2 layers: the un-convolved
    ``xBC`` (the pool's dtype, as the state's rows), and ``dt x``, ``B``
    and the running ``dt a`` in float32."""
    L = cfg.n_ssm_layers
    return (jnp.zeros((L, batch, chunk, cfg.ssm_conv_dim), dtype),
            jnp.zeros((L, batch, chunk, cfg.ssm_heads, cfg.ssm_head_dim),
                      f32),
            jnp.zeros((L, batch, chunk, cfg.ssm_groups, cfg.ssm_state), f32),
            jnp.zeros((L, batch, chunk, cfg.ssm_heads), f32))
