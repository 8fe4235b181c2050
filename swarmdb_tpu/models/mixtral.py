"""Mixtral-style sparse Mixture-of-Experts family — functional JAX.

Same skeleton as ``models/llama.py`` (stacked layers + lax.scan, slot KV
cache, GQA attention with per-row positions) with the dense FFN replaced by
a top-k routed MoE block. Two numerically-equivalent dispatch forms:

- ``einsum``: the classic capacity-based one-hot dispatch (router -> top-k
  -> position-in-expert via cumsum -> [N, E, C] dispatch/combine tensors ->
  expert-major einsums). This is the GSPMD-native form: with tokens sharded
  over 'data' and expert weights over an 'expert' mesh axis, XLA lowers the
  dispatch/combine einsums to all-to-alls over ICI (SURVEY §2.4 EP row;
  BASELINE config 4 — Mixtral-8x7B tool-use backend). It is also ruinously
  expensive off the EP path: the [N, k, E, C] intermediates grow with
  N^2 (C ∝ N), and at a [16, 256] prefill the dispatch einsums cost ~10x
  the expert matmuls themselves (PROFILE r6: the 5.6x tooluse gap was
  almost entirely this term — 1766 ms vs 24 ms per block on the CPU A/B).
- ``scatter``: same routing decisions (same capacity, same overflow drops,
  same gates) realized as a token scatter into per-expert queues and a
  gather back — O(N·k·D) data movement, no one-hot tensors. Used on
  single-device / pure-DP engines; selected by default
  (SWARMDB_MOE_DISPATCH overrides; ``parallel/serving`` pins ``einsum``
  whenever the expert axis is actually sharded).

Tokens over capacity are dropped (contribute zero; the residual connection
carries them) in both forms.

No reference counterpart: the reference has no model code (SURVEY §2.4).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.layers import (
    gqa_attention,
    gqa_attention_chunked,
    qkv_proj,
    rms_norm,
    rope_cos_sin,
    write_kv_cache,
)
from .configs import ModelConfig
from .llama import random_dense

Params = Dict[str, Any]
KVCache = Tuple[jnp.ndarray, jnp.ndarray]

DEFAULT_CAPACITY_FACTOR = 2.0


# ---------------------------------------------------------------------- init


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype = jnp.bfloat16
) -> Params:
    if not cfg.is_moe:
        raise ValueError(f"{cfg.name!r} is dense; use models.llama")
    L, D, F, E = cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.n_experts
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense(key, shape, fan_in):
        return random_dense(key, shape, fan_in, dtype)

    ks = jax.random.split(k_layers, 9)
    params: Params = {
        "embed": dense(k_embed, (cfg.vocab_size, D), D),
        "layers": {
            "attn_norm": jnp.ones((L, D), dtype),
            "wq": dense(ks[0], (L, D, Hq * hd), D),
            "wk": dense(ks[1], (L, D, Hkv * hd), D),
            "wv": dense(ks[2], (L, D, Hkv * hd), D),
            "wo": dense(ks[3], (L, Hq * hd, D), Hq * hd),
            "mlp_norm": jnp.ones((L, D), dtype),
            "router": dense(ks[4], (L, D, E), D),
            "w_gate": dense(ks[5], (L, E, D, F), D),
            "w_up": dense(ks[6], (L, E, D, F), D),
            "w_down": dense(ks[7], (L, E, F, D), F),
        },
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": dense(k_head, (D, cfg.vocab_size), D),
    }
    return params


def param_specs(cfg: ModelConfig, model_axis: str = "model",
                expert_axis: str = "expert") -> Params:
    """TP over ``model_axis`` + EP over ``expert_axis``: attention is
    Megatron-sharded as in Llama; expert weights shard their leading expert
    dim so each device owns E/ep experts, and the dispatch/combine einsums
    become all-to-alls."""
    m, e = model_axis, expert_axis
    return {
        "embed": P(m, None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, m),
            "wk": P(None, None, m),
            "wv": P(None, None, m),
            "wo": P(None, m, None),
            "mlp_norm": P(None, None),
            "router": P(None, None, None),
            "w_gate": P(None, e, None, m),
            "w_up": P(None, e, None, m),
            "w_down": P(None, e, m, None),
        },
        "final_norm": P(None),
        "lm_head": P(None, m),
    }


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_seq: int, dtype: jnp.dtype = jnp.bfloat16
) -> KVCache:
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


# ---------------------------------------------------------------- MoE block


def _default_dispatch() -> str:
    """Module default for the MoE dispatch form (read at TRACE time, so a
    jitted caller latches the value its first call saw). ``scatter`` is
    strictly cheaper off the EP path; ``parallel/serving`` pins ``einsum``
    explicitly when the expert axis is sharded (the all-to-all lowering
    needs the einsum form)."""
    return os.environ.get("SWARMDB_MOE_DISPATCH", "scatter")


def moe_block(
    x: jnp.ndarray,          # [B, T, D]
    router_w: jnp.ndarray,   # [D, E]
    w_gate: jnp.ndarray,     # [E, D, F]
    w_up: jnp.ndarray,       # [E, D, F]
    w_down: jnp.ndarray,     # [E, F, D]
    top_k: int,
    capacity_factor: float = DEFAULT_CAPACITY_FACTOR,
    dispatch: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routed expert FFN with capacity-based dispatch.

    Returns (output [B, T, D], router aux: mean expert load [E] for
    balance metrics). Static shapes: capacity C = ceil(N * top_k / E *
    capacity_factor); overflow tokens are dropped (zero contribution).
    ``dispatch`` picks the einsum (EP-shardable) or scatter (single-device
    fast path) realization — same routing, same values (module docstring).
    """
    B, T, D = x.shape
    E = router_w.shape[-1]
    N = B * T
    C = max(1, int(N * top_k * capacity_factor / E))
    if dispatch is None:
        dispatch = _default_dispatch()

    xf = x.reshape(N, D)
    router_logits = jnp.einsum(
        "nd,de->ne", xf.astype(jnp.float32), router_w.astype(jnp.float32)
    )

    # top-k gating, Mixtral convention: softmax over the SELECTED logits
    top_logits, top_idx = jax.lax.top_k(router_logits, top_k)      # [N, k]
    gates = jax.nn.softmax(top_logits, axis=-1)                    # [N, k]

    # expert assignment one-hots [N, k, E]
    assign = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)

    # position of each (token, choice) within its expert queue: cumsum over
    # the flattened (k-major) token order
    flat_assign = assign.reshape(N * top_k, E)
    pos_in_expert = (jnp.cumsum(flat_assign, axis=0) - flat_assign)  # [N*k, E]
    pos = jnp.sum(pos_in_expert * flat_assign, axis=-1).reshape(N, top_k)
    pos = pos.astype(jnp.int32)
    within_cap = pos < C
    load = jnp.mean(jnp.sum(assign, axis=1), axis=0)               # [E]

    if dispatch == "scatter":
        # token scatter into per-expert queues. (expert, pos) pairs are
        # unique by construction (pos = running count within its expert),
        # so the set never collides; over-capacity choices target column C
        # which mode="drop" discards.
        e_idx = top_idx.reshape(-1)                                # [N*k]
        c_idx = jnp.where(within_cap, pos, C).reshape(-1)
        tok_rows = jnp.repeat(jnp.arange(N), top_k)                # [N*k]
        xe = jnp.zeros((E, C, D), x.dtype).at[e_idx, c_idx].set(
            xf[tok_rows], mode="drop")
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate))
        u = jnp.einsum("ecd,edf->ecf", xe, w_up)
        ye = jnp.einsum("ecf,efd->ecd", g * u, w_down)             # [E, C, D]
        # gather each (token, choice)'s result back, gate-weighted;
        # over-capacity choices read a clamped row and are masked to zero
        yk = ye[e_idx, jnp.minimum(c_idx, C - 1)]                  # [N*k, D]
        yk = yk * (within_cap.reshape(-1)[:, None]
                   * gates.reshape(-1)[:, None]).astype(x.dtype)
        y = jnp.zeros((N, D), x.dtype).at[tok_rows].add(yk)
        return y.reshape(B, T, D), load

    # dispatch [N, E, C] (0/1) and combine [N, E, C] (gate-weighted)
    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32)             # [N, k, C]
    disp_k = assign[:, :, :, None] * pos_oh[:, :, None, :]         # [N, k, E, C]
    disp_k = disp_k * within_cap[:, :, None, None]
    dispatch_t = jnp.sum(disp_k, axis=1)                           # [N, E, C]
    combine = jnp.sum(disp_k * gates[:, :, None, None], axis=1)    # [N, E, C]

    # expert-major compute (bf16 matmuls on the MXU)
    xe = jnp.einsum("nd,nec->ecd", xf, dispatch_t.astype(x.dtype))  # [E, C, D]
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate))
    u = jnp.einsum("ecd,edf->ecf", xe, w_up)
    ye = jnp.einsum("ecf,efd->ecd", g * u, w_down)                 # [E, C, D]
    y = jnp.einsum("ecd,nec->nd", ye, combine.astype(x.dtype))

    return y.reshape(B, T, D), load


# ------------------------------------------------------------------- forward


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    cache: KVCache,
    logits_at: Optional[jnp.ndarray] = None,
    moe_dispatch: Optional[str] = None,
) -> Tuple[jnp.ndarray, KVCache]:
    """Forward pass; same contract as ``llama.forward`` (fp32 logits +
    updated cache, head-at-last-position via ``logits_at``), with
    per-layer MoE FFN."""
    if not cfg.is_moe:
        raise ValueError(f"{cfg.name!r} is dense; use models.llama.forward")
    x = params["embed"][tokens]
    cache_k, cache_v = cache
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

    def layer_step(x, scanned):
        lp, ck, cv = scanned
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        B, T = h.shape[0], h.shape[1]
        q, k, v = qkv_proj(h, lp, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim, cos, sin)
        ck, cv = write_kv_cache(ck, cv, k, v, positions)
        attn = gqa_attention(q, ck, cv, positions, window=cfg.sliding_window)
        x = x + jnp.einsum("bth,hd->btd", attn.reshape(B, T, -1), lp["wo"])

        h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        # the router-load aux is for direct moe_block callers (tests,
        # balance metrics); the serving forward keeps the llama cache-only
        # scan contract and drops it here
        moe_out, _load = moe_block(
            h2, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            top_k=cfg.experts_per_token, dispatch=moe_dispatch,
        )
        x = x + moe_out
        return x, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        layer_step, x, (params["layers"], cache_k, cache_v)
    )

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if logits_at is not None:
        x = x[jnp.arange(x.shape[0]), logits_at]
        logits = jnp.einsum("bd,dv->bv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
        return logits, (new_k, new_v)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, (new_k, new_v)


# chunk-KV / prefix-pool helpers are attention-side and identical across
# families — shared with the dense stack (one definition, review finding r4)
from .llama import (  # noqa: E402, F401
    init_chunk_kv,
    init_prefix_pool,
    merge_chunk,
    merge_chunk_scatter,
    merge_paged_chunk,
)


def forward_prefix_pages(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,        # [Bp, T] SUFFIX tokens (padded)
    prefix_table: jnp.ndarray,  # [Bp, PP] int32 prefix-pool page ids
    prefix_lens: jnp.ndarray,   # [Bp] int32 reused prefix length (tokens)
    pool_k: jnp.ndarray,        # [L, P, ps, Hkv, D]
    pool_v: jnp.ndarray,
    logits_at: Optional[jnp.ndarray] = None,
    moe_dispatch: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefix-cache suffix prefill core (see ``llama.forward_prefix_pages``
    for the design); MoE FFN unchanged. Returns (fp32 logits, sfx_k,
    sfx_v [L, Bp, T, Hkv, D])."""
    if not cfg.is_moe:
        raise ValueError(f"{cfg.name!r} is dense; use models.llama")
    from ..ops.layers import gqa_attention_prefix

    from ..ops.paged_kv import (_dequantize_pages, is_quantized, pool_data,
                                pools_flat)

    Bp, T = tokens.shape
    quant = is_quantized(pool_k)
    ps = pool_data(pool_k).shape[2]
    Pt = prefix_table.shape[1] * ps
    x = params["embed"][tokens]
    positions = prefix_lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

    pool_k_flat, pool_v_flat, L, P = pools_flat(pool_k, pool_v)

    def _gather_pages(flat, idx):
        if quant:
            return _dequantize_pages(flat.data[idx], flat.scale[idx]
                                     ).reshape(Bp, Pt, cfg.n_kv_heads,
                                               cfg.head_dim)
        return flat[idx].reshape(Bp, Pt, cfg.n_kv_heads, cfg.head_dim)

    def layer_step(x, scanned):
        lp, l = scanned
        kp = _gather_pages(pool_k_flat, l * P + prefix_table)
        vp = _gather_pages(pool_v_flat, l * P + prefix_table)
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = qkv_proj(h, lp, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim, cos, sin)
        attn = gqa_attention_prefix(q, kp, vp, k.astype(kp.dtype),
                                    v.astype(vp.dtype), prefix_lens,
                                    window=cfg.sliding_window)
        x = x + jnp.einsum("bth,hd->btd", attn.reshape(Bp, T, -1), lp["wo"])
        h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        moe_out, _load = moe_block(
            h2, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            top_k=cfg.experts_per_token, dispatch=moe_dispatch,
        )
        x = x + moe_out
        return x, (k.astype(kp.dtype), v.astype(vp.dtype))

    x, (sfx_k, sfx_v) = jax.lax.scan(
        layer_step, x,
        (params["layers"], jnp.arange(L, dtype=jnp.int32)),
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if logits_at is not None:
        x = x[jnp.arange(x.shape[0]), logits_at]
        logits = jnp.einsum("bd,dv->bv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
        return logits, sfx_k, sfx_v
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, sfx_k, sfx_v


def forward_prefix_lane(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    prefix_table: jnp.ndarray,
    prefix_lens: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    lane_pages: int,
    logits_at: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense-cache prefix prefill: core + shared lane composition (see
    ``llama.forward_prefix_lane``)."""
    from ..ops.layers import compose_prefix_lane

    logits, sfx_k, sfx_v = forward_prefix_pages(
        params, cfg, tokens, prefix_table, prefix_lens, pool_k, pool_v,
        logits_at=logits_at)
    lane_k, lane_v = compose_prefix_lane(
        pool_k, pool_v, prefix_table, prefix_lens, sfx_k, sfx_v, lane_pages)
    return logits, lane_k, lane_v


def forward_paged_chunked(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,       # [B, 1]
    positions: jnp.ndarray,    # [B, 1]
    cache,                     # {"k","v","page_table"} — FROZEN this chunk
    chunk_kv: Tuple[jnp.ndarray, jnp.ndarray],
    step: jnp.ndarray,
    moe_dispatch: Optional[str] = None,
):
    """Two-segment chunked decode over the paged pool (see
    ``llama.forward_paged_chunked``); MoE FFN unchanged."""
    if not cfg.is_moe:
        raise ValueError(f"{cfg.name!r} is dense; use models.llama")
    from ..ops.layers import paged_attention_dispatch_chunked
    from ..ops.paged_kv import pools_flat

    x = params["embed"][tokens]
    table = cache["page_table"]
    pool_k_flat, pool_v_flat, L, P = pools_flat(cache["k"], cache["v"])
    chunk_k, chunk_v = chunk_kv
    pos0 = cache.get("pos0")  # rolling-KV RoPE offset (llama.forward_paged)
    rope_pos = positions if pos0 is None else positions + pos0[:, None]
    cos, sin = rope_cos_sin(rope_pos, cfg.head_dim, cfg.rope_theta)

    def layer_step(x, scanned):
        lp, l, hk, hv = scanned
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        B, T = h.shape[0], h.shape[1]
        q, k, v = qkv_proj(h, lp, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim, cos, sin)
        hk = jax.lax.dynamic_update_slice(hk, k.astype(hk.dtype),
                                          (0, step, 0, 0))
        hv = jax.lax.dynamic_update_slice(hv, v.astype(hv.dtype),
                                          (0, step, 0, 0))
        attn = paged_attention_dispatch_chunked(
            q, pool_k_flat, pool_v_flat, table + l * P, hk, hv, positions,
            step, window=cfg.sliding_window)
        x = x + jnp.einsum("bth,hd->btd", attn.reshape(B, T, -1), lp["wo"])
        h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        moe_out, _load = moe_block(
            h2, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            top_k=cfg.experts_per_token, dispatch=moe_dispatch,
        )
        x = x + moe_out
        return x, (hk, hv)

    x, (new_hk, new_hv) = jax.lax.scan(
        layer_step, x,
        (params["layers"], jnp.arange(L, dtype=jnp.int32), chunk_k, chunk_v),
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, (new_hk, new_hv)


def forward_chunked(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,       # [B, 1]
    positions: jnp.ndarray,    # [B, 1]
    cache: KVCache,            # FROZEN during the chunk
    chunk_kv: Tuple[jnp.ndarray, jnp.ndarray],
    step: jnp.ndarray,         # scalar int32
    moe_dispatch: Optional[str] = None,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Two-segment chunked decode step (see ``llama.forward_chunked``);
    MoE FFN unchanged."""
    if not cfg.is_moe:
        raise ValueError(f"{cfg.name!r} is dense; use models.llama")
    x = params["embed"][tokens]
    cache_k, cache_v = cache
    chunk_k, chunk_v = chunk_kv
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

    def layer_step(x, scanned):
        lp, ck, cv, hk, hv = scanned
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        B, T = h.shape[0], h.shape[1]
        q, k, v = qkv_proj(h, lp, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim, cos, sin)
        hk = jax.lax.dynamic_update_slice(hk, k.astype(hk.dtype),
                                          (0, step, 0, 0))
        hv = jax.lax.dynamic_update_slice(hv, v.astype(hv.dtype),
                                          (0, step, 0, 0))
        attn = gqa_attention_chunked(q, ck, cv, hk, hv, positions, step,
                                     window=cfg.sliding_window)
        x = x + jnp.einsum("bth,hd->btd", attn.reshape(B, T, -1), lp["wo"])
        h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        moe_out, _load = moe_block(
            h2, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            top_k=cfg.experts_per_token, dispatch=moe_dispatch,
        )
        x = x + moe_out
        return x, (hk, hv)

    x, (new_hk, new_hv) = jax.lax.scan(
        layer_step, x, (params["layers"], cache_k, cache_v, chunk_k, chunk_v)
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, (new_hk, new_hv)


def init_paged_cache(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    num_pages: int,
    page_size: int,
    dtype: Optional[jnp.dtype] = None,
):
    """Block-paged KV pool; see ``llama.init_paged_cache``.

    ``dtype=None`` resolves from ``SWARMDB_KV_DTYPE`` (int8 → quantized
    ``QuantPool``)."""
    from ..ops.paged_kv import init_paged_kv_cache

    return init_paged_kv_cache(
        cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim,
        batch, max_seq, dtype,
    )


def forward_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,     # [B, 1] — DECODE steps only
    positions: jnp.ndarray,  # [B, 1]
    cache,                   # {"k", "v", "page_table"}
    moe_dispatch: Optional[str] = None,
):
    """Decode forward over the block-paged KV pool; MoE FFN unchanged.
    Same contract as ``llama.forward_paged``."""
    if not cfg.is_moe:
        raise ValueError(f"{cfg.name!r} is dense; use models.llama.forward_paged")
    from ..ops.layers import paged_attention_dispatch
    from ..ops.paged_kv import paged_write_decode

    x = params["embed"][tokens]
    table = cache["page_table"]
    pos0 = cache.get("pos0")  # rolling-KV RoPE offset (llama.forward_paged)
    rope_pos = positions if pos0 is None else positions + pos0[:, None]
    cos, sin = rope_cos_sin(rope_pos, cfg.head_dim, cfg.rope_theta)

    def layer_step(x, scanned):
        lp, kp, vp = scanned
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        B, T = h.shape[0], h.shape[1]
        q, k, v = qkv_proj(h, lp, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim, cos, sin)
        kp, vp = paged_write_decode(kp, vp, k, v, positions, table)
        attn = paged_attention_dispatch(
            q, kp, vp, table, positions, window=cfg.sliding_window)
        x = x + jnp.einsum("bth,hd->btd", attn.reshape(B, T, -1), lp["wo"])
        h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        moe_out, _load = moe_block(
            h2, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            top_k=cfg.experts_per_token, dispatch=moe_dispatch,
        )
        x = x + moe_out
        return x, (kp, vp)

    x, (new_k, new_v) = jax.lax.scan(
        layer_step, x, (params["layers"], cache["k"], cache["v"])
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    out = {"k": new_k, "v": new_v, "page_table": table}
    if pos0 is not None:
        out["pos0"] = pos0
    return logits, out
