"""Mixtral-style sparse Mixture-of-Experts family — functional JAX.

The decoder is ``models/llama.py``'s (one layer body, one head, every
forward); this family brings its parameters and its FFN, a top-k routed MoE
block (``llama._ffn`` calls it). Two numerically-equivalent dispatch forms:

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

What the router decided leaves the block: ``moe_block`` returns, beside its
output and the mean load, the ROUTING of its call — for each token and each
of its ``top_k`` choices the expert's index and whether that choice was
computed or dropped (``encode_routing`` has the format). ``llama._ffn``
hands it on, the layer scan stacks it over the layers that route, and every
forward of ``models/llama.py`` returns it last, ``[.., T, L_routed, k]``.
The engine carries those rows to the request that owns each position
(``GenRequest.routing``) and the prefix cache keeps them beside a page's
tokens, so a reference can follow the served path's choices.

No reference counterpart: the reference has no model code (SURVEY §2.4).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import llama
from .configs import ModelConfig

Params = Dict[str, Any]

DEFAULT_CAPACITY_FACTOR = 2.0

# what callers of this family import beside ``init_params``
init_kv_cache = llama.init_kv_cache


def forward(params, cfg, tokens, positions, cache, **kw):
    """``llama.forward`` without the routing it reports: logits and cache,
    for the callers that compare this family's logits with a reference
    (the tests; ``tests/benchmark/test_bench_moe_reference.py``)."""
    logits, cache, _routing = llama.forward(params, cfg, tokens, positions,
                                            cache, **kw)
    return logits, cache


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
        # looked up when called: a caller that plans memory without
        # drawing weights replaces ``llama.random_dense`` for both families
        return llama.random_dense(key, shape, fan_in, dtype)

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


# ------------------------------------------------------------------ routing


def encode_routing(top_idx: jnp.ndarray, within_cap: jnp.ndarray) -> jnp.ndarray:
    """One int16 a choice: the expert's index ``e``, or ``~e`` (negative)
    where the choice fell over the capacity and its contribution was left
    out. The sign bit is the drop, so a reader needs no mask beside it;
    it holds for up to 32768 experts."""
    e = top_idx.astype(jnp.int16)
    return jnp.where(within_cap, e, ~e)


def routing_experts(routing):
    """Expert indices of an encoded routing (numpy or jax, any shape)."""
    return routing ^ (routing >> 15)      # ~r where r < 0, r elsewhere


def routing_dropped(routing):
    """True where the encoded choice was dropped, not computed."""
    return routing < 0


def routing_shape(cfg: ModelConfig) -> Optional[Tuple[int, int, int]]:
    """``(L_routed, k, E)`` of what the forwards report a position, or
    None for a dense configuration. The layers that route are those the
    layer scan runs ``moe_block`` in: every one today."""
    if not cfg.is_moe:
        return None
    return cfg.n_layers, cfg.experts_per_token, cfg.n_experts


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
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routed expert FFN with capacity-based dispatch.

    Returns (output [B, T, D], router aux: mean expert load [E] for
    balance metrics, routing [B, T, top_k] int16: ``encode_routing`` of
    the chosen experts and of which choices were computed). Static shapes: capacity C = ceil(N * top_k / E *
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
    routing = encode_routing(top_idx, within_cap).reshape(B, T, top_k)

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
        return y.reshape(B, T, D), load, routing

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

    return y.reshape(B, T, D), load, routing
