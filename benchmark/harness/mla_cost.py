"""Operations and bytes of latent (MLA) attention and of one decode step of
a ``deepseek_v2`` stack as one chip holds it, from a configuration file's
published keys alone (never from ``cost_analysis()``). Every count is the
least the algorithm allows, whatever implements it: a cached row, a query,
an output and a weight a step needs move once, an expert no live row chose
not at all.

A cached token is one row of ``kv_lora_rank + qk_rope_head_dim`` values a
layer (576 as published; the 640 lanes the chip keeps it at are the
implementation's). ``itemsize`` is bytes an element (2 for bf16)."""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple


def row(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """``(heads, latent row width, kv_lora_rank)``."""
    rank = cfg["kv_lora_rank"]
    return cfg["num_attention_heads"], rank + cfg["qk_rope_head_dim"], rank


def absorbed_decode(cfg: Dict[str, Any], context_lens: Iterable[float],
                    itemsize: int = 2):
    """One decode step of one layer in the absorbed form, a live row a
    context. A head's query of the row's width scores against every cached
    row and the softmax sums their first ``kv_lora_rank`` values:
    ``2 * heads * (width + rank)`` operations a cached token. Bytes: the
    context's rows once, the absorbed queries in and the latent outputs
    out. Returns (flops, bytes)."""
    h, width, rank = row(cfg)
    flops = bytes_moved = 0.0
    for ctx in context_lens:
        flops += 2.0 * h * (width + rank) * ctx
        bytes_moved += itemsize * (ctx * width + h * (width + rank))
    return flops, bytes_moved


def absorbed_prefill(cfg: Dict[str, Any], rows: Iterable[Tuple[int, int]],
                     itemsize: int = 2):
    """One ragged prefill wave of one layer in the form the program takes:
    absorbed throughout, over the cached rows in place and over the wave's
    own (no cached row is expanded). ``rows``: (prefix_len, new_len) a row.
    A row has ``new * prefix + new * (new + 1) / 2`` (query, key) pairs of
    ``2 * heads * (width + rank)`` operations; bytes are the rows of
    prefix + new once and the new tokens' queries and outputs."""
    h, width, rank = row(cfg)
    flops = bytes_moved = 0.0
    for prefix, new in rows:
        pairs = new * prefix + new * (new + 1) // 2
        flops += 2.0 * h * (width + rank) * pairs
        bytes_moved += itemsize * ((prefix + new) * width
                                   + new * h * (width + rank))
    return flops, bytes_moved


def weights(cfg: Dict[str, Any], itemsize: int = 2) -> Dict[str, float]:
    """Bytes of the weights a step reads whatever it routes (``fixed``:
    every layer's attention, norms and router, the dense layers' FFN, the
    shared experts, and the held rows of the head once; the embedding is a
    gather of a row a token) and of one routed expert of one layer
    (``expert``), with the layers that route and the experts held."""
    n, D, H = cfg["num_hidden_layers"], cfg["hidden_size"], \
        cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    attn = (D * qr + qr + qr * H * (dn + dr) + D * (kr + dr) + kr
            + kr * H * (dn + dv) + H * dv * D)
    dense = cfg["first_k_dense_replace"]
    routed = n - dense
    Fe = cfg["moe_intermediate_size"]
    fixed = (n * (attn + 2 * D) + D
             + dense * 3 * D * cfg["intermediate_size"]
             + routed * (D * cfg["n_routed_experts"]
                         + 3 * D * Fe * cfg["n_shared_experts"])
             + cfg["vocab_size"] * D)
    return {"fixed": fixed * itemsize, "expert": 3 * D * Fe * itemsize,
            "routed_layers": routed,
            "held": cfg.get("n_routed_experts_held",
                            cfg["n_routed_experts"]),
            "top_k": cfg["num_experts_per_tok"]}


def decode_steps(cfg: Dict[str, Any], steps: float, expert_hits: float,
                 held_choices: float, row_steps: float, latent_bytes: float,
                 itemsize: int = 2):
    """(flops, bytes) of ``steps`` decode steps in which the live rows hit
    ``expert_hits`` distinct (step, layer, held expert) triples, made
    ``held_choices`` choices of held experts, took ``row_steps`` row-steps
    in all and read ``latent_bytes`` in their attention. A row-step
    multiplies by every fixed weight and by the held experts it chose."""
    w = weights(cfg, itemsize)
    moved = steps * w["fixed"] + expert_hits * w["expert"] + latent_bytes
    flops = 2.0 * (row_steps * w["fixed"] + held_choices * w["expert"]
                   ) / itemsize
    return flops, moved
