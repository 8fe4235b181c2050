"""Operations and bytes of the Mamba-2 layers and of one decode step of a
``nemotron_h`` stack as one chip holds it, from a configuration file's
published keys alone (never from ``cost_analysis()``). Every count is the
least the algorithm allows, whatever implements it: a weight a step needs
moves once, an expert no live row chose not at all, a live row's
recurrent state is read once a step (a step's output needs all of it) and
written once a decode chunk (the chunk's steps can be put on top of the
state they started from; nothing needs it written sooner).

A layer is ONE sublayer by ``hybrid_override_pattern``: ``M`` Mamba-2,
``E`` routed FFN of un-gated experts (two matrices each) beside a shared
one, ``*`` attention. ``itemsize`` is bytes an element (2 for bf16)."""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple


def kinds(cfg: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each letter run."""
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return {k: pattern.count(k) for k in "ME*"}


def mixer(cfg: Dict[str, Any]) -> Tuple[int, int, int, int, int]:
    """``(heads, head size, groups, state size, conv width)``."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return H, P, G, N, H * P + 2 * G * N


def state_values(cfg: Dict[str, Any]) -> int:
    """Values ONE Mamba-2 layer keeps a sequence: ``S`` [H, P, N] and the
    last ``conv_kernel - 1`` rows of the un-convolved x, B, C."""
    H, P, _G, N, conv = mixer(cfg)
    return H * P * N + (cfg["conv_kernel"] - 1) * conv


def state_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of one sequence's state over every Mamba-2 layer: what a slot
    holds, and what a snapshot holds."""
    return kinds(cfg)["M"] * state_values(cfg) * itemsize


def mixer_weights(cfg: Dict[str, Any]) -> int:
    """Parameters of one Mamba-2 layer: ``W_in``, ``W_out``, the conv and
    its bias, ``A_log``, ``dt_bias``, ``D`` and the gated norm."""
    H, P, _G, _N, conv = mixer(cfg)
    D, Di = cfg["hidden_size"], H * P
    return (D * (Di + conv + H) + Di * D
            + conv * (cfg["conv_kernel"] + bool(cfg["use_conv_bias"]))
            + 3 * H + Di)


def step(cfg: Dict[str, Any], rows: int, chunk: int, itemsize: int = 2):
    """One decode step of ONE Mamba-2 layer over ``rows`` live rows:
    ``W_in`` and ``W_out`` once; a row its projections, the update ``S <-
    exp(dt a) S + dt x (x) B`` and the read ``S C`` (2 operations a state
    value each), its state once in and 1 / ``chunk`` of it out. Returns
    (flops, bytes)."""
    H, P, _G, N, _conv = mixer(cfg)
    w = mixer_weights(cfg)
    flops = rows * (2.0 * w + 4.0 * H * P * N)
    moved = itemsize * (w + rows * state_values(cfg) * (1 + 1 / chunk))
    return flops, moved


def scan(cfg: Dict[str, Any], rows: Iterable[int], emitted: int,
         itemsize: int = 2):
    """The scan of ONE Mamba-2 layer over a wave whose rows hold ``rows``
    new tokens each: a token 4 operations a state value (the update and
    the read, in whatever form), its x, B, C, dt in and its y out; a row
    its seed state in and its state out, and ``emitted`` more states out
    (the snapshots at page ends). Without the projections. Returns
    (flops, bytes)."""
    H, P, _G, N, conv = mixer(cfg)
    rows = list(rows)
    tokens = sum(rows)
    flops = 4.0 * tokens * H * P * N
    moved = itemsize * (tokens * (conv + H + H * P)
                        + (2 * len(rows) + emitted) * state_values(cfg))
    return flops, moved


def weights(cfg: Dict[str, Any], itemsize: int = 2) -> Dict[str, float]:
    """Bytes of the weights a step reads whatever it routes (``fixed``:
    every Mamba-2 and attention layer, every norm and router with its
    selection bias, the shared experts, and the held rows of the head
    once; the embedding is a gather of a row a token) and of one routed
    expert of one layer (``expert``: two matrices), with the layers that
    route, the experts held and scored, and the choices a token."""
    n = kinds(cfg)
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    attn = D * q + 2 * D * kv + q * D
    scored = cfg.get("published", {}).get("n_routed_experts",
                                          cfg["n_routed_experts"])
    shared = 2 * D * cfg["moe_shared_expert_intermediate_size"] \
        * cfg["n_shared_experts"]
    fixed = (n["M"] * mixer_weights(cfg) + n["*"] * attn
             + n["E"] * (D * scored + scored + shared)
             + cfg["num_hidden_layers"] * D + D + cfg["vocab_size"] * D)
    return {"fixed": fixed * itemsize,
            "expert": 2 * D * cfg["moe_intermediate_size"] * itemsize,
            "routed_layers": n["E"], "held": cfg["n_routed_experts"],
            "scored": scored, "top_k": cfg["num_experts_per_tok"]}


def decode_steps(cfg: Dict[str, Any], steps: float, expert_hits: float,
                 held_choices: float, row_steps: float, kv_bytes: float,
                 chunk: int, itemsize: int = 2):
    """(flops, bytes) of ``steps`` decode steps in which the live rows hit
    ``expert_hits`` distinct (step, layer, held expert) triples, made
    ``held_choices`` choices of held experts, took ``row_steps`` row-steps
    in all and read ``kv_bytes`` of keys, values, queries and outputs in
    the attention layers. A row-step multiplies by every fixed weight and
    by the held experts it chose, and reads its state once and writes 1 /
    ``chunk`` of it."""
    w = weights(cfg, itemsize)
    H, P, _G, N, _conv = mixer(cfg)
    moved = (steps * w["fixed"] + expert_hits * w["expert"] + kv_bytes
             + row_steps * state_bytes(cfg, itemsize) * (1 + 1 / chunk))
    flops = (2.0 * (row_steps * w["fixed"] + held_choices * w["expert"])
             / itemsize + row_steps * kinds(cfg)["M"] * 4.0 * H * P * N)
    return flops, moved
