"""The family with latent attention (DeepSeek-V2 style) — functional JAX.

The decoder is ``models/llama.py``'s (``layer_step``, the forwards, the
head) and the stack is ``models/lfm2.py``'s segments (a leading dense
layer unrolled, the routed layers one scan). This family brings:

- **its token mixer** (``latent_token_mixer``, MLA). A token ``x``:
  ``c_q = rmsnorm(x W_qa)``; ``q = c_q W_qb`` in heads of ``q_nope |
  q_pe`` (``q_pe`` takes RoPE); ``[c_kv | k_pe] = x W_kva``, ``c_kv =
  rmsnorm(c_kv)``, ``k_pe`` takes RoPE, one for all heads; keys and values
  a head are ``[k_nope | v] = c_kv W_kvb``; ``score = (q_nope . k_nope +
  q_pe . k_pe) s`` with ``s = head_dim^-0.5 m^2`` (``softmax_scale``).
  What a token leaves in the cache is its ROW ``[c_kv | k_pe]``, after the
  norm and after RoPE: ``latent_dim`` values a layer, no heads axis, no
  values beside it, kept ``row_width`` wide in the pool. The served
  forwards attend in the ABSORBED form: ``q_lat = q_nope W_kvb^K[h]``, a
  head's query ``[q_lat | q_pe] s`` is as wide as a row and scores against
  the row itself, ``o_lat = softmax . c_kv`` and ``out_h = o_lat
  W_kvb^V[h]``: every head over ONE cached row whose first
  ``kv_lora_rank`` values are also the values. ``forward`` below is the
  plain whole-sequence forward in the EXPANDED form (per-head keys and
  values made from ``c_kv``), what the served paths are compared with.
- **its RoPE** (``rope_terms``): YaRN, the blend of the plain and the
  interpolated inverse frequencies by a linear ramp between the
  correction dims, in the repo's rotate-half convention on the
  ``qk_rope_head_dim`` dims.
- **its router and FFN** (``routed_ffn``): float32 softmax scores over all
  ``n_experts``; a group's score is its largest; the ``topk_group`` best
  groups stay; top-k of the scores among theirs; gates the chosen scores
  times ``routed_scaling_factor``, not renormalised; the result is the
  shared experts' one SwiGLU (width ``shared_experts * expert_ffn_dim``)
  plus ``lfm2.moe_block``'s dropless sum over the chosen experts THIS CHIP
  HOLDS (``n_experts_held`` from ``first_held_expert``): a choice of an
  expert it does not hold has gate 0 here and is reported as left out
  (``~e``); on one chip nothing stands in for the chips that hold them.

Refused by name (``refuse_latent``): every path that assumes pages of
``[ps, Hkv, D]`` with keys and values apart.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.layers import apply_rope, rms_norm
from . import lfm2, llama
from .configs import ModelConfig

Params = Dict[str, Any]

layer_plan = lfm2.layer_plan
routing_shape = lfm2.routing_shape


def refuse_latent(cfg: ModelConfig, path: str) -> None:
    """A path that assumes pages with a heads axis and values beside the
    keys refuses a latent configuration by name; none runs it wrong."""
    if cfg.latent:
        raise NotImplementedError(
            f"{cfg.name!r} attends through a latent (MLA): its cache holds "
            f"one row of {cfg.latent_dim} values a token a layer, with no "
            f"heads axis and no values beside it; {path} assumes pages of "
            "keys and values a head (the paged engine's ragged prefill "
            "and chunked decode carry latent pages)")


def row_width(cfg: ModelConfig) -> int:
    """Width a latent row is kept at in the pool and the chunk buffers:
    ``latent_dim`` (576 as published), and on a TPU the next multiple of
    128 lanes (640), the upper lanes zero: the chip lays a last dimension
    out in whole lanes anyway, and the kernels copy whole pages."""
    w = cfg.latent_dim
    if w % llama.LANES and jax.default_backend() == "tpu":
        return -(-w // llama.LANES) * llama.LANES
    return w


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: ModelConfig) -> float:
    """``head_dim^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``."""
    m = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
         if cfg.yarn_factor else 1.0)
    return cfg.head_dim ** -0.5 * m * m


def yarn_inv_freq(cfg: ModelConfig):
    """Inverse frequencies [qk_rope_head_dim / 2] (numpy float64) and the
    factor cos and sin are scaled by. Plain ``theta^(-2i/d)`` where a
    dimension turns more than ``beta_fast`` times over the original
    context, that divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, a linear ramp between the two correction dims."""
    import numpy as np

    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    plain = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if not cfg.yarn_factor:
        return plain, 1.0

    def correction_dim(turns):
        return (d * math.log(cfg.yarn_original_max_seq / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = plain / cfg.yarn_factor * ramp + plain * (1.0 - ramp)
    scale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return inv, scale


def rope_terms(cfg: ModelConfig, positions: jnp.ndarray):
    """(cos, sin), each [B, T, 1, qk_rope_head_dim / 2] float32, as
    ``ops.layers.rope_cos_sin`` gives them, at YaRN's frequencies."""
    inv, scale = yarn_inv_freq(cfg)
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv, jnp.float32)
    return ((jnp.cos(angles) * scale)[:, :, None, :],
            (jnp.sin(angles) * scale)[:, :, None, :])


# ---------------------------------------------------------------------- init


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> Params:
    D, F, Fe = cfg.dim, cfg.ffn_dim, cfg.expert_ffn_dim or cfg.ffn_dim
    H, E, Eh = cfg.n_heads, cfg.n_experts, cfg.experts_held
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    Fs = cfg.shared_experts * Fe
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense(key, shape, fan_in):
        return llama.random_dense(key, shape, fan_in, dtype)

    def near_one(key, shape):
        # a norm weight of all ones would hide a norm left out
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    def layer(key, sig, n: int) -> Params:
        _mixer, ffn = sig
        ks = jax.random.split(key, 16)
        lp: Params = {
            "attn_norm": jnp.ones((n, D), dtype),
            "mlp_norm": jnp.ones((n, D), dtype),
            "w_qa": dense(ks[0], (n, D, qr), D),
            "q_a_norm": near_one(ks[1], (n, qr)),
            "w_qb": dense(ks[2], (n, qr, H * (dn + dr)), qr),
            "w_kva": dense(ks[3], (n, D, kr + dr), D),
            "kv_a_norm": near_one(ks[4], (n, kr)),
            "w_kvb": dense(ks[5], (n, kr, H * (dn + dv)), kr),
            "wo": dense(ks[6], (n, H * dv, D), H * dv)}
        if ffn == "dense":
            lp["w_gate"] = dense(ks[7], (n, D, F), D)
            lp["w_up"] = dense(ks[8], (n, D, F), D)
            lp["w_down"] = dense(ks[9], (n, F, D), F)
        else:
            lp["router"] = dense(ks[7], (n, D, E), D)
            lp["w_gate"] = dense(ks[8], (n, Eh, D, Fe), D)
            lp["w_up"] = dense(ks[9], (n, Eh, D, Fe), D)
            lp["w_down"] = dense(ks[10], (n, Eh, Fe, D), Fe)
            if Fs:
                lp["ws_gate"] = dense(ks[11], (n, D, Fs), D)
                lp["ws_up"] = dense(ks[12], (n, D, Fs), D)
                lp["ws_down"] = dense(ks[13], (n, Fs, D), Fs)
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


# ----------------------------------------------------------- the token mixer


def latent_projections(cfg: ModelConfig, h, lp, cos, sin, q_scale=1.0):
    """``(q_nope [B, T, H, dn], q_pe [B, T, H, dr], row [B, T,
    latent_dim])`` of normed hidden states ``h``: the queries times
    ``q_scale`` (float32 out of ``W_qb``, scaled, rounded once), ``q_pe``
    after RoPE, and what the token leaves in the cache, ``c_kv`` after its
    norm and ``k_pe`` after RoPE, in ``h``'s dtype."""
    B, T = h.shape[0], h.shape[1]
    H, dn, kr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    c_q = rms_norm(jnp.einsum("btd,dr->btr", h, lp["w_qa"]), lp["q_a_norm"],
                   cfg.norm_eps)
    q = (jnp.einsum("btr,rh->bth", c_q, lp["w_qb"],
                    preferred_element_type=jnp.float32) * q_scale
         ).astype(h.dtype).reshape(B, T, H, cfg.head_dim)
    ckv = jnp.einsum("btd,dr->btr", h, lp["w_kva"])
    c_kv = rms_norm(ckv[..., :kr], lp["kv_a_norm"], cfg.norm_eps)
    k_pe = apply_rope(ckv[..., None, kr:], cos, sin)[:, :, 0]
    row = jnp.concatenate([c_kv, k_pe], axis=-1)
    return q[..., :dn], apply_rope(q[..., dn:], cos, sin), row


def _w_kvb(cfg: ModelConfig, lp):
    """``(W^K [kr, H, dn], W^V [kr, H, dv])`` of the layer's ``w_kvb``."""
    w = lp["w_kvb"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                            cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def latent_token_mixer(cfg: ModelConfig, cos, sin, mixer):
    """MLA in the absorbed form as a token mixer of ``llama.layer_step``.
    ``mixer(q [B, T, H, Wd], row [B, T, Wd], ops) -> (o_lat [B, T, H, Wd],
    out)`` is the forward's cache step and attention over rows: ``q`` is
    ``[q_nope W^K | q_pe] s`` and ``row`` the token's own, both
    ``latent_dim`` wide (the forward pads them to its pool's width,
    ``at_width``); ``o_lat`` is the softmax-weighted sum of rows, of which
    the first ``kv_lora_rank`` lanes are read."""
    kr = cfg.kv_lora_rank

    def token_mixer(h, lp, ops):
        # the softmax scale rides in the queries from where they are
        # made: a wave's absorbed queries are its widest tensor (128
        # heads of 576 a token), and are never held in float32
        q_nope, q_pe, row = latent_projections(cfg, h, lp, cos, sin,
                                               softmax_scale(cfg))
        wk, wv = _w_kvb(cfg, lp)
        q = jnp.concatenate(
            [jnp.einsum("bthn,chn->bthc", q_nope, wk), q_pe], axis=-1)
        o_lat, out = mixer(q, row, ops)
        o = jnp.einsum("bthc,chv->bthv", o_lat[..., :kr].astype(h.dtype), wv)
        B, T = h.shape[0], h.shape[1]
        return jnp.einsum("bth,hd->btd", o.reshape(B, T, -1), lp["wo"]), out

    return token_mixer


def at_width(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """``x`` [..., w] zero-padded to the pool's ``width``."""
    pad = width - x.shape[-1]
    return x if not pad else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def expanded_token_mixer(cfg: ModelConfig, cos, sin, positions):
    """MLA in the EXPANDED form over one call's own tokens (no cache):
    per-head keys ``[k_nope | k_pe]`` and values made from ``c_kv`` through
    ``W_kvb``, causal by ``positions`` [B, T]. Keeps each token's row."""

    def token_mixer(h, lp, _ops):
        q_nope, q_pe, row = latent_projections(cfg, h, lp, cos, sin)
        wk, wv = _w_kvb(cfg, lp)
        kr = cfg.kv_lora_rank
        f32 = jnp.float32
        k_nope = jnp.einsum("bsc,chn->bshn", row[..., :kr], wk)
        v = jnp.einsum("bsc,chv->bshv", row[..., :kr], wv)
        s = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                        preferred_element_type=f32)
             + jnp.einsum("bthr,bsr->bhts", q_pe, row[..., kr:],
                          preferred_element_type=f32)) * softmax_scale(cfg)
        causal = positions[:, None, :, None] >= positions[:, None, None, :]
        p = jax.nn.softmax(jnp.where(causal, s, f32(-1e30)), axis=-1)
        o = jnp.einsum("bhts,bshv->bthv", p.astype(v.dtype), v)
        B, T = h.shape[0], h.shape[1]
        return jnp.einsum("bth,hd->btd", o.reshape(B, T, -1), lp["wo"]), row

    return token_mixer


# ------------------------------------------------------------ the expert FFN


def route(cfg: ModelConfig, h: jnp.ndarray, router_w: jnp.ndarray
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(chosen [N, k] int32, gates [N, k] float32)`` of ``h`` [N, D]:
    float32 softmax scores over every expert, the ``topk_group`` best
    groups by their largest score, top-k of the scores among theirs (ties
    to the lower index, as ``lax.top_k``), the chosen scores times
    ``routed_scaling_factor``."""
    p = jax.nn.softmax(jnp.einsum(
        "nd,de->ne", h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    N, E = p.shape
    G = cfg.n_group
    _, best = jax.lax.top_k(jnp.max(p.reshape(N, G, E // G), axis=-1),
                            cfg.topk_group)
    stays = jnp.any(jax.nn.one_hot(best, G, dtype=jnp.bool_), axis=1)  # [N, G]
    among = jnp.where(jnp.repeat(stays, E // G, axis=1), p, -1.0)
    _, chosen = jax.lax.top_k(among, cfg.experts_per_token)
    gates = jnp.take_along_axis(p, chosen, axis=-1)
    return chosen, gates * cfg.routed_scaling_factor


def routed_ffn(cfg: ModelConfig, live):
    """The routed layers' FFN for ``lfm2.run_layers``: ``ffn(h, lp, repeat)
    -> (y, routing)``, the shared experts' SwiGLU plus the dropless sum
    over the chosen experts this chip holds."""
    held = (cfg.first_held_expert, cfg.experts_held)

    def ffn(h, lp, repeat):
        y, routing = lfm2.moe_block(
            h, lp, cfg.experts_per_token, live, repeat * cfg.experts_held,
            chosen_gates=lambda xf, lp: route(cfg, xf, lp["router"]),
            held=held)
        if "ws_gate" in lp:
            y = (y.astype(jnp.float32) + lfm2._swiglu(
                h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])).astype(h.dtype)
        return y, routing

    return ffn


def run_layers(params: Params, cfg: ModelConfig, x, cos, sin, mixer, ops,
               live=None, token_mixer=None):
    """``x`` through every layer (``lfm2.run_layers`` with this family's
    token mixer and FFN): ``(x, rows kept by the mixer stacked over the
    layers, None, routing)``."""
    return lfm2.run_layers(
        params, cfg, x, cos, sin, mixer, ops, None, None, live,
        attn_tm=token_mixer or latent_token_mixer(cfg, cos, sin, mixer),
        routed_ffn=routed_ffn(cfg, live))


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, logits_at: Optional[jnp.ndarray] = None):
    """The plain whole-sequence forward, EXPANDED form, no cache: rows
    start at position 0 with nothing before them. Returns (float32 logits,
    rows [L, B, T, latent_dim], routing [B, T, L_routed, k]). What the
    served paths are compared with; no engine runs it."""
    x = params["embed"][tokens]
    cos, sin = rope_terms(cfg, positions)
    x, rows, _none, routing = run_layers(
        params, cfg, x, cos, sin, None,
        jnp.arange(cfg.n_layers, dtype=jnp.int32),
        token_mixer=expanded_token_mixer(cfg, cos, sin, positions))
    return llama.lm_logits(params, cfg, x, logits_at), rows, *routing
