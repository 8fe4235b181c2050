"""Plain reference: the forward pass of a DeepSeek-V2 decoder (``model_type``
``deepseek_v2``) as ONE chip of those that share each layer holds it, in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``. No
kernel, no cache, no latent pages, no absorbed form, no batching, and
nothing imported from the program: only its weight arrays are read, in the
layout ``models/deepseek.py`` documents (``params["segments"][s][j]``: the
``j``-th layer of a segment's pattern, its weights stacked over the
segment's repeats; the layers in order are segment by segment, repeat by
repeat, pattern position by position). Which layer is dense comes from the
**published** ``first_k_dense_replace`` of the configuration file, and a
layer whose weights are not of that kind is an error.

``x`` is ``[T, D]``; no bias anywhere; RMSNorm with ``rms_norm_eps`` and a
learned weight.

  x = embed[tokens]
  layer l:   h = x + MLA_l(rmsnorm(x; input_layernorm))            ("attn_norm")
             x = h + FFN_l(rmsnorm(h; post_attention_layernorm))   ("mlp_norm")
  logits = rmsnorm(x; norm) lm_head                                (untied)

  MLA (the EXPANDED form only), a token u:
     c_q = rmsnorm(u W_qa; q_a_layernorm);  q = c_q W_qb -> H heads of
     [q_nope (qk_nope_head_dim) | q_pe (qk_rope_head_dim)];  q_pe <- RoPE
     [c_kv | k_pe] = u W_kva (kv_lora_rank | qk_rope_head_dim);
     c_kv <- rmsnorm(c_kv; kv_a_layernorm);  k_pe <- RoPE, one for all heads
     [k_nope | v] = c_kv W_kvb -> H heads of [qk_nope_head_dim | v_head_dim]
     score = (q_nope . k_nope + q_pe . k_pe) s,   s = (qk_nope + qk_rope)^-0.5 m^2,
     m = 0.1 mscale_all_dim ln(factor) + 1;  causal softmax;  out = (p v) W_o
  RoPE: YaRN. inv_freq_i = plain_i (1 - ramp_i) + plain_i / factor * ramp_i,
     plain_i = theta^(-2i/d), ramp the linear ramp from ``low`` to ``high``,
     the correction dims d ln(orig / (2 pi beta)) / (2 ln theta) of beta_fast
     (floored) and beta_slow (ceiled); cos and sin times
     mscale(factor, mscale) / mscale(factor, mscale_all_dim) (1 as published).
     Pairs (i, i + d/2) are rotated (rotate-half; the configuration file
     lists this under ``assumed``: the published code de-interleaves q_pe and
     k_pe first, which with random weights is a permutation of columns).
  FFN_l, l < first_k_dense_replace:  W_2(silu(W_1 u) * W_3 u)
  FFN_l, else:  p = softmax(u W_r) over all n_routed_experts, float32;
     a group's score is the largest p among its n_routed_experts / n_group;
     the topk_group best groups stay; sel = top-k of p among their experts;
     g = p[sel] * routed_scaling_factor (norm_topk_prob false: not
     renormalised);  y = SwiGLU_shared(u) + sum_j g_j SwiGLU_{sel_j}(u),
     SwiGLU_shared one SwiGLU of width n_shared_experts * moe_intermediate_size.

**The share.** The weights hold experts ``first_held_expert`` ..
``+ n_routed_experts_held`` of every routed layer (one routing group, as
the deployment's chip does), both shared experts, all of attention, and
``vocab_size`` rows of the embedding and of the head. This file is given
the same share: a chosen expert that is not held adds nothing here, as it
adds nothing in the program (the chips that hold it are not there), and
the logits are over the held rows of the vocabulary.

Departures, all of form and none of mathematics: one sequence at a time;
queries in blocks of ``Q_BLOCK`` against the whole context; the held
experts are computed for every token, one at a time with one float32 copy
of one expert's weights beside the program's arrays, the unchosen weighted
0; logits only at the positions asked for.

**It follows the program's routing** (``FOLLOWS_ROUTING``; ``PERF.md``
section 4). ``logits_at`` takes, as ``routing`` ``[T, L_routed, k]`` int16,
what the program reports it chose: ``e``, or ``~e`` for a choice it left
out, here a choice of an expert this chip does not hold, which is skipped.
Row ``i`` of the layer axis is layer ``first_k_dense_replace + i``. The
gates are this file's own float32 scores at the named experts. With
``routing=None`` it chooses by its own group-limited top-k."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256
# logits_at takes the program's reported routing (harness/check.py)
FOLLOWS_ROUTING = True


def dims(cfg_file):
    """What ``logits_at`` needs beside the weights, from the keys of the
    published ``config.json`` in the configuration file (and the two that
    say which experts the weights hold)."""
    rs = cfg_file["rope_scaling"]
    if rs["type"] != "yarn" or cfg_file["scoring_func"] != "softmax" \
            or cfg_file["topk_method"] != "group_limited_greedy":
        raise ValueError("this reference is YaRN, softmax scores, "
                         "group_limited_greedy")
    return dict(
        n_heads=cfg_file["num_attention_heads"],
        kv_rank=cfg_file["kv_lora_rank"],
        nope=cfg_file["qk_nope_head_dim"], rope=cfg_file["qk_rope_head_dim"],
        v_dim=cfg_file["v_head_dim"],
        eps=float(cfg_file["rms_norm_eps"]),
        theta=float(cfg_file["rope_theta"]),
        yarn=(float(rs["factor"]), int(rs["original_max_position_embeddings"]),
              float(rs["beta_fast"]), float(rs["beta_slow"]),
              float(rs["mscale"]), float(rs["mscale_all_dim"])),
        n_experts=cfg_file["n_routed_experts"],
        top_k=cfg_file["num_experts_per_tok"],
        n_group=cfg_file["n_group"], topk_group=cfg_file["topk_group"],
        scaling=float(cfg_file["routed_scaling_factor"]),
        norm_topk=bool(cfg_file["norm_topk_prob"]),
        first_held=cfg_file.get("first_held_expert", 0),
        n_held=cfg_file.get("n_routed_experts_held",
                            cfg_file["n_routed_experts"]),
        n_layers=cfg_file["num_hidden_layers"],
        n_dense=cfg_file["first_k_dense_replace"])


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(jnp.float32)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(d, theta, yarn):
    """``(inv_freq [d / 2] float32, cos-and-sin factor)``."""
    factor, orig, beta_fast, beta_slow, mscale, mscale_all = yarn
    plain = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def correction(beta):
        return d * math.log(orig / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (plain * (1.0 - ramp) + plain / factor * ramp,
            _mscale(factor, mscale) / _mscale(factor, mscale_all))


def _rope(x, pos, theta, yarn):
    """x [T, H, d]; rotate pairs (x[..., i], x[..., i + d/2])."""
    d = x.shape[-1]
    inv, f = yarn_inv_freq(d, theta, yarn)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.cos(ang) * f)[:, None, :], (jnp.sin(ang) * f)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _mla(u, w, n_heads, kv_rank, nope, rope, v_dim, eps, theta, yarn):
    T = u.shape[0]
    pos = jnp.arange(T)
    c_q = _rmsnorm(u @ w("w_qa"), w("q_a_norm"), eps)
    q = (c_q @ w("w_qb")).reshape(T, n_heads, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, theta, yarn)
    ckv = u @ w("w_kva")
    c_kv = _rmsnorm(ckv[:, :kv_rank], w("kv_a_norm"), eps)
    k_pe = _rope(ckv[:, None, kv_rank:], pos, theta, yarn)[:, 0]   # [T, rope]
    kv = (c_kv @ w("w_kvb")).reshape(T, n_heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    m = _mscale(yarn[0], yarn[5])
    scale = (nope + rope) ** -0.5 * m * m

    def attend(args):
        qn, qp, pb = args                   # [Bq, H, nope], [Bq, H, rope], [Bq]
        s = (jnp.einsum("qhd,shd->hqs", qn, k_nope)
             + jnp.einsum("qhd,sd->hqs", qp, k_pe)) * scale
        s = jnp.where(pos[None, None, :] <= pb[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v)

    nb = T // Q_BLOCK
    att = jax.lax.map(attend, (q_nope.reshape(nb, Q_BLOCK, n_heads, nope),
                               q_pe.reshape(nb, Q_BLOCK, n_heads, rope),
                               pos.reshape(nb, Q_BLOCK)))
    return att.reshape(T, n_heads * v_dim) @ w("wo")


def group_limited_top_k(p, n_group, topk_group, top_k):
    """Indices [T, k] of the top-k of ``p`` [T, E] among the experts of the
    ``topk_group`` groups whose largest score is largest; ties go to the
    lower index in both."""
    T, E = p.shape
    best = jax.lax.top_k(jnp.max(p.reshape(T, n_group, E // n_group), axis=-1),
                         topk_group)[1]                           # [T, topk_group]
    stays = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], best].set(True)
    among = jnp.where(jnp.repeat(stays, E // n_group, axis=1), p, -1.0)
    return jax.lax.top_k(among, top_k)[1]


def _expert_ffn(u, lp, r, w, routing, n_experts, top_k, n_group, topk_group,
                scaling, norm_topk, first_held, n_held):
    if lp["router"].shape[-1] != n_experts:
        raise ValueError(f"the file says {n_experts} routed experts, the "
                         f"router has {lp['router'].shape[-1]} outputs")
    if lp["w_gate"].shape[1] != n_held:
        raise ValueError(f"the file says {n_held} experts are held, the "
                         f"weights hold {lp['w_gate'].shape[1]}")
    p = jax.nn.softmax(u @ w("router"), axis=-1)                  # [T, E]
    if routing is None:
        idx = group_limited_top_k(p, n_group, topk_group, top_k)
        kept = jnp.ones(idx.shape, jnp.float32)
    else:
        # the program's choices; this file's float32 scores there
        idx = (routing ^ (routing >> 15)).astype(jnp.int32)
        kept = (routing >= 0).astype(jnp.float32)
    gates = jnp.take_along_axis(p, idx, axis=-1)
    if norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    gates = gates * kept * scaling
    # [T, E]: a chosen expert's gate, 0 for the others
    gate = jnp.sum(jax.nn.one_hot(idx, n_experts) * gates[..., None], axis=1)

    def expert(e, acc):
        wg, wu, wd = (jax.lax.dynamic_slice(
            lp[n], (r, e, 0, 0), (1, 1) + lp[n].shape[2:])[0, 0]
            .astype(jnp.float32) for n in ("w_gate", "w_up", "w_down"))
        y = (jax.nn.silu(u @ wg) * (u @ wu)) @ wd
        ge = jax.lax.dynamic_slice_in_dim(gate, first_held + e, 1, axis=1)
        return acc + ge * y

    shared = (jax.nn.silu(u @ w("ws_gate")) * (u @ w("ws_up"))) @ w("ws_down")
    return jax.lax.fori_loop(0, n_held, expert, shared)


@functools.partial(jax.jit, static_argnames=(
    "dense", "n_heads", "kv_rank", "nope", "rope", "v_dim", "eps", "theta",
    "yarn", "n_experts", "top_k", "n_group", "topk_group", "scaling",
    "norm_topk", "first_held", "n_held"))
def layer(x, lp, routing=None, *, r, dense, n_heads, kv_rank, nope, rope,
          v_dim, eps, theta, yarn, n_experts, top_k, n_group, topk_group,
          scaling, norm_topk, first_held, n_held):
    """One layer over one whole sequence; x [T, D] float32, T a multiple of
    Q_BLOCK (padding after the sequence is causal-safe). ``lp`` is the
    program's stack of this pattern position and ``r`` (traced) the
    repeat, sliced in here, an expert at a time. ``routing`` [T, k] int16,
    where given, takes the place of a routed layer's own top-k."""
    with jax.default_matmul_precision("highest"):
        w = lambda name: jax.lax.dynamic_index_in_dim(
            lp[name], r, keepdims=False).astype(jnp.float32)
        h = x + _mla(_rmsnorm(x, w("attn_norm"), eps), w, n_heads, kv_rank,
                     nope, rope, v_dim, eps, theta, yarn)
        u = _rmsnorm(h, w("mlp_norm"), eps)
        if dense:
            return h + (jax.nn.silu(u @ w("w_gate")) * (u @ w("w_up"))
                        ) @ w("w_down")
        return h + _expert_ffn(u, lp, r, w, routing, n_experts, top_k,
                               n_group, topk_group, scaling, norm_topk,
                               first_held, n_held)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, at, final_norm, lm_head, *, eps):
    """Float32 logits [len(at), V] at the positions ``at``."""
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("td,dv->tv", _rmsnorm(x[at], final_norm, eps),
                          lm_head.astype(jnp.float32))


def logits_at(params, dims, tokens, at, routing=None):
    """Reference logits of one sequence at positions ``at``.

    ``params``: the program's weight pytree (module docstring). ``dims``:
    what ``dims()`` returns. ``tokens``: int32 [T], T a multiple of
    Q_BLOCK. ``routing``: int16 [T, L_routed, k], the program's choices to
    follow; ``None`` for the reference's own."""
    dims = dict(dims)
    n_layers, n_dense = dims.pop("n_layers"), dims.pop("n_dense")
    if "lm_head" not in params:
        raise ValueError("the published model does not tie its head; the "
                         "weights have no lm_head")
    x = params["embed"][tokens].astype(jnp.float32)
    n_routed = n_layers - n_dense
    if routing is not None and routing.shape != (
            x.shape[0], n_routed, dims["top_k"]):
        raise ValueError(f"routing {routing.shape} for {x.shape[0]} "
                         f"positions, {n_routed} layers that route and "
                         f"top-{dims['top_k']}")
    l = 0
    for segment in params["segments"]:
        for r in range(segment[0]["attn_norm"].shape[0]):
            for lp in segment:
                if l >= n_layers:
                    raise ValueError("more layers in the weights than the "
                                     f"file's {n_layers}")
                dense = l < n_dense
                if "w_kva" not in lp or ("router" in lp) == dense:
                    raise ValueError(
                        f"layer {l}: the file says latent attention and a "
                        f"{'dense' if dense else 'routed'} FFN; the weights "
                        f"have {sorted(lp)}")
                rows = (None if routing is None or dense
                        else routing[:, l - n_dense])
                x = layer(x, lp, rows, r=r, dense=dense, **dims)
                l += 1
    if l != n_layers:
        raise ValueError(f"{l} layers in the weights, {n_layers} in the file")
    return head(x, at, params["final_norm"], params["lm_head"],
                eps=dims["eps"])
