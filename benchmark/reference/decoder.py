"""Plain reference: the forward pass of a Llama-style dense decoder
(Mistral-7B, Yi-1.5) as the model cards describe it, in ``jax.numpy`` and
float32 under ``default_matmul_precision("highest")``. No kernel, no
cache, no batching, and nothing imported from the program: only its
weight arrays are read, in the layout ``models/llama.py`` documents
(per-layer weights stacked on a leading axis).

  x = embed[tokens]
  per layer:  h = rmsnorm(x, attn_norm);  q, k, v = h wq, h wk, h wv
              q, k = rope(q), rope(k)     (split-half pairs, as HF Llama)
              x += softmax(causal(q k^T / sqrt(hd))) v  wo    (GQA: query
                   head h reads KV head h // (Hq / Hkv))
              x += (silu(rmsnorm(x, mlp_norm) w_gate) * (.. w_up)) w_down
  logits = rmsnorm(x, final_norm) lm_head        (embed^T when tied)

Departures, all of form and none of mathematics: one sequence at a time;
queries are taken in blocks against the whole context so that a 4096-token
context does not need a [heads, T, T] score tensor at once; logits are
computed only at the positions asked for."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, H, hd]; rotate pairs (x[..., i], x[..., i + hd/2])."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "eps",
                                             "theta"))
def layer(x, lp, *, n_heads, n_kv_heads, eps, theta):
    """One decoder layer over one whole sequence; x [T, D] float32,
    T a multiple of Q_BLOCK (padding after the sequence is causal-safe)."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        T, D = x.shape
        hd = D // n_heads
        g = n_heads // n_kv_heads
        pos = jnp.arange(T)
        h = _rmsnorm(x, lp["attn_norm"], eps)
        q = _rope((h @ f32(lp["wq"])).reshape(T, n_heads, hd), pos, theta)
        k = _rope((h @ f32(lp["wk"])).reshape(T, n_kv_heads, hd), pos, theta)
        v = (h @ f32(lp["wv"])).reshape(T, n_kv_heads, hd)

        def attend(args):
            qb, pb = args                       # [Bq, Hkv, g, hd], [Bq]
            s = jnp.einsum("qkgd,skd->kgqs", qb, k) / jnp.sqrt(
                jnp.float32(hd))
            s = jnp.where(pos[None, None, None, :] <= pb[None, None, :, None],
                          s, -jnp.inf)
            return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v)

        nb = T // Q_BLOCK
        att = jax.lax.map(attend, (
            q.reshape(nb, Q_BLOCK, n_kv_heads, g, hd),
            pos.reshape(nb, Q_BLOCK)))
        x = x + att.reshape(T, n_heads * hd) @ f32(lp["wo"])

        def mlp(xb):                            # [Bq, D]
            hb = _rmsnorm(xb, lp["mlp_norm"], eps)
            return (jax.nn.silu(hb @ f32(lp["w_gate"]))
                    * (hb @ f32(lp["w_up"]))) @ f32(lp["w_down"])

        return x + jax.lax.map(mlp, x.reshape(nb, Q_BLOCK, D)).reshape(T, D)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, at, final_norm, lm_head, *, eps):
    """Float32 logits [len(at), V] at the positions ``at``."""
    with jax.default_matmul_precision("highest"):
        return (_rmsnorm(x[at], final_norm, eps)
                @ lm_head.astype(jnp.float32))


def logits_at(params, dims, tokens, at):
    """Reference logits of one sequence at positions ``at``.

    ``params``: the weight pytree (``embed``, ``layers`` stacked [L, ...],
    ``final_norm``, ``lm_head`` unless tied). ``dims``: n_heads,
    n_kv_heads, eps, theta. ``tokens``: int32 [T], T a multiple of
    Q_BLOCK."""
    x = params["embed"][tokens].astype(jnp.float32)
    n_layers = params["layers"]["wq"].shape[0]
    for i in range(n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = layer(x, lp, **dims)
    lm_head = params.get("lm_head")
    if lm_head is None:
        lm_head = params["embed"].T
    return head(x, at, params["final_norm"], lm_head, eps=dims["eps"])
