"""Plain reference: the forward pass of a Mixtral-style decoder with sparse
experts as the model card and the published modelling code describe it, in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``.
No kernel, no cache, no batching, no capacity, and nothing imported from
the program: only its weight arrays are read, in the layout
``models/mixtral.py`` documents (per-layer weights stacked on a leading
axis, expert weights on a second one).

  x = embed[tokens]
  per layer:  attention exactly as ``decoder.py`` has it (rmsnorm, q k v,
              split-half rope, causal GQA softmax, wo, residual)
              h = rmsnorm(x, mlp_norm);   r = h router           [T, E]
              for each token: the top_k experts by r, gates = softmax over
              those top_k logits alone (Mixtral; the gates sum to 1)
              x += sum over the chosen e of
                   gate_e * (silu(h w_gate[e]) * (h w_up[e])) w_down[e]
  logits = rmsnorm(x, final_norm) lm_head        (embed^T when tied)

Every token reaches every expert it chose: there is no capacity and no
dropped token, as in every published sparse-expert model at inference. The
program's ``moe_block`` has a capacity of ``N * top_k * 2 / E`` and drops
what exceeds it; where that bites, program and reference disagree, and
``tests/benchmark/test_bench_moe_reference.py`` keeps a case on record.

Departures, all of form and none of mathematics: one sequence at a time;
queries are taken in blocks against the whole context; every expert is
computed for every token of a block and the unchosen ones are weighted 0
(plain, and dropless by construction; at E / top_k times the arithmetic);
logits are computed only at the positions asked for. The attention half is
a copy of ``decoder.py``'s and not an import, so that each reference reads
whole and an architecture that changes attention changes its own file.

What this file can hold a program to (``PERF.md`` section 6, PR 29): the
choice of experts is a step function of the router's logits, so where the
k-th and the (k+1)-th lie closer than bf16's noise a correct program
chooses another expert than this file and its logits jump. At the tests'
tiny widths (top-2 of 4, 2 layers) one position in a hundred does, and
``tiny-moe.chat`` reads a gap over the tolerance on some seeds with
nothing wrong; at published router widths 5-59% of positions do. Setting
aside the positions whose own router margin is small was measured and
does not carry such widths, because the changed choices of a position's
context move it as far. A cell of a routed configuration therefore waits
for a program that reports its chosen experts and a reference that
follows them (``benchmark/calibrate_routing.py``'s ``followed_*``
readings)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256


def dims(cfg_file):
    """What ``logits_at`` needs beside the weights, from the keys of the
    published ``config.json`` in the configuration file."""
    return dict(n_heads=cfg_file["num_attention_heads"],
                n_kv_heads=cfg_file["num_key_value_heads"],
                eps=float(cfg_file["rms_norm_eps"]),
                theta=float(cfg_file["rope_theta"]),
                n_experts=cfg_file["num_local_experts"],
                top_k=cfg_file["num_experts_per_tok"])


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
                                             "theta", "n_experts", "top_k"))
def layer(x, lp, *, n_heads, n_kv_heads, eps, theta, n_experts, top_k):
    """One decoder layer over one whole sequence; x [T, D] float32,
    T a multiple of Q_BLOCK (padding after the sequence is causal-safe,
    and without a capacity a padded token takes nothing from a real one)."""
    if lp["router"].shape[-1] != n_experts:
        raise ValueError(f"the file says {n_experts} experts, the weights "
                         f"have {lp['router'].shape[-1]}")
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

        router, w_gate, w_up, w_down = (f32(lp[n]) for n in (
            "router", "w_gate", "w_up", "w_down"))

        def experts(xb):                        # [Bq, D]
            hb = _rmsnorm(xb, lp["mlp_norm"], eps)
            top, idx = jax.lax.top_k(hb @ router, top_k)
            # [Bq, E]: a chosen expert's gate, 0 for the others
            gates = jnp.sum(jax.nn.one_hot(idx, n_experts)
                            * jax.nn.softmax(top, axis=-1)[..., None], axis=1)
            act = (jax.nn.silu(jnp.einsum("td,edf->tef", hb, w_gate))
                   * jnp.einsum("td,edf->tef", hb, w_up))
            return jnp.einsum("te,tef,efd->td", gates, act, w_down)

        return x + jax.lax.map(experts,
                               x.reshape(nb, Q_BLOCK, D)).reshape(T, D)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, at, final_norm, lm_head, *, eps):
    """Float32 logits [len(at), V] at the positions ``at``."""
    with jax.default_matmul_precision("highest"):
        return (_rmsnorm(x[at], final_norm, eps)
                @ lm_head.astype(jnp.float32))


def logits_at(params, dims, tokens, at):
    """Reference logits of one sequence at positions ``at``.

    ``params``: the weight pytree (``embed``, ``layers`` stacked [L, ...]
    with ``router`` [L, D, E] and ``w_gate`` / ``w_up`` [L, E, D, F],
    ``w_down`` [L, E, F, D], ``final_norm``, ``lm_head`` unless tied).
    ``dims``: what ``dims()`` returns. ``tokens``: int32 [T], T a multiple
    of Q_BLOCK."""
    x = params["embed"][tokens].astype(jnp.float32)
    n_layers = params["layers"]["wq"].shape[0]
    for i in range(n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = layer(x, lp, **dims)
    lm_head = params.get("lm_head")
    if lm_head is None:
        lm_head = params["embed"].T
    return head(x, at, params["final_norm"], lm_head, eps=dims["eps"])
