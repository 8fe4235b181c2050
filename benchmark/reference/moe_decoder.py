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
computed for every token, one expert at a time, and the unchosen ones are
weighted 0 (plain, and dropless by construction; at E / top_k times the
arithmetic, with one float32 copy of one expert's weights at a time beside
the program's own arrays, so that the check fits a chip that holds them);
logits are computed only at the positions asked for. The attention half is
a copy of ``decoder.py``'s and not an import, so that each reference reads
whole and an architecture that changes attention changes its own file.

**It follows the program's routing** (``FOLLOWS_ROUTING``; ``PERF.md``
section 4 and section 6, PRs 29, 33 and 35). The choice of experts is a
step function of the router's logits, so where the k-th and the (k+1)-th
lie closer than bf16's noise a correct program chooses another expert than
this file would and its logits jump by 0.1-2: at the tests' tiny widths
(top-2 of 4, 2 layers) one position in a hundred does, at published
router widths 5-59% of positions do. So ``logits_at`` takes, as ``routing``
``[T, L_routed, k]`` int16, what the program reports it chose
(``GenRequest.routing``: the expert ``e``, or ``~e`` for a choice the
program made and then left out over its capacity; the layer axis counts
the layers that route, in order, which here is every layer). A layer's
top-k is then replaced by ``idx = routing ^ (routing >> 15)``; its gates
are this file's own rule (the softmax over its own float32 router logits
at ``idx``) times ``routing >= 0``: a dropped choice adds nothing and the
others are not renormalised, which is what the program computed.
Everything else is the reference as it is. With ``routing=None`` it
chooses by its own top-k and drops nothing."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256
# logits_at takes the program's reported routing (harness/check.py)
FOLLOWS_ROUTING = True


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
def layer(x, layers, routing=None, *, i, n_heads, n_kv_heads, eps, theta,
          n_experts, top_k):
    """Layer ``i`` of the stacked weights ``layers`` over one whole
    sequence; x [T, D] float32, T a multiple of Q_BLOCK (padding after the
    sequence is causal-safe, and without a capacity a padded token takes
    nothing from a real one). ``routing`` [T, k] int16, where given, is
    this layer's rows of the program's record and takes the place of the
    layer's own top-k (module docstring). The stack is passed whole and
    sliced in here, an expert at a time: a slice made outside would be a
    copy of the layer's every expert. ``i`` is traced, so one compiled
    program serves every layer of a sequence length."""
    if layers["router"].shape[-1] != n_experts:
        raise ValueError(f"the file says {n_experts} experts, the weights "
                         f"have {layers['router'].shape[-1]}")
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        w = lambda name: jax.lax.dynamic_index_in_dim(layers[name], i,
                                                      keepdims=False)
        T, D = x.shape
        hd = D // n_heads
        g = n_heads // n_kv_heads
        pos = jnp.arange(T)
        h = _rmsnorm(x, w("attn_norm"), eps)
        q = _rope((h @ f32(w("wq"))).reshape(T, n_heads, hd), pos, theta)
        k = _rope((h @ f32(w("wk"))).reshape(T, n_kv_heads, hd), pos, theta)
        v = (h @ f32(w("wv"))).reshape(T, n_kv_heads, hd)

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
        x = x + att.reshape(T, n_heads * hd) @ f32(w("wo"))

        hb = _rmsnorm(x, w("mlp_norm"), eps)
        r = hb @ f32(w("router"))                               # [T, E]
        if routing is None:
            idx = jax.lax.top_k(r, top_k)[1]
            kept = jnp.ones(idx.shape, jnp.float32)
        else:
            # the program's choices; this file's float32 logits there
            idx = (routing ^ (routing >> 15)).astype(jnp.int32)
            kept = (routing >= 0).astype(jnp.float32)
        gates = jax.nn.softmax(jnp.take_along_axis(r, idx, axis=-1),
                               axis=-1) * kept                  # [T, k]
        # [T, E]: a chosen expert's gate, 0 for the others
        gate = jnp.sum(jax.nn.one_hot(idx, n_experts) * gates[..., None],
                       axis=1)

        def expert(e, acc):
            wg, wu, wd = (f32(jax.lax.dynamic_slice(
                layers[n], (i, e, 0, 0), (1, 1) + layers[n].shape[2:])[0, 0])
                for n in ("w_gate", "w_up", "w_down"))
            y = (jax.nn.silu(hb @ wg) * (hb @ wu)) @ wd
            return acc + gate[:, e][:, None] * y

        return x + jax.lax.fori_loop(0, n_experts, expert, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, at, final_norm, lm_head, *, eps):
    """Float32 logits [len(at), V] at the positions ``at``."""
    with jax.default_matmul_precision("highest"):
        return (_rmsnorm(x[at], final_norm, eps)
                @ lm_head.astype(jnp.float32))


def logits_at(params, dims, tokens, at, routing=None):
    """Reference logits of one sequence at positions ``at``.

    ``params``: the weight pytree (``embed``, ``layers`` stacked [L, ...]
    with ``router`` [L, D, E] and ``w_gate`` / ``w_up`` [L, E, D, F],
    ``w_down`` [L, E, F, D], ``final_norm``, ``lm_head`` unless tied).
    ``dims``: what ``dims()`` returns. ``tokens``: int32 [T], T a multiple
    of Q_BLOCK. ``routing``: int16 [T, L, k], the program's choices to
    follow (module docstring); ``None`` for the reference's own."""
    x = params["embed"][tokens].astype(jnp.float32)
    n_layers = params["layers"]["wq"].shape[0]
    if routing is not None and routing.shape != (
            x.shape[0], n_layers, dims["top_k"]):
        raise ValueError(f"routing {routing.shape} for {x.shape[0]} "
                         f"positions, {n_layers} layers that route and "
                         f"top-{dims['top_k']}")
    for i in range(n_layers):
        x = layer(x, params["layers"],
                  None if routing is None else routing[:, i], i=i, **dims)
    lm_head = params.get("lm_head")
    if lm_head is None:
        lm_head = params["embed"].T
    return head(x, at, params["final_norm"], lm_head, eps=dims["eps"])
