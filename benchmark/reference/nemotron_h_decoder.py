"""Plain reference: the forward pass of a Nemotron-H decoder (``model_type``
``nemotron_h``) as ONE chip of those that share each layer holds it, in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``. No
kernel, no cache, no pages, no snapshots, no chunked scan, no batching, and
nothing imported from the program: only its weight arrays are read, in the
layout ``models/nemotron_h.py`` documents (``params["segments"][s][j]``:
the ``j``-th layer of a segment's pattern, its weights stacked over the
segment's repeats; the layers in order are segment by segment, repeat by
repeat, pattern position by position). Which layer is of which kind comes
from the **published** ``hybrid_override_pattern`` of the configuration
file (``M`` Mamba-2, ``E`` routed FFN, ``*`` attention), and a layer whose
weights are not of that kind is an error.

``x`` is ``[T, D]``; a layer ``i`` is ONE sublayer behind one norm:

  x = embed[tokens]
  layer i:   x = x + f_i(rmsnorm(x; norm_i))
  logits = rmsnorm(x; final_norm) lm_head                          (untied)

  M (Mamba-2), a token u:
     [z | xBC | dt] = u W_in      (H P | H P + 2 G N | H; no bias)
     xBC_t <- silu(sum_j w_j xBC_{t - (taps - 1) + j} + b)   (causal, depthwise)
     xBC = x [H, P] | B [G, N] | C [G, N];  dt = softplus(dt + dt_bias);
     a = -exp(A_log);  a head h of group g = h // (H / G):
     S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_{g,t}   (S [P, N], S_{-1} = 0)
     y_t = S_t C_{g,t} + D x_t
     y <- rmsnorm over each group of H P / G of (y * silu(z)), a weight
     out = y W_out
     (``dt`` is not clamped: the published ``time_step_min`` / ``_max`` /
     ``_floor`` are initialisers; the norm comes after the gate.)
  * (attention): q, k, v = u W_q, u W_k, u W_v in heads of ``head_dim``
     (``num_attention_heads`` x ``head_dim`` need not be ``hidden_size``);
     causal softmax of q . k / sqrt(head_dim); W_o. NO rotary embedding
     (the configuration file lists this under ``assumed``).
  E (routed FFN): s = sigmoid(u W_r), float32, over all the published
     ``n_routed_experts``; sel = the ``num_experts_per_tok`` largest of
     s + e_score_correction_bias; g = s[sel] / (sum s[sel] + 1e-20) *
     ``routed_scaling_factor``; an expert is un-gated:
     relu(u W_up)^2 W_down;  y = shared(u) + sum_j g_j expert_{sel_j}(u),
     ``shared`` of the same form at ``moe_shared_expert_intermediate_size``.

**The share.** The weights hold experts ``first_held_expert`` .. ``+
n_routed_experts`` (the file's, reduced) of every routed layer, the shared
expert and both mixers whole, and ``vocab_size`` rows of the embedding and
of the head. This file is given the same share: a chosen expert that is not
held adds nothing here, as it adds nothing in the program, and the logits
are over the held rows of the vocabulary.

Departures, all of form and none of mathematics: one sequence at a time;
the recurrence as a ``lax.scan`` over positions; queries in blocks of
``Q_BLOCK`` against the whole context; the held experts computed for every
token, one at a time with one float32 copy of one expert's weights, the
unchosen weighted 0; logits only at the positions asked for.

**It follows the program's routing** (``FOLLOWS_ROUTING``). ``logits_at``
takes, as ``routing`` ``[T, L_routed, k]`` int16, the program's choices
(``e``, or ``~e`` for a choice of an expert it does not hold) and computes
them in place of its own top-k: ``idx = routing ^ (routing >> 15)``, the
gates this file's own float32 rule at ``idx`` (renormalised over all k
chosen, held or not, as the program does), times ``routing >= 0``. The
layer axis counts the ``E`` layers in order. With ``routing=None`` it
chooses for itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 128
FOLLOWS_ROUTING = True
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def dims(cfg):
    """What ``logits_at`` needs of the configuration FILE's published keys
    (never of the program's ``ModelConfig``)."""
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    unknown = set(pattern) - set(KINDS)
    if unknown or len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern {pattern!r} for "
                         f"{cfg['num_hidden_layers']} layers")
    return dict(
        pattern=pattern,
        eps=float(cfg["norm_eps"]),
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_state=cfg["ssm_state_size"], ssm_groups=cfg["n_groups"],
        taps=cfg["conv_kernel"], conv_bias=bool(cfg["use_conv_bias"]),
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_experts=cfg.get("published", {}).get("n_routed_experts",
                                               cfg["n_routed_experts"]),
        n_held=cfg["n_routed_experts"],
        first_held=cfg.get("first_held_expert", 0),
        top_k=cfg["num_experts_per_tok"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]))


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _mamba(u, w, d):
    T = u.shape[0]
    H, P, G, N = d["ssm_heads"], d["ssm_head_dim"], d["ssm_groups"], \
        d["ssm_state"]
    Di, taps = H * P, d["taps"]
    Cd = Di + 2 * G * N
    zxd = u @ w("in_proj")
    # (the program may keep W_in's columns at a lane multiple, zeros past
    # the last dt: they are not read)
    z, xbc, dt = zxd[:, :Di], zxd[:, Di:Di + Cd], zxd[:, Di + Cd:Di + Cd + H]
    cw = w("conv_w")                                        # [taps, Cd]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    c = sum(cw[j] * padded[j:j + T] for j in range(taps))
    if d["conv_bias"]:
        c = c + w("conv_b")
    c = jax.nn.silu(c)
    x = c[:, :Di].reshape(T, H, P)
    Bm = jnp.repeat(c[:, Di:Di + G * N].reshape(T, G, N), H // G, axis=1)
    Cm = jnp.repeat(c[:, Di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w("dt_bias"))                 # [T, H]
    a = -jnp.exp(w("A_log"))

    def step(S, t):
        x_t, b_t, c_t, dt_t = t
        S = (jnp.exp(dt_t * a)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return S, jnp.sum(S * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, Bm, Cm, dt))
    y = (y + w("D")[:, None] * x).reshape(T, Di) * jax.nn.silu(z)
    y = y.reshape(T, G, Di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + d["eps"])
    return (y.reshape(T, Di) * w("gate_norm")) @ w("out_proj")


def _attention(u, w, d):
    T = u.shape[0]
    Hq, Hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    q = (u @ w("wq")).reshape(T, Hkv, Hq // Hkv, hd)
    k = (u @ w("wk")).reshape(T, Hkv, hd)
    v = (u @ w("wv")).reshape(T, Hkv, hd)
    pos = jnp.arange(T)

    def attend(args):
        qb, pb = args                                       # [Bq, Hkv, g, hd]
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) * hd ** -0.5
        s = jnp.where(pos[None, None, None, :] <= pb[None, None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v)

    nb = T // Q_BLOCK
    att = jax.lax.map(attend, (q.reshape(nb, Q_BLOCK, Hkv, Hq // Hkv, hd),
                               pos.reshape(nb, Q_BLOCK)))
    return att.reshape(T, Hq * hd) @ w("wo")


def _relu2(u, w_up, w_down):
    return jnp.square(jax.nn.relu(u @ w_up)) @ w_down


def _experts(u, lp, r, w, routing, d):
    n_experts, n_held, first = d["n_experts"], d["n_held"], d["first_held"]
    if lp["router"].shape[-1] != n_experts:
        raise ValueError(f"the file says {n_experts} routed experts, the "
                         f"router has {lp['router'].shape[-1]} outputs")
    if lp["w_up"].shape[1] != n_held:
        raise ValueError(f"the file says {n_held} experts are held, the "
                         f"weights hold {lp['w_up'].shape[1]}")
    s = jax.nn.sigmoid(u @ w("router"))                     # [T, E]
    if routing is None:
        idx = jax.lax.top_k(s + w("expert_bias"), d["top_k"])[1]
        kept = jnp.ones(idx.shape, jnp.float32)
    else:
        # the program's choices; this file's float32 scores there
        idx = (routing ^ (routing >> 15)).astype(jnp.int32)
        kept = (routing >= 0).astype(jnp.float32)
    gates = jnp.take_along_axis(s, idx, axis=-1)
    if d["norm_topk"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    local = idx - first
    kept = kept * ((local >= 0) & (local < n_held))
    gates = gates * kept * d["scaling"]
    gate = jnp.sum(jax.nn.one_hot(local, n_held) * gates[..., None], axis=1)

    def expert(e, acc):
        wu, wd = (jax.lax.dynamic_slice(
            lp[n], (r, e, 0, 0), (1, 1) + lp[n].shape[2:])[0, 0]
            .astype(jnp.float32) for n in ("w_up", "w_down"))
        ge = jax.lax.dynamic_slice_in_dim(gate, e, 1, axis=1)
        return acc + ge * _relu2(u, wu, wd)

    shared = (_relu2(u, w("ws_up"), w("ws_down")) if "ws_up" in lp
              else jnp.zeros_like(u))
    return jax.lax.fori_loop(0, n_held, expert, shared)


@functools.partial(jax.jit, static_argnames=("kind", "dims"))
def layer(x, lp, routing=None, *, r, kind, dims):
    """One layer over one whole sequence; x [T, D] float32, T a multiple of
    Q_BLOCK (padding after the sequence is causal-safe). ``lp`` is the
    program's stack of this pattern position and ``r`` (traced) the
    repeat. ``routing`` [T, k] int16, where given, takes the place of a
    routed layer's own top-k."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        w = lambda name: jax.lax.dynamic_index_in_dim(
            lp[name], r, keepdims=False).astype(jnp.float32)
        u = _rmsnorm(x, w("norm"), d["eps"])
        if kind == "mamba":
            return x + _mamba(u, w, d)
        if kind == "attention":
            return x + _attention(u, w, d)
        return x + _experts(u, lp, r, w, routing, d)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, at, final_norm, lm_head, *, eps):
    """Float32 logits [len(at), V] at the positions ``at``."""
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("td,dv->tv", _rmsnorm(x[at], final_norm, eps),
                          lm_head.astype(jnp.float32))


_MARK = {"mamba": "in_proj", "attention": "wq", "moe": "router"}


def logits_at(params, dims, tokens, at, routing=None):
    """Reference logits of one sequence at positions ``at``.

    ``params``: the program's weight pytree (module docstring). ``dims``:
    what ``dims()`` returns. ``tokens``: int32 [T], T a multiple of
    Q_BLOCK. ``routing``: int16 [T, L_routed, k], the program's choices to
    follow; ``None`` for the reference's own."""
    pattern = dims["pattern"]
    static = tuple(sorted((k, v) for k, v in dims.items() if k != "pattern"))
    if "lm_head" not in params:
        raise ValueError("the published model does not tie its head; the "
                         "weights have no lm_head")
    x = params["embed"][tokens].astype(jnp.float32)
    n_routed = pattern.count("E")
    if routing is not None and routing.shape != (
            x.shape[0], n_routed, dims["top_k"]):
        raise ValueError(f"routing {routing.shape} for {x.shape[0]} "
                         f"positions, {n_routed} layers that route and "
                         f"top-{dims['top_k']}")
    l = e = 0
    for segment in params["segments"]:
        for r in range(segment[0]["norm"].shape[0]):
            for lp in segment:
                if l >= len(pattern):
                    raise ValueError("more layers in the weights than the "
                                     f"file's {len(pattern)}")
                kind = KINDS[pattern[l]]
                if _MARK[kind] not in lp:
                    raise ValueError(
                        f"layer {l}: the file says {kind}; the weights "
                        f"have {sorted(lp)}")
                rows = None
                if kind == "moe":
                    rows = None if routing is None else routing[:, e]
                    e += 1
                x = layer(x, lp, rows, r=r, kind=kind, dims=static)
                l += 1
    if l != len(pattern):
        raise ValueError(f"{l} layers in the weights, {len(pattern)} in "
                         "the file")
    return head(x, at, params["final_norm"], params["lm_head"],
                eps=dims["eps"])
