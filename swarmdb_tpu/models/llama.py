"""The decoder, and the Llama-3 family's parameters — functional JAX.

Design (idiomatic TPU, not a torch port):
- The layer skeleton (``layer_step``: norm, token mixer, residual, norm,
  FFN, residual), its attention form (``decoder_layer``) and the head
  (``lm_logits``) are written once. Every forward is its own
  attention-and-cache step (its ``mixer``) around them, and a model family
  is its FFN (``_ffn``) and its parameters: a routed configuration's are
  in models/mixtral.py, and models/lfm2.py has the family whose mixer
  differs a layer (a gated short convolution or attention) with the state
  its conv layers carry, and models/deepseek.py the family that attends
  through a latent (its token mixer, its row a token in place of keys and
  values a head), and models/nemotron_h.py the family whose layer is ONE
  sublayer (a Mamba-2 mixer, attention or a routed FFN alone) with the
  Mamba-2 state a sequence carries; ``run_stack`` is where a forward hands
  its mixers to the one or the other.
- Parameters are a plain pytree dict; per-layer weights are STACKED along a
  leading [L, ...] axis and the forward pass is one `lax.scan` over layers —
  one compiled layer body regardless of depth (fast compiles, natural hook
  for pipeline parallelism later).
- Same forward for prefill ([B, T] tokens) and decode ([B, 1]): each batch
  row carries its own absolute positions, and K/V are scattered into a
  fixed-shape slot cache — the continuous-batching engine admits/retires
  sequences by rewriting slot state, never by changing shapes.
- Tensor parallelism is expressed as PartitionSpecs over a 'model' mesh axis
  (`param_specs`): attention/MLP column-sharded in, row-sharded out, GSPMD
  inserts the all-reduces (SURVEY §2.4 TP row).

The reference has no model layer (SURVEY §2.4); this is the north-star
serving backend for Llama-3-8B/70B (BASELINE.json configs 2, 3, 5).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.layers import (
    gqa_attention,
    gqa_attention_chunked,
    gqa_attention_prefix,
    merge_chunk_kv,
    qkv_proj,
    rms_norm,
    rope_cos_sin,
    swiglu,
    write_kv_cache,
)
from .configs import ModelConfig

Params = Dict[str, Any]
KVCache = Tuple[jnp.ndarray, jnp.ndarray]  # each [L, B, S, Hkv, D]


# ---------------------------------------------------------------------- init


@functools.partial(jax.jit, static_argnames=("shape", "fan_in", "dtype"))
def random_dense(key: jax.Array, shape, fan_in: int, dtype) -> jnp.ndarray:
    """One ``normal / sqrt(fan_in)`` weight in ``dtype``. Jitted per
    tensor so the float32 draw fuses into the cast: drawn eagerly, a
    stacked 16-layer ``w_gate`` at Llama-3-8B widths leaves two 3.76 GB
    float32 temporaries on the device beside the weights."""
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(fan_in)).astype(dtype)


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype = jnp.bfloat16
) -> Params:
    """Random init (serving weights normally come from a checkpoint; random
    params exercise identical shapes/compute for tests and benches)."""
    if cfg.is_moe:
        raise ValueError(
            f"{cfg.name!r} is a MoE config (n_experts={cfg.n_experts}); "
            "use swarmdb_tpu.models.mixtral, not the dense Llama stack"
        )
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    L, D, F = cfg.n_layers, cfg.dim, cfg.ffn_dim
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def dense(key, shape, fan_in):
        return random_dense(key, shape, fan_in, dtype)

    ks = jax.random.split(k_layers, 7)
    params: Params = {
        "embed": dense(k_embed, (cfg.vocab_size, D), D),
        "layers": {
            "attn_norm": jnp.ones((L, D), dtype),
            "wq": dense(ks[0], (L, D, Hq * hd), D),
            "wk": dense(ks[1], (L, D, Hkv * hd), D),
            "wv": dense(ks[2], (L, D, Hkv * hd), D),
            "wo": dense(ks[3], (L, Hq * hd, D), Hq * hd),
            "mlp_norm": jnp.ones((L, D), dtype),
            "w_gate": dense(ks[4], (L, D, F), D),
            "w_up": dense(ks[5], (L, D, F), D),
            "w_down": dense(ks[6], (L, F, D), F),
        },
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k_head, (D, cfg.vocab_size), D)
    return params


def param_specs(cfg: ModelConfig, model_axis: str = "model") -> Params:
    """PartitionSpecs for tensor parallelism over ``model_axis``.

    Megatron-style: QKV/gate/up column-parallel (shard output features),
    O/down row-parallel (shard input features) — one all-reduce per block,
    emitted by GSPMD. Embedding/head shard the vocab dimension.
    """
    m = model_axis
    specs: Params = {
        "embed": P(m, None),        # vocab-sharded
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, m),
            "wk": P(None, None, m),
            "wv": P(None, None, m),
            "wo": P(None, m, None),
            "mlp_norm": P(None, None),
            "w_gate": P(None, None, m),
            "w_up": P(None, None, m),
            "w_down": P(None, m, None),
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, m)
    return specs


LANES = 128      # a TPU vector register's lane width


def kv_head_dim(cfg: ModelConfig) -> int:
    """Head size the PAGED pool and the decode chunk buffers are kept at.
    The chip lays an array's last dimension out in 128 lanes, so a pool
    of 64-wide heads occupies what one of 128-wide heads does, and its
    compiler refuses the paged kernels' page copies at a width that is
    not a lane multiple (PR 36: "slice shape along dimension 3 must be
    aligned to tiling (128), but is 64"). Where that bites, the pool is
    kept at the next lane multiple, the upper lanes zero: the same
    bytes, and the kernels run at the geometry they are proven at.
    ``forward_ragged_prefill`` and ``forward_paged_chunked`` pad q, k, v
    to the pool's width and cut the output back (``_at_pool_width``);
    the other paged forwards take the pool at the configuration's own
    head size, so only a configuration confined to those two (one with
    conv state) is widened, and only on a TPU, where the kernels run."""
    hd = cfg.head_dim
    if hd % LANES and cfg.stateful and jax.default_backend() == "tpu":
        return -(-hd // LANES) * LANES
    return hd


def _at_pool_width(q, k, v, width: int):
    """q, k, v ``[..., D]`` zero-padded to the pool's ``width``: scores
    are unchanged but for the kernels' ``1 / sqrt(width)``, which q takes
    back here; the output's upper lanes are zeros the caller cuts off."""
    D = q.shape[-1]
    if width == D:
        return q, k, v
    pad = [(0, 0)] * (q.ndim - 1) + [(0, width - D)]
    q = q * jnp.asarray((width / D) ** 0.5, q.dtype)
    return jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_seq: int, dtype: jnp.dtype = jnp.bfloat16
) -> KVCache:
    shape = (cfg.n_attn_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_paged_cache(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    num_pages: int,
    page_size: int,
    dtype: Optional[jnp.dtype] = None,
):
    """Block-paged KV pool (ops/paged_kv.py): HBM ∝ num_pages*page_size,
    not batch*max_seq. Returns {"k", "v", "page_table"} over the layers
    that attend and, for a configuration whose conv layers carry state,
    {"state", "page_state"} beside them. ``dtype=None``
    resolves SWARMDB_KV_DTYPE (bf16 default; int8 yields QuantPool
    entries — see ops/paged_kv.py)."""
    from ..ops.paged_kv import init_paged_kv_cache

    if cfg.latent:
        # the second page format (models/deepseek.py): ONE pool of rows
        # ``[L, P, ps, row_width]``, no heads axis and no value pool. It
        # sits under ``"k"`` (every head's keys are read from it, and
        # its first ``kv_lora_rank`` lanes are the values) with ``"v"``
        # a ``NoValuePool``, so that the allocator, the pins, the prefix
        # cache and the engine's programs manage it under the page ids
        # and the arguments they have
        from ..ops.paged_kv import (KV_DTYPES, NoValuePool, kv_dtype_name,
                                    kv_quantized, pages_per_slot)
        from . import deepseek

        if dtype is None:
            if kv_quantized():
                deepseek.refuse_latent(
                    cfg, "an int8 pool (SWARMDB_KV_DTYPE=int8: QuantPool "
                         "keeps a scale a page and a head)")
            dtype = KV_DTYPES[kv_dtype_name()]
        return {
            "k": jnp.zeros((cfg.n_layers, num_pages, page_size,
                            deepseek.row_width(cfg)), dtype),
            "v": NoValuePool(),
            "page_table": jnp.zeros(
                (batch, pages_per_slot(max_seq, page_size)), jnp.int32),
            "pos0": jnp.zeros((batch,), jnp.int32)}
    cache = init_paged_kv_cache(
        cfg.n_attn_layers, num_pages, page_size, cfg.n_kv_heads,
        kv_head_dim(cfg), batch, max_seq, dtype,
    )
    if cfg.stateful:
        # state beside pages (models/lfm2.py): ``state`` is what each
        # slot's live sequence carries, ``page_state`` what the sequence
        # that wrote a page carried at that page's end, under the page's
        # own id: the allocator, the pins and the prefix cache's LRU that
        # manage a page's keys and values manage its state with them
        from ..ops.paged_kv import is_quantized, pool_dtype
        from . import lfm2, nemotron_h

        dt = pool_dtype(cache["k"])
        if cfg.n_ssm_layers and is_quantized(cache["k"]):
            refuse_state(cfg, "an int8 pool (SWARMDB_KV_DTYPE=int8: no "
                              "test and no chip run holds this family's "
                              "waves to quantized pages)")
        if cfg.n_ssm_layers:
            # a Mamba-2 layer's state is hundreds of times a page's keys
            # and values: no state a page but a pool of SNAPSHOTS, far
            # fewer than pages, each owned by a page while it lives
            # (``PrefixLRU.keep_state_slots``); row 0 is the bin
            if cfg.state_snapshots < 1:
                raise ValueError(
                    f"{cfg.name!r}: a paged pool for Mamba-2 layers needs "
                    "state_snapshots, how many snapshots it keeps (each "
                    "as large as a slot's state: what is left of the "
                    "chip decides)")
            cache["state"] = nemotron_h.init_state(cfg, batch, dt)
            cache["page_state"] = nemotron_h.init_state(
                cfg, 1 + cfg.state_snapshots, dt)
        else:
            cache["state"] = lfm2.init_state(cfg, batch, dt)
            cache["page_state"] = lfm2.init_state(cfg, num_pages, dt)
    return cache


# --------------------------------------------------------- the decoder block


def _ffn(cfg: ModelConfig, moe_dispatch: Optional[str] = None):
    """The family's FFN, chosen from the configuration: ``swiglu`` for a
    dense one, ``mixtral.moe_block`` for one that routes. ``moe_dispatch``
    pins the routed form's realization (``parallel/serving`` pins
    ``einsum`` on an expert-sharded mesh); ``None`` keeps the module
    default (models/mixtral.py docstring). A dense FFN returns its
    output; a routed one returns ``(output, routing [B, T, k])``."""
    if not cfg.is_moe:
        return lambda h, lp: swiglu(h, lp["w_gate"], lp["w_up"],
                                    lp["w_down"])
    from .mixtral import moe_block

    def routed(h, lp):
        # the forwards carry the cache and the routing. The mean load is
        # for direct moe_block callers: it averages over every row of the
        # call, dead lanes and padding too, so the engine reckons the
        # served path's balance from the routing of its live rows instead
        # (Engine._process_host_block, ``moe_load_max_over_mean``)
        out, _load, routing = moe_block(
            h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            top_k=cfg.experts_per_token, dispatch=moe_dispatch)
        return out, routing

    return routed


def layer_step(cfg: ModelConfig, token_mixer, ffn, routed: bool):
    """THE layer skeleton, for every family: norm, the layer's token mixer,
    residual, norm, the layer's FFN, residual. ``token_mixer(h, lp, ops)
    -> (mixed [B, T, D], out)`` is attention (``attention_token_mixer``)
    or a family's own (``lfm2.conv_token_mixer``); ``ffn(h, lp)`` returns
    its output, or ``(output, routing [B, T, k])`` where ``routed``.
    Returns ``step(x, lp, ops) -> (x, out, routing or None)``."""

    def step(x, lp, ops):
        mixed, out = token_mixer(rms_norm(x, lp["attn_norm"], cfg.norm_eps),
                                 lp, ops)
        x = x + mixed
        y = ffn(rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp)
        if routed:
            y, routing = y
            return x + y, out, routing
        return x + y, out, None

    return step


def attention_token_mixer(cfg: ModelConfig, cos, sin, mixer):
    """Attention as a token mixer of ``layer_step``: ``qkv_proj`` (with the
    q/k norm of a layer that has one), the forward's ``mixer`` (its cache
    step and attention), ``wo``."""

    def token_mixer(h, lp, ops):
        q, k, v = qkv_proj(h, lp, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim, cos, sin, cfg.norm_eps)
        attn, out = mixer(q, k, v, ops)
        B, T = h.shape[0], h.shape[1]
        return jnp.einsum("bth,hd->btd", attn.reshape(B, T, -1),
                          lp["wo"]), out

    return token_mixer


def decoder_layer(cfg: ModelConfig, cos, sin, mixer,
                  moe_dispatch: Optional[str] = None):
    """``layer_step`` with attention in every layer, as the body of a
    ``lax.scan`` over the stacked layers: attention norm, ``qkv_proj``,
    the caller's ``mixer``, ``wo`` and residual, MLP norm, the family's
    FFN (``_ffn``), residual. Every forward below runs this body, with a
    mixer of its own, over a configuration whose layers all attend.

    ``mixer(q, k, v, ops)`` is all that differs between the forwards: it
    takes the layer's RoPE'd projections and the layer's slice ``ops`` of
    whatever the forward scans beside the weights (cache layers, chunk
    buffers, a layer index), does its cache write and attention, and
    returns ``(attn [.., T, Hq, hd], out)``. The body takes
    ``(x, (layer_params, ops))`` and returns ``(x, out)``, so the scan
    stacks ``out`` over layers. Where the configuration routes, the body
    returns ``(x, (out, routing))`` and the scan stacks the layer's
    routing ``[B, T, k]`` beside it: ``take_routing`` splits the two."""
    step = layer_step(cfg, attention_token_mixer(cfg, cos, sin, mixer),
                      _ffn(cfg, moe_dispatch), cfg.is_moe)

    def scan_body(x, scanned):
        x, out, routing = step(x, *scanned)
        return x, (out if routing is None else (out, routing))

    return scan_body


def take_routing(cfg: ModelConfig, out):
    """Split what a scan of ``decoder_layer`` stacked into the mixer's
    outputs and what a forward returns after its other outputs: for a
    configuration that routes ``(routing,)``, position-major
    (``[L_routed, B, T, k]`` as stacked becomes ``[B, T, L_routed, k]``,
    a row a position as ``GenRequest.routing`` holds it); for a dense one
    ``()``, so its forwards return what they always did."""
    if not cfg.is_moe:
        return out, ()
    out, routing = out
    return out, (jnp.moveaxis(routing, 0, 2),)


def run_stack(params: Params, cfg: ModelConfig, x, cos, sin, mixer, ops,
              moe_dispatch: Optional[str] = None, history=None,
              conv_ops=None, live=None, mamba=None):
    """``x`` through every layer, for a forward that brings its mixers:
    ``(x, attention outs, conv outs, routing)``. A configuration whose
    layers all attend is one ``lax.scan`` of ``decoder_layer`` over
    ``params["layers"]`` (``ops`` beside them; conv outs None); one with
    ``layer_types`` goes to ``lfm2.run_layers``, which also takes the
    forward's ``history`` and ``conv_ops`` for its conv layers and
    ``live``, the rows that take part, for its expert FFN; a latent one
    to ``deepseek.run_layers``, whose ``mixer(q, row, ops)`` attends over
    rows (``deepseek.latent_token_mixer``); one whose layer is a
    sublayer to ``nemotron_h.run_layers``, with ``mamba``, the forward's
    ``(Mamba-2 token mixer, aux)`` (the three ``nemotron_h.*_mixers``:
    ``aux`` is carried whole through the layers and a layer is handed its
    index), and its third result is ``(mamba outs, aux)``. ``routing`` is
    what ``take_routing`` returns."""
    if cfg.sublayers:
        from . import nemotron_h

        mamba_tm, aux = mamba or (None, None)
        return nemotron_h.run_layers(
            params, cfg, x, attention_token_mixer(cfg, cos, sin, mixer),
            ops, mamba_tm, live, aux)
    if cfg.latent:
        from . import deepseek

        return deepseek.run_layers(params, cfg, x, cos, sin, mixer, ops,
                                   live)
    if cfg.layer_types is None:
        x, out = jax.lax.scan(
            decoder_layer(cfg, cos, sin, mixer, moe_dispatch), x,
            (params["layers"], ops))
        out, routing = take_routing(cfg, out)
        return x, out, None, routing
    from . import lfm2

    return lfm2.run_layers(params, cfg, x, cos, sin, mixer, ops, history,
                           conv_ops, live)


def refuse_state(cfg: ModelConfig, path: str) -> None:
    """A path that cannot carry a conv layer's state refuses the
    configuration by name; none runs it wrong. Those paths all assume
    keys and values a head too, so a latent configuration is refused
    here with them (``deepseek.refuse_latent``)."""
    if cfg.latent:
        from . import deepseek

        deepseek.refuse_latent(cfg, path)
    if cfg.n_ssm_layers:
        raise NotImplementedError(
            f"{cfg.name!r} has Mamba-2 layers whose recurrent state rides "
            f"beside the KV pages, a snapshot for a prefix hit; {path} "
            "does not carry Mamba-2 state (the paged engine's ragged "
            "prefill and chunked decode do)")
    if cfg.stateful:
        raise NotImplementedError(
            f"{cfg.name!r} has conv layers whose recurrent state rides "
            f"beside the KV pages; {path} does not carry conv state (the "
            "paged engine's ragged prefill and chunked decode do)")


def rope_terms(cfg: ModelConfig, positions: jnp.ndarray):
    """RoPE's (cos, sin) for a forward: over the head size at
    ``rope_theta``, or a latent configuration's own (YaRN over its rope
    dims, ``deepseek.rope_terms``)."""
    if cfg.latent:
        from . import deepseek

        return deepseek.rope_terms(cfg, positions)
    if not cfg.rope:
        # attention that does not rotate: the identity's terms
        shape = positions.shape + (1, cfg.head_dim // 2)
        return jnp.ones(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    return rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)


def lm_logits(params: Params, cfg: ModelConfig, x: jnp.ndarray,
              logits_at: Optional[jnp.ndarray] = None,
              stream_at: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Final norm and LM head, fp32 logits, in the three shapes the
    forwards use: ``[B, T, V]``; ``[B, V]`` at each row's ``logits_at``
    position; ``[R, V]`` at the ``stream_at`` offsets of a packed
    ``[1, W, D]`` stream. A configuration with tied embeddings has no
    ``lm_head`` and uses the transposed embedding."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:  # tied embeddings
        head = params["embed"].T
    if stream_at is not None:
        x = x[0, stream_at]                              # [R, D]
    elif logits_at is not None:
        x = x[jnp.arange(x.shape[0]), logits_at]         # [B, D]
    return jnp.einsum("btd,dv->btv" if x.ndim == 3 else "bd,dv->bv",
                      x, head, preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ forwards


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,       # [B, T] int32
    positions: jnp.ndarray,    # [B, T] int32 absolute positions per row
    cache: KVCache,            # ([L, B, S, Hkv, hd], ...)
    logits_at: Optional[jnp.ndarray] = None,  # [B] int32 row indices into T
    moe_dispatch: Optional[str] = None,
) -> Tuple[jnp.ndarray, KVCache]:
    """One forward pass; returns fp32 logits and updated cache and, where
    the configuration routes, the routing ``[B, T, L_routed, k]`` last
    (``take_routing``; so it is with every forward below).

    Works for mixed prefill/decode batches: each row's ``positions`` are its
    own absolute offsets, and attention masks by position (ops/layers.py).

    ``logits_at`` computes the LM head ONLY at each row's named position,
    returning [B, V] instead of [B, T, V] — same math (head columns are
    per-position independent; only reduction tiling can differ) while
    skipping the full-bucket fp32 logits the prefill path would otherwise
    materialize (0.5 GB per admission wave at Bp=16, T=255, V=32k, and
    ~7% of prefill FLOPs).
    """
    if cfg.latent:
        from . import deepseek

        deepseek.refuse_latent(
            cfg, "the whole-sequence forward over a slab cache "
                 "(llama.forward; deepseek.forward is this family's)")
    x = params["embed"][tokens]  # [B, T, D]; compute dtype = param dtype
    # RoPE terms depend only on positions: compute once, reuse in every
    # scanned layer (XLA can't hoist transcendentals out of the loop body)
    cos, sin = rope_terms(cfg, positions)

    def mixer(q, k, v, kv):
        ck, cv = write_kv_cache(*kv, k, v, positions)
        attn = gqa_attention(q, ck, cv, positions, window=cfg.sliding_window)
        return attn, (ck, cv)

    history = mamba = None
    if cfg.n_ssm_layers:
        # likewise for Mamba-2 layers: the plain recurrence from zeros
        from . import nemotron_h

        mamba = nemotron_h.whole_mixers(cfg)
    elif cfg.stateful:
        # the plain whole-sequence forward of a configuration with conv
        # layers: rows start at position 0 with nothing before them, and
        # the cache is the attending layers' alone. What the served paths
        # are compared with; no engine runs it (the slab engine refuses)
        from . import lfm2

        history = lfm2.history_whole(cfg.conv_taps - 1)
    x, new_cache, _state, routing = run_stack(
        params, cfg, x, cos, sin, mixer, tuple(cache), moe_dispatch,
        history=history, mamba=mamba)
    return lm_logits(params, cfg, x, logits_at), new_cache, *routing


def forward_prefix_pages(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,        # [Bp, T] SUFFIX tokens (padded)
    prefix_table: jnp.ndarray,  # [Bp, PP] int32 prefix-pool page ids
    prefix_lens: jnp.ndarray,   # [Bp] int32 reused prefix length (tokens)
    pool_k: jnp.ndarray,        # [L, P, ps, Hkv, D] prefix page pool
    pool_v: jnp.ndarray,
    logits_at: Optional[jnp.ndarray] = None,  # [B] int32 row indices into T
    moe_dispatch: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefix-cache suffix prefill CORE: compute ONLY the suffix tokens,
    attending each row's reused prefix pages + the suffix itself
    (ops/layers.gqa_attention_prefix). Shared by the dense path (which
    composes lane images via ops/layers.compose_prefix_lane) and the
    paged path (which scatters the suffix straight into fresh pages).

    Returns (fp32 logits [Bp, T, V] — or [Bp, V] with ``logits_at``, see
    ``forward`` — plus sfx_k, sfx_v [L, Bp, T, Hkv, D]) and, where the
    configuration routes, the SUFFIX tokens' routing [Bp, T, L_routed, k]:
    the prefix was routed by whoever computed its pages.
    """
    from ..ops.paged_kv import (_dequantize_pages, is_quantized, pool_data,
                                pools_flat)

    refuse_state(cfg, "the row-bucketed prefix prefill "
                      "(forward_prefix_pages)")
    Bp, T = tokens.shape
    quant = is_quantized(pool_k)
    ps = pool_data(pool_k).shape[2]
    Pt = prefix_table.shape[1] * ps
    x = params["embed"][tokens]
    positions = prefix_lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

    # one fused gather per layer: flatten (L, P) so layer index l and the
    # page table combine into a single index array (a dynamic_slice of the
    # pool followed by a page gather may or may not fuse; this form always
    # reads only the needed pages). Quantized pools gather payload AND
    # scale rows, dequantizing to f32 right after the gather.
    pool_k_flat, pool_v_flat, L, P = pools_flat(pool_k, pool_v)

    def _gather_pages(flat, idx):
        if quant:
            return _dequantize_pages(flat.data[idx], flat.scale[idx]
                                     ).reshape(Bp, Pt, cfg.n_kv_heads,
                                               cfg.head_dim)
        return flat[idx].reshape(Bp, Pt, cfg.n_kv_heads, cfg.head_dim)

    def mixer(q, k, v, l):
        kp = _gather_pages(pool_k_flat, l * P + prefix_table)
        vp = _gather_pages(pool_v_flat, l * P + prefix_table)
        k, v = k.astype(kp.dtype), v.astype(vp.dtype)
        attn = gqa_attention_prefix(q, kp, vp, k, v, prefix_lens,
                                    window=cfg.sliding_window)
        return attn, (k, v)

    x, out = jax.lax.scan(
        decoder_layer(cfg, cos, sin, mixer, moe_dispatch), x,
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    (sfx_k, sfx_v), routing = take_routing(cfg, out)
    return lm_logits(params, cfg, x, logits_at), sfx_k, sfx_v, *routing


def forward_ragged_prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [W] int32 packed token stream (rows concat)
    tok_row: jnp.ndarray,     # [W] int32 owning wave row (>= R = padding)
    tok_pos: jnp.ndarray,     # [W] int32 absolute position within the row
    row_tables: jnp.ndarray,  # [R, maxp] int32 page-pool ids per row
    starts: jnp.ndarray,      # [R] int32 row offset in the stream
    lens: jnp.ndarray,        # [R] int32 row token count (0 = dead row)
    prefix_lens: jnp.ndarray,  # [R] int32 tokens already in the row's pages
    pool_k: jnp.ndarray,      # [L, P, ps, Hkv, D] MAIN paged pool
    pool_v: jnp.ndarray,
    state_seed: Optional[jnp.ndarray] = None,  # [L_conv, R, taps-1, D]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Packed ragged PREFILL forward (ISSUE 11): ONE no-padding token
    stream per admission wave — the wave's rows concatenated back to back,
    described by per-row ``(start, len, prefix_len)`` descriptors. Each
    token attends its own row's prefix KV straight out of the page pool
    (prefix-cache hits AND earlier chunks of a split prompt — the
    ``ops.layers.ragged_prefill_dispatch`` kernel reads pages in place,
    no ``paged_gather_kv`` densification) plus the row's suffix causally.

    The layer scan addresses the pool through its flattened [L*P] view
    with a per-layer table offset, so the kernel sees a single page axis
    (a reshape, not a copy). Returns (fp32 logits [R, V] at each row's
    LAST live token, sfx_k, sfx_v [L, W, Hkv, D] — packed, stream order,
    for ``ops.paged_kv.paged_write_ragged``) and, where the configuration
    routes, the stream's routing [W, L_routed, k], stream order.

    A configuration whose conv layers carry state takes ``state_seed``,
    each row's state as it stood before the row's first token of this
    call (zeros for a cold row, a cached page's for a prefix hit, the
    slot's own for a later chunk of a split prompt), and a row's
    convolution reads that and never its neighbour in the stream. It
    returns, after ``sfx_v``: ``row_state`` [L_conv, R, taps-1, D], the
    state after each row's last token of this call; ``page_state``
    [L_conv, W // ps + R, taps-1, D] with ``page_ids`` [W // ps + R], the
    state after every token that ends a page and that page's id (0, the
    trash page, for the unused entries).

    A configuration with Mamba-2 layers (models/nemotron_h.py) takes as
    ``state_seed`` ``{"conv": the rows' conv rows [L_m, R, taps - 1, conv
    dim], "ssm": (src [R], slots [R], dst [R], the slots' pool, the
    snapshots' pool)}`` (``nemotron_h.ssm_segments`` has what each means:
    the large part of the state is read from and written to its pools in
    place, never stacked over a wave's rows), and returns after ``sfx_v``:
    the conv rows after each row's last token of this call and after its
    LAST page end of this call (what a snapshot holds: one a row, not one
    a page), both over R rows; ``end_lens`` [R], how many of the row's
    tokens lie up to that page end (0: it crossed none); and the two
    pools as written.
    """
    from ..ops.layers import ragged_prefill_dispatch
    from ..ops.paged_kv import pool_data, pool_dtype, pools_flat

    x = params["embed"][tokens][None]                    # [1, W, D]
    cos, sin = rope_terms(cfg, tok_pos[None])
    pool_k_flat, pool_v_flat, L, P = pools_flat(pool_k, pool_v)
    kdt = pool_dtype(pool_k)
    vdt = None if cfg.latent else pool_dtype(pool_v)
    width = pool_data(pool_k).shape[-1]
    tables = row_tables.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    plens = prefix_lens.astype(jnp.int32)

    def mixer(q, k, v, l):
        # suffix K/V cast to the pool's LOGICAL dtype BEFORE attention
        # (matching forward_prefix_pages): what this wave attends is
        # bit-identical to what later waves/decodes read back from the
        # pages — under int8 pools the cast targets the dequant dtype
        # and the residual quantization error is bounded by the parity
        # suite instead (tests/test_kv_quant.py)
        qs, ks, vs = _at_pool_width(q[0], k[0], v[0], width)
        ks = ks.astype(kdt)
        vs = vs.astype(vdt)
        attn = ragged_prefill_dispatch(
            qs, ks, vs, pool_k_flat, pool_v_flat, tables + l * P,
            starts, lens, plens, tok_row, window=cfg.sliding_window)
        return attn[..., :cfg.head_dim], (ks, vs)

    if cfg.latent:
        from ..ops.layers import latent_prefill_dispatch
        from .deepseek import at_width

        def mixer(q, row, l):
            # the wave's rows in the pool's dtype BEFORE attention, as
            # above: what this wave attends is what later waves and
            # decodes read back. ``sfx_v`` is None: a row is both
            rs = at_width(row[0], width).astype(kdt)
            o_lat = latent_prefill_dispatch(
                at_width(q[0], width), rs, pool_k_flat, tables + l * P,
                starts, lens, plens, tok_row)
            return o_lat[None], (rs, None)

    history = page_ids = mamba = None
    R = starts.shape[0]
    live = (tok_row >= 0) & (tok_row < R)
    if cfg.n_ssm_layers:
        from . import nemotron_h

        mamba, page_ids = nemotron_h.stream_mixers(
            cfg, state_seed, tok_row, tok_pos, starts, lens, live,
            pool_data(pool_k).shape[2])
    elif cfg.stateful:
        from . import lfm2

        ps = pool_data(pool_k).shape[2]
        W = tokens.shape[0]
        # the tokens that end a page, in stream order, W for the unused
        ends = live & ((tok_pos + 1) % ps == 0)
        (page_end,) = jnp.nonzero(ends, size=W // ps + R, fill_value=W)
        at = jnp.minimum(page_end, W - 1)
        page_ids = jnp.where(
            page_end < W,
            tables[jnp.clip(tok_row[at], 0, R - 1),
                   jnp.clip(tok_pos[at] // ps, 0, tables.shape[1] - 1)], 0)
        history = lfm2.history_stream(cfg.conv_taps - 1, tok_row, starts,
                                      lens, at)
    x, (sfx_k, sfx_v), state, routing = run_stack(
        params, cfg, x, cos, sin, mixer, jnp.arange(L, dtype=jnp.int32),
        history=history, conv_ops=state_seed, live=live[None], mamba=mamba)
    pools = ()
    if cfg.n_ssm_layers:
        (state, _none), pools = state
    last_w = starts + jnp.maximum(lens - 1, 0)           # dead rows -> 0
    return (lm_logits(params, cfg, x, stream_at=last_w), sfx_k, sfx_v,
            *(() if state is None else (*state, page_ids)), *pools,
            *(r[0] for r in routing))


def forward_prefix_lane(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,        # [Bp, T] SUFFIX tokens (padded)
    prefix_table: jnp.ndarray,  # [Bp, PP] int32 prefix-pool page ids
    prefix_lens: jnp.ndarray,   # [Bp] int32 reused prefix length (tokens)
    pool_k: jnp.ndarray,        # [L, P, ps, Hkv, D] prefix page pool
    pool_v: jnp.ndarray,
    lane_pages: int,            # static: output lane length in pages
    logits_at: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense-cache prefix prefill: ``forward_prefix_pages`` + per-row lane
    composition (ops/layers.compose_prefix_lane) ready for one uniform
    slot-cache insert. Returns (fp32 logits, lane_k, lane_v) and the
    suffix tokens' routing as ``forward_prefix_pages`` does.
    """
    from ..ops.layers import compose_prefix_lane

    logits, sfx_k, sfx_v, *routing = forward_prefix_pages(
        params, cfg, tokens, prefix_table, prefix_lens, pool_k, pool_v,
        logits_at=logits_at)
    lane_k, lane_v = compose_prefix_lane(
        pool_k, pool_v, prefix_table, prefix_lens, sfx_k, sfx_v, lane_pages)
    return logits, lane_k, lane_v, *routing


def init_prefix_pool(
    cfg: ModelConfig, num_pages: int, page_size: int,
    dtype: jnp.dtype = jnp.bfloat16,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zeroed prefix-cache page pool (page 0 = trash)."""
    shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_chunk_kv(
    cfg: ModelConfig, batch: int, chunk: int, dtype: jnp.dtype = jnp.bfloat16
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-chunk K/V accumulator for the two-segment decode (zeros; shape
    [L, B, Kc, Hkv, D] over the layers that attend) and, for a
    configuration whose conv layers carry state, a third buffer for the
    chunk's gated conv inputs ``z`` ([L_conv, B, Kc, D]). For one with
    Mamba-2 layers the third entry is ``nemotron_h``'s tuple of the
    chunk's buffers (``init_chunk_state``), and that module owns them:
    ``forward_paged_chunked`` hands the tuple to ``chunk_mixers`` as the
    stack's carry and takes it back as it comes out, ``merge_paged_chunk``
    hands it to ``merge_state``; nothing here slices or stacks it."""
    if cfg.latent:
        # a latent configuration's chunk holds rows, as its pool does
        from . import deepseek

        return (jnp.zeros((cfg.n_layers, batch, chunk,
                           deepseek.row_width(cfg)), dtype), None)
    # the paged decode's buffers are as wide as its pool (kv_head_dim);
    # the slab engine's are the configuration's head size, and no
    # stateful configuration reaches it
    shape = (cfg.n_attn_layers, batch, chunk, cfg.n_kv_heads,
             kv_head_dim(cfg))
    kv = jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    if cfg.n_ssm_layers:
        from . import nemotron_h

        kv += (nemotron_h.init_chunk_state(cfg, batch, chunk, dtype),)
    elif cfg.stateful:
        kv += (jnp.zeros((cfg.n_conv_layers, batch, chunk, cfg.dim), dtype),)
    return kv


def _write_chunk_step(hk, hv, k, v, step):
    """This decode step's K/V into the chunk buffer at index ``step``."""
    hk = jax.lax.dynamic_update_slice(hk, k.astype(hk.dtype), (0, step, 0, 0))
    hv = jax.lax.dynamic_update_slice(hv, v.astype(hv.dtype), (0, step, 0, 0))
    return hk, hv


def forward_chunked(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,       # [B, 1] int32 — one decode step
    positions: jnp.ndarray,    # [B, 1] int32 absolute positions
    cache: KVCache,            # FROZEN during the chunk
    chunk_kv: Tuple[jnp.ndarray, jnp.ndarray],  # [L, B, Kc, Hkv, D] each
    step: jnp.ndarray,         # scalar int32 — index within the chunk
    moe_dispatch: Optional[str] = None,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Decode step against a frozen cache + in-chunk K/V buffer.

    The engine's chunked decode loop (Engine._decode) calls this K times
    per chunk, then folds chunk_kv into the big cache with
    ``merge_chunk_kv`` — one full-cache write per CHUNK, not per step
    (ops/layers.gqa_attention_chunked has the profile numbers). This
    step's K/V is written at chunk index ``step`` via dynamic_update_slice
    (uniform index across rows, no scatter).
    """
    refuse_state(cfg, "the dense slab engine's chunked decode "
                      "(forward_chunked)")
    x = params["embed"][tokens]  # [B, 1, D]
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

    def mixer(q, k, v, scanned):
        ck, cv, hk, hv = scanned
        hk, hv = _write_chunk_step(hk, hv, k, v, step)
        attn = gqa_attention_chunked(q, ck, cv, hk, hv, positions, step,
                                     window=cfg.sliding_window)
        return attn, (hk, hv)

    x, out = jax.lax.scan(
        decoder_layer(cfg, cos, sin, mixer, moe_dispatch), x,
        (params["layers"], (*cache, *chunk_kv)))
    new_chunk, routing = take_routing(cfg, out)
    return lm_logits(params, cfg, x), new_chunk, *routing


def merge_chunk(
    cache: KVCache,
    chunk_kv: Tuple[jnp.ndarray, jnp.ndarray],
    start_positions: jnp.ndarray,  # [B]
) -> KVCache:
    """Fold a finished chunk's K/V into the slot cache (ops/layers)."""
    ck, cv = cache
    hk, hv = chunk_kv
    return merge_chunk_kv(ck, cv, hk, hv, start_positions)


def merge_chunk_scatter(
    cache: KVCache,
    chunk_kv: Tuple[jnp.ndarray, jnp.ndarray],
    start_positions: jnp.ndarray,  # [B]
) -> KVCache:
    """Scatter-form merge (ops/layers.merge_chunk_kv_scatter); selected
    by SWARMDB_MERGE=scatter — see that function for the trade."""
    from ..ops.layers import merge_chunk_kv_scatter

    ck, cv = cache
    hk, hv = chunk_kv
    return merge_chunk_kv_scatter(ck, cv, hk, hv, start_positions)


def _paged_rope_terms(cfg: ModelConfig, cache, positions):
    """RoPE terms for a decode step over the paged pool. Rolling-KV
    conversations carry a per-row RoPE offset ``pos0``: kept pages' K
    were rope'd at their original absolute positions, so queries must be
    too (RoPE scores depend only on position differences). ``positions``
    itself stays LOGICAL (page writes + masks)."""
    pos0 = cache.get("pos0")
    rope_pos = positions if pos0 is None else positions + pos0[:, None]
    return rope_terms(cfg, rope_pos)


def forward_paged_chunked(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,       # [B, 1]
    positions: jnp.ndarray,    # [B, 1]
    cache,                     # {"k","v","page_table"} — FROZEN this chunk
    chunk_kv: Tuple[jnp.ndarray, jnp.ndarray],  # [L, B, Kc, Hkv, D] each
    step: jnp.ndarray,         # scalar int32
    moe_dispatch: Optional[str] = None,
):
    """Two-segment chunked decode over the PAGED pool: the pool stays
    frozen for the chunk's K steps (one bulk page write per chunk via
    ``merge_paged_chunk``), this step's K/V lands in the chunk buffer,
    and attention spans live pages + chunk buffer under one softmax
    (ops/layers.paged_attention_dispatch_chunked). Like
    ``forward_ragged_prefill`` the layer scan reads the pool in place,
    through its flat view and a per-layer table offset."""
    from ..ops.layers import paged_attention_dispatch_chunked
    from ..ops.paged_kv import live_row_list, pools_flat

    x = params["embed"][tokens]
    table = cache["page_table"]
    pool_k_flat, pool_v_flat, L, P = pools_flat(cache["k"], cache["v"])
    cos, sin = _paged_rope_terms(cfg, cache, positions)
    # once a step, from the table as it is: inside the scan a layer's
    # table is offset and its trash page is no longer page 0
    live_rows = live_row_list(table)

    def mixer(q, k, v, scanned):
        l, hk, hv = scanned
        q, k, v = _at_pool_width(q, k, v, hk.shape[-1])
        hk, hv = _write_chunk_step(hk, hv, k, v, step)
        attn = paged_attention_dispatch_chunked(
            q, pool_k_flat, pool_v_flat, table + l * P, hk, hv, positions,
            step, window=cfg.sliding_window, live_rows=live_rows)
        return attn[..., :cfg.head_dim], (hk, hv)

    if cfg.latent:
        from ..ops.layers import latent_decode_dispatch
        from .deepseek import at_width

        def mixer(q, row, scanned):
            l, hk, _none = scanned
            width = hk.shape[-1]
            hk = jax.lax.dynamic_update_slice(
                hk, at_width(row, width).astype(hk.dtype), (0, step, 0))
            o_lat = latent_decode_dispatch(
                at_width(q[:, 0], width), pool_k_flat, table + l * P, hk,
                (positions[:, 0] - step).astype(jnp.int32), step)
            return o_lat[:, None], (hk, None)

    history = conv_ops = live = mamba = None
    if cfg.latent:
        # a lane whose table row is empty holds no sequence: it reads no
        # expert
        live = table[:, :1] != 0
    if cfg.n_ssm_layers:
        # as below, for the Mamba-2 layers: the step reads the LIVE
        # slots' frozen state once and carries the chunk's own buffers
        # (chunk_kv[2]) whole through the layers
        from . import nemotron_h

        mamba = nemotron_h.chunk_mixers(
            cfg, cache["state"], chunk_kv[2], step, live_rows)
        live = table[:, :1] != 0
    elif cfg.stateful:
        # the slots' state stays frozen for the chunk like the pool; a
        # step reads it and the chunk's own z so far (chunk_kv[2]), and
        # ``merge_paged_chunk`` folds the chunk in. A lane whose table
        # row is empty holds no sequence: it reads no expert
        from . import lfm2

        history = lfm2.history_chunk(cfg.conv_taps - 1, step)
        conv_ops = (cache["state"], chunk_kv[2])
        live = table[:, :1] != 0
    x, new_chunk, hz, routing = run_stack(
        params, cfg, x, cos, sin, mixer,
        (jnp.arange(L, dtype=jnp.int32), *chunk_kv[:2]), moe_dispatch,
        history=history, conv_ops=conv_ops, live=live, mamba=mamba)
    if cfg.n_ssm_layers:
        _kept, hz = hz
    if hz is not None:
        new_chunk = (*new_chunk, hz)
    return lm_logits(params, cfg, x), new_chunk, *routing


def merge_paged_chunk(cache, chunk_kv, start_positions: jnp.ndarray):
    """Fold a finished chunk's K/V into the page pool — one bulk write
    (ops/paged_kv.paged_write_chunk) — and, where the slots carry state,
    the chunk into it (a Mamba-2 stack's for the slots the table holds a
    sequence in, and no others: ``nemotron_h.merge_state``)."""
    from ..ops.paged_kv import live_row_list, paged_write_chunk

    hk, hv, *hz = chunk_kv
    new_k, new_v = paged_write_chunk(
        cache["k"], cache["v"], hk, hv, start_positions,
        cache["page_table"],
    )
    out = {**cache, "k": new_k, "v": new_v}
    if hz and isinstance(cache["state"], dict):
        from . import nemotron_h

        out["state"] = nemotron_h.merge_state(
            cache["state"], hz[0], *live_row_list(cache["page_table"]))
    elif hz:
        from . import lfm2

        out["state"] = lfm2.merge_state(cache["state"], hz[0])
    return out



# ----------------------------------------------------- pipeline parallelism


def param_specs_pp(cfg: ModelConfig, pipe_axis: str = "pipe") -> Params:
    """PartitionSpecs for pipeline parallelism: the stacked [L, ...] layer
    arrays shard their LAYER axis over ``pipe_axis`` (each stage holds
    L/pipe layers); embedding/norms/head are replicated. This is the
    storage layout ``forward_pipelined`` consumes — the stacked-layer
    design makes PP a leading-axis sharding, not a model rewrite."""
    p = pipe_axis
    specs: Params = {
        "embed": P(None, None),
        "layers": jax.tree.map(lambda _: P(p), {
            "attn_norm": 0, "wq": 0, "wk": 0, "wv": 0, "wo": 0,
            "mlp_norm": 0, "w_gate": 0, "w_up": 0, "w_down": 0,
        }),
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, None)
    return specs


def forward_pipelined(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [B, T]
    positions: jnp.ndarray,   # [B, T]
    mesh,                     # jax.sharding.Mesh with a 'pipe' axis
    *,
    microbatches: Optional[int] = None,
    pipe_axis: str = "pipe",
) -> Tuple[jnp.ndarray, KVCache]:
    """Pipeline-parallel prefill: GPipe-style microbatch rotation.

    Layers shard over ``pipe_axis`` (SURVEY §2.4 PP row); the batch splits
    into M microbatches that flow through the stage ring via
    ``lax.ppermute`` — at steady state every stage computes a different
    microbatch, with the classic (P-1)/(M+P-1) bubble at the edges.
    Stage 0 embeds, the last stage applies the head; invalid edge steps
    compute masked garbage that is never stored. All collectives are the
    forward neighbor ppermute plus one psum to replicate the logits.

    Returns fp32 logits [B, T, V] and prompt K/V [L, B, T, Hkv, hd]
    (layer axis pipe-sharded on device). Requires n_layers % pipe == 0
    and B % microbatches == 0. This is the PREFILL path; decode keeps
    TP/DP (single-token PP would serialize on inter-stage latency).
    """
    shard_map = functools.partial(jax.shard_map, check_vma=False)

    refuse_state(cfg, "pipeline-parallel prefill (forward_pipelined)")
    if cfg.is_moe:
        raise ValueError(f"{cfg.name!r} is MoE; PP is dense-only for now")
    n_stages = mesh.shape[pipe_axis]
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by pipe={n_stages}")
    B, T = tokens.shape
    M = microbatches or min(B, max(2, n_stages))
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    Bm = B // M

    def stage_fwd(params, tokens, positions):
        stage = jax.lax.axis_index(pipe_axis)
        n_p = jax.lax.psum(1, pipe_axis)
        lp = params["layers"]  # local [L/P, ...] slices
        L_local = lp["attn_norm"].shape[0]
        mb_tok = tokens.reshape(M, Bm, T)
        mb_pos = positions.reshape(M, Bm, T)

        def run_layers(x, pos):
            cos, sin = rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)

            def mixer(q, k, v, _):
                attn = gqa_attention(q, k, v, pos, window=cfg.sliding_window)
                return attn, (k, v)

            return jax.lax.scan(decoder_layer(cfg, cos, sin, mixer), x,
                                (lp, None))

        state = jnp.zeros((Bm, T, cfg.dim), params["embed"].dtype)
        ks_all = jnp.zeros((L_local, M, Bm, T, cfg.n_kv_heads, cfg.head_dim),
                           params["embed"].dtype)
        vs_all = jnp.zeros_like(ks_all)
        # accumulate the LAST stage's post-norm activations, not logits: a
        # [M, Bm, T, dim] carry + one dim-sized psum beats a fp32
        # [M, Bm, T, V] carry + V-sized psum by V/dim (16-64x), and the
        # head matmul then runs once after the scan instead of per step
        act_acc = jnp.zeros((M, Bm, T, cfg.dim), params["embed"].dtype)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def step(carry, t_idx):
            state, ks_all, vs_all, act_acc = carry
            m_in = t_idx - stage                      # microbatch here now
            m_cl = jnp.clip(m_in, 0, M - 1)
            valid = (m_in >= 0) & (m_in < M)
            tok_m = jax.lax.dynamic_index_in_dim(mb_tok, m_cl, 0, False)
            pos_m = jax.lax.dynamic_index_in_dim(mb_pos, m_cl, 0, False)
            inject = params["embed"][tok_m]           # stage-0 entry point
            x = jnp.where(stage == 0, inject, state)
            x, (ks, vs) = run_layers(x, pos_m)

            sel = valid
            old_k = jax.lax.dynamic_index_in_dim(ks_all, m_cl, 1, False)
            old_v = jax.lax.dynamic_index_in_dim(vs_all, m_cl, 1, False)
            ks_all = jax.lax.dynamic_update_index_in_dim(
                ks_all, jnp.where(sel, ks, old_k), m_cl, 1)
            vs_all = jax.lax.dynamic_update_index_in_dim(
                vs_all, jnp.where(sel, vs, old_v), m_cl, 1)

            xn = rms_norm(x, params["final_norm"], cfg.norm_eps)
            old_a = jax.lax.dynamic_index_in_dim(act_acc, m_cl, 0, False)
            keep = sel & (stage == n_p - 1)
            act_acc = jax.lax.dynamic_update_index_in_dim(
                act_acc, jnp.where(keep, xn, old_a), m_cl, 0)

            state = jax.lax.ppermute(x, pipe_axis, perm)
            return (state, ks_all, vs_all, act_acc), None

        (state, ks_all, vs_all, act_acc), _ = jax.lax.scan(
            step, (state, ks_all, vs_all, act_acc),
            jnp.arange(M + n_stages - 1, dtype=jnp.int32),
        )
        # activations live only on the last stage (zeros elsewhere): one
        # psum replicates them, then every stage applies the (replicated)
        # head identically; K/V stay pipe-sharded on their layer axis
        act = jax.lax.psum(act_acc, pipe_axis)
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        logits = jnp.einsum("mbtd,dv->mbtv", act, head,
                            preferred_element_type=jnp.float32)
        ks_out = ks_all.reshape(L_local, B, T, cfg.n_kv_heads, cfg.head_dim)
        vs_out = vs_all.reshape(L_local, B, T, cfg.n_kv_heads, cfg.head_dim)
        return logits.reshape(B, T, cfg.vocab_size), ks_out, vs_out

    from jax.sharding import PartitionSpec as P_

    sharded = shard_map(
        stage_fwd,
        mesh=mesh,
        in_specs=(param_specs_pp(cfg, pipe_axis), P_(), P_()),
        out_specs=(P_(), P_(pipe_axis), P_(pipe_axis)),
    )
    logits, ks, vs = sharded(params, tokens, positions)
    return logits, (ks, vs)


# ------------------------------------------- sequence-parallel long prefill


def forward_seq_parallel(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [B, T] with T = seq_axis_size * T_local
    positions: jnp.ndarray,   # [B, T] absolute positions
    mesh,                     # jax.sharding.Mesh
    seq_axis: str = "data",
) -> Tuple[jnp.ndarray, KVCache]:
    """Long-prompt prefill with the SEQUENCE sharded over a mesh axis.

    Context parallelism (SURVEY §5.7 design hook, made real): each device
    holds T/axis_size tokens; attention is `ops.ring_attention` — K/V
    chunks rotate over ICI with ppermute while softmax accumulates online,
    so peak memory per device is O(T/axis) and no [T, T] scores exist.
    During prefill of one long prompt the batch axis is idle, so the
    ``data`` axis doubles as the ring (no dedicated mesh axis needed).

    Returns fp32 logits [B, T, V] and the prompt KV [L, B, T, Hkv, D],
    both seq-sharded on device; callers either read the last-token logits
    or scatter the KV into a slot cache for decode.
    """
    from jax.sharding import PartitionSpec as P

    from ..ops.ring_attention import ring_attention
    shard_map = functools.partial(jax.shard_map, check_vma=False)
    refuse_state(cfg, "sequence-parallel prefill (forward_seq_parallel)")

    def local_fwd(params, tokens, positions):
        x = params["embed"][tokens]
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

        def mixer(q, k, v, _):
            attn = ring_attention(q, k, v, positions, positions, seq_axis,
                                  window=cfg.sliding_window)
            return attn, (k, v)

        x, out = jax.lax.scan(decoder_layer(cfg, cos, sin, mixer), x,
                              (params["layers"], None))
        (ks, vs), _routing = take_routing(cfg, out)
        return lm_logits(params, cfg, x), ks, vs

    sharded = shard_map(
        local_fwd,
        mesh=mesh,
        in_specs=(P(), P(None, seq_axis), P(None, seq_axis)),
        out_specs=(
            P(None, seq_axis, None),
            P(None, None, seq_axis, None, None),
            P(None, None, seq_axis, None, None),
        ),
    )
    logits, ks, vs = sharded(params, tokens, positions)
    return logits, (ks, vs)

