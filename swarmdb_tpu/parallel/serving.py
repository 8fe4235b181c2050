"""Sharded serving: place a model family onto a mesh and expose the same
``(forward_fn, init_cache_fn, params)`` contract the continuous-batching
Engine consumes — multi-chip serving drops into the single-chip engine
unchanged.

Parallelism mapping (SURVEY §2.4 table):
- DP: batch slots (= broker partitions) shard over ``data``.
- TP: Megatron column/row sharding from ``models/*.param_specs`` over
  ``model``; GSPMD inserts one all-reduce per attention/MLP block.
- EP: Mixtral expert weights shard over ``expert``; token dispatch/combine
  einsums lower to all-to-alls.

Params are initialized *directly sharded* (``jax.jit`` with
``out_shardings``) so no host ever materializes the full 70B weight tree —
the same path an orbax sharded-checkpoint restore takes.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..models import llama, mixtral
from ..models.configs import ModelConfig, get_config
from .mesh import make_mesh, tree_shardings

# Activations/tokens shard batch over data; cache shards batch over data and
# KV heads over model.
TOKEN_SPEC = P("data", None)
CACHE_SPEC = P(None, "data", None, "model", None)

# DP-sharded PAGED serving (VERDICT r4 #2): the page pool shards its PAGE
# axis over ``data`` and the page table its SLOT axis, with slot→shard
# affinity enforced host-side by ops.paged_kv.ShardedPageAllocator — every
# page a slot references lives in that slot's shard of the pool, so the
# shard_map'd decode below is collective-free (dp independent single-chip
# decode programs; linear scaling over ICI-connected chips).
PAGED_POOL_SPEC = P(None, "data", None, None, None)   # [L, P, ps, Hkv, D]
PAGED_TABLE_SPEC = P("data", None)                    # [B, maxp]
PAGED_CACHE_SPECS = {
    "k": PAGED_POOL_SPEC,
    "v": PAGED_POOL_SPEC,
    "page_table": PAGED_TABLE_SPEC,
    "pos0": P("data"),
}
CHUNK_KV_SPEC = P(None, "data", None, None, None)     # [L, B, Kc, Hkv, D]


@dataclass
class ShardedModel:
    """A model family placed on a mesh, Engine-ready."""

    cfg: ModelConfig
    mesh: Mesh
    params: Any
    forward_fn: Callable  # (params, tokens, positions, cache) -> (logits, cache)
    init_cache_fn: Callable  # (batch, max_seq) -> cache pytree
    param_shardings: Any
    # two-segment chunked decode triple (chunk_forward, init_chunk, merge)
    # with shardings pinned — Engine(chunked_fns=...); see ops/layers.py
    chunked_fns: Any = None

    @property
    def data_size(self) -> int:
        return self.mesh.shape["data"]


def _family(cfg: ModelConfig):
    """Whose parameters: the module that has the configuration's
    ``init_params`` and ``param_specs``. The decoder itself is one
    (models/llama.py) and is called by that name below."""
    return mixtral if cfg.is_moe else llama


def param_shardings_for(cfg: ModelConfig, mesh: Mesh) -> Any:
    return tree_shardings(mesh, _family(cfg).param_specs(cfg))


def build_sharded_model(
    model_name_or_cfg: Any,
    mesh: Optional[Mesh] = None,
    *,
    seed: int = 0,
    dtype: jnp.dtype = jnp.bfloat16,
) -> ShardedModel:
    """Init params sharded over the mesh and return Engine-compatible fns.

    ``forward_fn`` pins activation and cache shardings with
    ``with_sharding_constraint`` so the Engine's own ``jax.jit`` wrapper
    (engine.py `_decode`/`_prefill`) compiles to the intended SPMD program
    without knowing about the mesh.
    """
    cfg = (
        model_name_or_cfg
        if isinstance(model_name_or_cfg, ModelConfig)
        else get_config(model_name_or_cfg)
    )
    mesh = mesh or make_mesh()
    llama.refuse_state(cfg, "the GSPMD-sharded model (build_sharded_model)")
    fam = _family(cfg)
    shardings = param_shardings_for(cfg, mesh)

    init = jax.jit(
        partial(fam.init_params, cfg, dtype=dtype), out_shardings=shardings
    )
    params = init(jax.random.PRNGKey(seed))

    cache_sharding = NamedSharding(mesh, CACHE_SPEC)
    token_sharding = NamedSharding(mesh, TOKEN_SPEC)

    # EP meshes need the einsum MoE dispatch: only the one-hot
    # dispatch/combine einsums lower to all-to-alls over the sharded
    # expert axis (the scatter fast path would leave GSPMD guessing at
    # gather/scatter collectives). Everything else keeps the module
    # default (scatter — models/mixtral.py module docstring).
    moe_kw = ({"moe_dispatch": "einsum"}
              if cfg.is_moe and mesh.shape.get("expert", 1) > 1 else {})

    def forward_fn(p, tokens, positions, cache):
        from ..ops.layers import pallas_disabled

        # Prefill runs [1, T] (batch < data axis): leave the compiler free
        # there; constrain only when the batch divides the data axis.
        constrain = tokens.shape[0] % mesh.shape["data"] == 0
        if constrain:
            tokens = jax.lax.with_sharding_constraint(tokens, token_sharding)
            positions = jax.lax.with_sharding_constraint(positions, token_sharding)
            cache = jax.tree.map(
                lambda c: jax.lax.with_sharding_constraint(c, cache_sharding), cache
            )
        with pallas_disabled():
            logits, cache, *routing = llama.forward(
                p, cfg, tokens, positions, cache, **moe_kw)
        if constrain:
            cache = jax.tree.map(
                lambda c: jax.lax.with_sharding_constraint(c, cache_sharding), cache
            )
        return logits, cache, *routing

    def init_cache_fn(batch: int, max_seq: int):
        shape_fn = partial(llama.init_kv_cache, cfg, batch, max_seq)
        if batch % mesh.shape["data"] == 0:
            out_sh = jax.tree.map(lambda _: cache_sharding, jax.eval_shape(shape_fn))
            return jax.jit(shape_fn, out_shardings=out_sh)()
        return shape_fn()

    # -- chunked decode (Engine's two-segment path), shardings pinned -----
    # the chunk buffer [L, B, Kc, Hkv, D] shards exactly like the cache
    def _constrain_kv(tree):
        return jax.tree.map(
            lambda c: jax.lax.with_sharding_constraint(c, cache_sharding),
            tree,
        )

    def chunked_forward_fn(p, tokens, positions, cache, chunk_kv, step):
        from ..ops.layers import pallas_disabled

        cache = _constrain_kv(cache)
        chunk_kv = _constrain_kv(chunk_kv)
        with pallas_disabled():
            logits, chunk_kv, *routing = llama.forward_chunked(
                p, cfg, tokens, positions, cache, chunk_kv, step, **moe_kw)
        return logits, _constrain_kv(chunk_kv), *routing

    def init_chunk_fn(batch: int, chunk: int):
        return _constrain_kv(llama.init_chunk_kv(cfg, batch, chunk))

    def merge_fn(cache, chunk_kv, start_positions):
        return _constrain_kv(
            llama.merge_chunk(cache, chunk_kv, start_positions))

    return ShardedModel(
        cfg=cfg,
        mesh=mesh,
        params=params,
        forward_fn=forward_fn,
        init_cache_fn=init_cache_fn,
        param_shardings=shardings,
        chunked_fns=(chunked_forward_fn, init_chunk_fn, merge_fn),
    )


def build_sharded_paged(
    sm: ShardedModel,
    *,
    max_batch: int,
    max_seq: int,
    page_size: int = 16,
    kv_pool_tokens: Optional[int] = None,
    prefix: bool = True,
):
    """DP-sharded paged-KV wiring for a :class:`ShardedModel`.

    Returns ``(paged_spec, prefix_fns)`` ready for ``Engine(paged=...,
    prefix_fns=...)``; the spec carries the pool's chunk triple. Design
    (VERDICT r4 #2 — the fast path must be constructible multi-chip):

    - The pool's PAGE axis and the table's SLOT axis shard over ``data``;
      ``ShardedPageAllocator`` stripes the global id space per shard and
      binds slot ``s`` to shard ``s // (B/dp)``, so every table entry is
      shard-local by construction.
    - The decode chunk runs under ``shard_map``: each device localizes
      its table block (``clip(table - shard*Pl, 0, Pl-1)`` — own ids map
      to [1, Pl), zeroed/trash entries to the shard's local trash 0) and
      gathers/scatters ONLY its own sub-pool. No collectives in the
      decode hot loop: DP decode is dp independent single-chip programs.
    - PLAIN prefill runs shard-packed under shard_map (``prefill_packed``
      below): the engine lays each admission wave out as per-shard row
      blocks, so the forward, sampling, page scatter and fed-token update
      are all block-local — the compiled program carries ZERO collectives
      (asserted by the multichip dry run), where the generic GSPMD form
      emitted pool-sized all-gathers per wave. PREFIX waves keep GSPMD
      with GLOBAL page ids (admission-time, shortened by the hits
      themselves, amortized); packing them too is the remaining headroom
      on this path. Resume waves don't arise here at all — rolling is
      disabled on sharded pools (below).
    - Requires a pure-DP mesh for the pool (``model`` axis size 1): TP
      inside shard_map would need manual collectives the model fns don't
      emit. TP+paged is a deliberate non-goal this round — the v5e-8
      500-msgs/sec target config is DP over 8 chips of an 8B-class model.

    Rolling-KV resume is not wired for sharded pools yet (a resumed
    conversation's pages pin it to one shard; the serving layer disables
    rolling when it sees a sharded allocator).
    """
    # check_vma off: the bodies are intentionally per-shard — nothing is
    # replicated
    shard_map = partial(jax.shard_map, check_vma=False)

    from ..ops.layers import pallas_disabled
    from ..ops.paged_kv import (init_paged_kv_cache, kv_quantized,
                                make_sharded_page_allocator,
                                pages_per_slot)

    cfg, mesh = sm.cfg, sm.mesh
    if kv_quantized():
        # PAGED_CACHE_SPECS are rank-5 payload PartitionSpecs; the int8
        # QuantPool carries rank-3 scale planes they cannot shard. Fail
        # loudly here rather than deep inside jit with a spec/rank error.
        raise NotImplementedError(
            "SWARMDB_KV_DTYPE=int8 is single-chip only: the sharded paged "
            "pool's PartitionSpecs do not cover QuantPool scale planes. "
            "Unset SWARMDB_KV_DTYPE (or use f32/bf16) for sharded serving."
        )
    if any(mesh.shape.get(ax, 1) > 1 for ax in ("model", "expert", "pipe")):
        raise ValueError(
            "sharded paged serving requires a pure-DP mesh (model/expert/"
            "pipe axes of size 1); TP/EP shard KV heads across devices, "
            "which the slot-affine page pool does not support"
        )
    dp = mesh.shape["data"]
    if max_batch % dp:
        raise ValueError(f"max_batch {max_batch} must divide the data "
                         f"axis {dp} (slot→shard affinity)")
    if max_seq % page_size:
        raise ValueError("max_seq must be a page-size multiple")
    maxp = pages_per_slot(max_seq, page_size)
    if kv_pool_tokens is None:
        kv_pool_tokens = max_batch * maxp * page_size
        if prefix:
            # cached pages compete with slot footprints (same rationale
            # as ServingService.from_model_name)
            import os as _os

            kv_pool_tokens += int(_os.environ.get(
                "SWARMDB_PREFIX_TOKENS", max_batch * max_seq // 2))
    # per-shard pool block: local trash page + this shard's share
    per_shard = 1 + -(-kv_pool_tokens // (page_size * dp))
    num_pages = per_shard * dp
    allocator = make_sharded_page_allocator(per_shard, dp, page_size,
                                            max_seq, max_batch)

    params_specs = jax.tree.map(lambda _: P(), sm.params)
    # a configuration that routes: each body's forward returns its routing
    # last ([rows, T, L_routed, k], rows on the data axis like the tokens)
    routing_specs = (P("data", None, None, None),) if cfg.is_moe else ()

    def _localize(table):
        base = jax.lax.axis_index("data").astype(jnp.int32) * per_shard
        return jnp.clip(table - base, 0, per_shard - 1)

    def _chunk_body(p, t, pos, c, chunk_kv, step):
        local = dict(c, page_table=_localize(c["page_table"]))
        with pallas_disabled():
            logits, out_ck, *routing = llama.forward_paged_chunked(
                p, cfg, t, pos, local, chunk_kv, step)
        return logits, out_ck, *routing

    chunk_forward = shard_map(
        _chunk_body, mesh=mesh,
        in_specs=(params_specs, TOKEN_SPEC, TOKEN_SPEC, PAGED_CACHE_SPECS,
                  (CHUNK_KV_SPEC, CHUNK_KV_SPEC), P()),
        out_specs=(P("data", None, None), (CHUNK_KV_SPEC, CHUNK_KV_SPEC),
                   *routing_specs),
    )

    def _merge_body(c, chunk_kv, starts):
        local = dict(c, page_table=_localize(c["page_table"]))
        out = llama.merge_paged_chunk(local, chunk_kv, starts)
        out["page_table"] = c["page_table"]
        return out

    merge = shard_map(
        _merge_body, mesh=mesh,
        in_specs=(PAGED_CACHE_SPECS, (CHUNK_KV_SPEC, CHUNK_KV_SPEC),
                  P("data")),
        out_specs=PAGED_CACHE_SPECS,
    )

    chunk_sharding = NamedSharding(mesh, CHUNK_KV_SPEC)

    def init_chunk_fn(batch: int, k: int):
        shape_fn = partial(llama.init_chunk_kv, cfg, batch, k)
        out_sh = jax.tree.map(lambda _: chunk_sharding,
                              jax.eval_shape(shape_fn))
        return jax.jit(shape_fn, out_shardings=out_sh)()

    def init_pool():
        shape_fn = partial(
            init_paged_kv_cache, cfg.n_layers, num_pages, page_size,
            cfg.n_kv_heads, cfg.head_dim, max_batch, max_seq,
        )
        out_sh = {
            k: NamedSharding(mesh, PAGED_CACHE_SPECS[k])
            for k in jax.eval_shape(shape_fn)
        }
        return jax.jit(shape_fn, out_shardings=out_sh)()

    # -- shard-packed PLAIN prefill (collective-free) ----------------------
    # The generic paged prefill writes pages with dynamic indices into the
    # pool's sharded axis, which GSPMD cannot prove shard-local — it
    # inserts pool-sized collectives per admission wave (the KNOWN COST
    # note above). But the allocator makes every write shard-local by
    # construction (slot→shard affinity), so when the engine packs a
    # wave's rows into per-shard blocks, the whole prefill — forward,
    # sampling, pool scatter, fed-token update — runs under shard_map
    # with ZERO collectives: dp independent single-chip prefills, the
    # exact structure of the decode path. Row geometry: [dp * rows_per,
    # T] with block d = shard d's rows (padding rows: length 1, local
    # trash pages, fed-scatter out of local range -> dropped).
    from ..backend.sampling import sample_tokens, token_logprob

    slots_per = max_batch // dp

    def _packed_body(p, tokens, lengths, target, scatter, k_pool, v_pool,
                     last_tokens, last_lps, keys, temp, topk, topp):
        # local shapes: tokens [R, T], target [R, chunks] GLOBAL page ids
        # (localized via _localize, like the decode body), scatter [R]
        # GLOBAL slot ids (block-local by packing; padding -> out of
        # range, dropped), k/v_pool [L, per_shard, ...], last_* [slots_per]
        #
        # PARITY CONTRACT: this is the shard-local twin of
        # backend/engine._prefill_paged_insert — same forward (llama.forward
        # with logits_at IS what the engine's _forward_last_of resolves
        # to), same sampling fold, same pad/reshape/page-scatter shapes.
        # A change to either body must land in both;
        # tests/test_parallel.py::test_sharded_paged_engine_matches_dense_
        # sharded pins greedy token parity across them.
        R, T = tokens.shape
        d = jax.lax.axis_index("data").astype(jnp.int32)
        positions = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None], (R, T))
        cacheB = llama.init_kv_cache(cfg, R, T)
        with pallas_disabled():
            logits, cacheB, *routing = llama.forward(
                p, cfg, tokens, positions, cacheB, logits_at=lengths - 1)
        last = (logits if logits.ndim == 2
                else logits[jnp.arange(R), lengths - 1])
        next_tok = sample_tokens(last, keys, lengths - 1, temp, topk, topp)
        lp = token_logprob(last, next_tok)
        ck, cv = cacheB
        ps_ = page_size
        chunks = target.shape[1]
        pad_to = chunks * ps_
        if pad_to != T:
            pad = [(0, 0), (0, 0), (0, pad_to - T), (0, 0), (0, 0)]
            ck = jnp.pad(ck, pad)
            cv = jnp.pad(cv, pad)
        L = ck.shape[0]
        tail = ck.shape[3:]
        kc = ck.reshape((L, R * chunks, ps_) + tail)
        vc = cv.reshape((L, R * chunks, ps_) + tail)
        flat = _localize(target).reshape(-1)
        k_pool = k_pool.at[:, flat].set(kc.astype(k_pool.dtype))
        v_pool = v_pool.at[:, flat].set(vc.astype(v_pool.dtype))
        local_slots = scatter - d * slots_per  # packing makes own rows
        last_tokens = last_tokens.at[local_slots].set(next_tok, mode="drop")
        last_lps = last_lps.at[local_slots].set(lp, mode="drop")
        return k_pool, v_pool, last_tokens, last_lps, *routing

    prefill_packed = shard_map(
        _packed_body, mesh=mesh,
        in_specs=(params_specs, P("data", None), P("data"),
                  P("data", None), P("data"), PAGED_POOL_SPEC,
                  PAGED_POOL_SPEC, P("data"), P("data"), P("data", None),
                  P("data"), P("data"), P("data")),
        out_specs=(PAGED_POOL_SPEC, PAGED_POOL_SPEC, P("data"), P("data"),
                   *routing_specs),
    )

    from ..backend.engine import PagedKV

    paged_spec = PagedKV(
        chunked_fns=(chunk_forward, init_chunk_fn, merge),
        init_pool=init_pool,
        page_size=page_size,
        num_pages=num_pages,
        allocator=allocator,
        prefill_packed=prefill_packed,
    )

    prefix_fns = None
    if prefix:
        # prefill path: GSPMD over GLOBAL ids (gathers from the sharded
        # pool; admission-time only, so the collectives amortize)
        def pages_fwd(p, t, tab, pl, pk, pv, logits_at=None):
            with pallas_disabled():
                return llama.forward_prefix_pages(p, cfg, t, tab, pl, pk, pv,
                                                  logits_at=logits_at)

        prefix_fns = (pages_fwd, None)

    return paged_spec, prefix_fns


def build_serving_engine(
    model_name_or_cfg: Any,
    mesh: Optional[Mesh] = None,
    *,
    max_batch: Optional[int] = None,
    max_seq: int = 1024,
    seed: int = 0,
    paged: Optional[bool] = None,
    page_size: int = 16,
    kv_pool_tokens: Optional[int] = None,
    admit_overlap: Optional[bool] = None,
    **engine_kwargs: Any,
):
    """One-call multi-chip engine: sharded model + continuous batching.

    ``max_batch`` defaults to 8 slots per data shard so every decode step
    is a full data-parallel batch over ICI (SURVEY §3.4). ``paged=True``
    (or SWARMDB_PAGED=1) builds the paged fast path. On a pure-DP mesh
    with more than one data shard, the DEFAULT paged build is now the
    per-shard admission-lane group (``parallel/lanes.ShardLaneGroup``:
    one single-device engine per shard, admission overlapped with the
    other shards' decode — the ISSUE 8 fix for the dp8 admission
    serialization); the second return value is then a
    :class:`~swarmdb_tpu.parallel.lanes.LaneGroupInfo` instead of a
    ShardedModel. ``admit_overlap=False`` (or SWARMDB_ADMIT_OVERLAP=0)
    restores the single-program GSPMD engine via
    :func:`build_sharded_paged`; requires a pure-DP mesh either way.
    """
    from ..backend.engine import Engine

    import os

    mesh = mesh or make_mesh()
    if paged is None:
        paged = os.environ.get("SWARMDB_PAGED", "0") == "1"
    if admit_overlap is None:
        admit_overlap = os.environ.get("SWARMDB_ADMIT_OVERLAP", "1") != "0"
    dp = mesh.shape.get("data", 1)
    pure_dp = all(mesh.shape.get(ax, 1) == 1
                  for ax in ("model", "expert", "pipe"))
    if (paged and admit_overlap and pure_dp and dp > 1
            and jax.process_count() == 1
            and engine_kwargs.get("paged") is None):
        from .lanes import build_lane_group

        group = build_lane_group(
            model_name_or_cfg, mesh,
            max_batch=max_batch if max_batch is not None else 8 * dp,
            max_seq=max_seq, seed=seed, page_size=page_size,
            kv_pool_tokens=kv_pool_tokens,
            metrics=engine_kwargs.get("metrics"),
            decode_chunk=engine_kwargs.get("decode_chunk", 8),
            prefill_batch=engine_kwargs.get("prefill_batch"),
            flight_dir=engine_kwargs.get("flight_dir"),
        )
        return group, group.info

    sm = build_sharded_model(model_name_or_cfg, mesh, seed=seed)
    if max_batch is None:
        max_batch = 8 * sm.data_size
    if paged is None:
        paged = os.environ.get("SWARMDB_PAGED", "0") == "1"
    if paged and engine_kwargs.get("paged") is None:
        prefix_on = os.environ.get("SWARMDB_PREFIX", "1") != "0"
        paged_spec, prefix_fns = build_sharded_paged(
            sm, max_batch=max_batch, max_seq=max_seq, page_size=page_size,
            kv_pool_tokens=kv_pool_tokens, prefix=prefix_on,
        )
        engine_kwargs["paged"] = paged_spec
        if prefix_fns is not None:
            engine_kwargs.setdefault("prefix_fns", prefix_fns)
    elif engine_kwargs.get("paged") is None:
        # the dense slab's triple; a page pool brings its own (PagedKV)
        engine_kwargs.setdefault("chunked_fns", sm.chunked_fns)
    engine = Engine(
        sm.forward_fn,
        sm.init_cache_fn,
        sm.params,
        max_batch=max_batch,
        max_seq=max_seq,
        seed=seed,
        routed=mixtral.routing_shape(sm.cfg),
        **engine_kwargs,
    )
    # replicated engine state must live ON the mesh (mandatory for
    # multi-process pods, harmless single-process): Engine.place_state
    engine.place_state(sm.mesh)
    # flight-recorder identity: step records of a sharded engine carry
    # per-shard occupancy (Engine._flight_step); the dump's meta block
    # names the mesh so a reader knows what those shards ARE
    engine.flight.meta.update({
        "mesh": {k: int(v) for k, v in sm.mesh.shape.items()},
        "paged_shards": int(getattr(
            getattr(engine.paged, "allocator", None), "n_shards", 1)
            if engine.paged else 1),
        "max_batch": max_batch,
        "max_seq": max_seq,
    })
    return engine, sm
