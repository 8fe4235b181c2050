"""ServingService + TPUBackend consumer — the north-star graft point.

The reference's LLM load balancer stops at a metadata map (agent →
backend-id, ` main.py:1281-1325`); nothing ever dispatches. Here the map
drives real serving (SURVEY §3.2 "graft point"):

- A ``TPUBackendConsumer`` drains the broker partitions for THIS backend
  (partition-affine, like any agent consumer) and turns chat /
  function_call messages addressed to LLM-backed agents into engine
  requests.
- Replies are emitted back through ``SwarmDB.send_message`` as first-class
  messages (type ``chat`` or ``function_result``), so lineage, stats,
  persistence, and the wire API all see them.
- ``stream_reply`` bridges the engine's per-token callbacks (engine
  thread) to an ``asyncio`` queue for SSE streaming
  (api/app.py ``_stream_reply``).
- Per-stage timestamps land in ``Message.metadata["stages"]`` — the
  tracing hook of SURVEY §5.1.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import os
import queue
import random
import threading
import time
from typing import Any, AsyncIterator, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.messages import Message, MessagePriority, MessageType
from ..core.runtime import SwarmDB
from ..obs import TRACER, procwatch
from ..utils.hashing import stable_partition
from .engine import Engine, GenRequest, PagedKV
from .sampling import SamplingParams
from .tokenizer import Tokenizer, default_tokenizer
from ..utils.sync import make_lock

logger = logging.getLogger("swarmdb_tpu.serving")

# module-level so repeated health() calls hit the jit cache instead of
# recompiling (and leaking cache entries) per probe
_HEALTH_PROBE = jax.jit(lambda x: (x * 2).sum())


def _env_int(name: str, default: int) -> int:
    """Forgiving env parse (repo convention: a malformed tuning knob
    logs and falls back, it never takes the serving path down)."""
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        logger.warning("%s=%r is not an int; using %d", name,
                       os.environ.get(name), default)
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        logger.warning("%s=%r is not a float; using %g", name,
                       os.environ.get(name), default)
        return default


def _history_line(m: Message) -> str:
    """One already-exchanged message as a prompt line — shared by
    build_prompt and the rolling-KV suffix builder (same no-drift rule
    as _current_lines)."""
    body = m.content if isinstance(m.content, str) else json.dumps(m.content)
    return f"{m.sender_id}: {body}"


def _current_lines(msg: Message) -> List[str]:
    """The served message's own prompt lines (+ the assistant cue) —
    shared by build_prompt and the rolling-KV suffix builder so the two
    renderings can never drift."""
    body = (msg.content if isinstance(msg.content, str)
            else json.dumps(msg.content))
    if msg.type == MessageType.FUNCTION_CALL:
        return [f"{msg.sender_id} [tool-call]: {body}",
                f"{msg.receiver_id} [tool-result]:"]
    return [f"{msg.sender_id}: {body}", f"{msg.receiver_id}:"]


def build_prompt(db: SwarmDB, msg: Message, tokenizer: Tokenizer,
                 history_limit: Optional[int] = None) -> List[int]:
    """Chat-style prompt from the two-way conversation plus the new message.

    For ``function_call`` messages the structured content (tool name/args)
    is embedded as JSON — the Mixtral tool-use path (BASELINE config 4).
    """
    if history_limit is None:
        # The window must be anchored in STREAM coordinates: a plain
        # newest-N fetch slides by one message every turn once N binds,
        # so consecutive prompts share no prefix and the prefix cache
        # goes dark for the rest of the conversation (measured: the
        # serve-mode hit rate cliffs to ~0 after ~N/2 turns). The
        # token-budget trim in serve_message provides the second,
        # token-level hysteresis.
        history_limit = _env_int("SWARMDB_HISTORY_LIMIT", 64)
    lines: List[str] = []
    if msg.receiver_id:
        convo = db.get_conversation_window(msg.sender_id, msg.receiver_id,
                                           history_limit)
        for m in convo:
            if m.id == msg.id:
                continue
            lines.append(_history_line(m))
    lines.extend(_current_lines(msg))
    return tokenizer.encode("\n".join(lines))


def _history_limit_for(max_seq: int) -> int:
    """History depth the serving layer actually renders. The env limit is
    an upper bound; a token-budgeted engine caps it near max_seq/8 —
    rendering + byte-encoding 64 history lines only for the trim to keep
    ~100 tokens of them was pure host work on every served message (the
    tooluse profile: ~25x the retained volume at S=256), and at >= 8
    tokens per line the cap can always still FILL the budget."""
    env = _env_int("SWARMDB_HISTORY_LIMIT", 64)
    return max(1, min(env, max(8, max_seq // 8)))


def sampling_from_message(msg: Message) -> SamplingParams:
    """Sampling knobs ride in Message.metadata (free-form dict the reference
    already reserves for annotations, ` main.py:80`)."""
    g = msg.metadata.get("generation", {}) if isinstance(msg.metadata, dict) else {}
    # clamp untrusted wire input to sane ranges
    raw_stop = g.get("stop", ())
    if isinstance(raw_stop, str):
        raw_stop = (raw_stop,)
    stop = tuple(str(s)[:64] for s in list(raw_stop)[:4] if s)
    seed = g.get("seed")
    return SamplingParams(
        temperature=max(0.0, float(g.get("temperature", 0.0))),
        top_k=max(0, int(g.get("top_k", 0))),
        top_p=min(1.0, max(1e-3, float(g.get("top_p", 1.0)))),
        max_new_tokens=min(4096, max(1, int(g.get("max_new_tokens", 64)))),
        stop=stop,
        seed=int(seed) if seed is not None else None,
    )


def build_backend_engine(
    model_name_or_cfg,
    *,
    max_batch: int = 8,
    max_seq: Optional[int] = None,
    seed: int = 0,
    decode_chunk: int = 8,
    paged: Optional[bool] = None,
    page_size: int = 16,
    kv_pool_tokens: Optional[int] = None,
    prefill_batch: Optional[int] = None,
    metrics=None,
    flight_dir: Optional[str] = None,
    tokenizer_path: Optional[str] = None,
) -> Tuple[Engine, Tokenizer]:
    """One single-device Engine (dense or paged) for a registry config —
    the construction ``ServingService.from_model_name`` has always done,
    factored out so the per-shard admission lanes
    (``parallel/lanes.ShardLaneGroup``) can build one engine PER DEVICE
    with identical wiring. Weights are randomly initialized (shapes and
    compute are identical to a checkpoint restore); everything eager
    here (params, pools, slot state) lands on the caller's
    ``jax.default_device`` scope, which is how a lane pins its engine to
    one mesh device."""
    from ..models import deepseek, lfm2, llama, mixtral, nemotron_h
    from ..models.configs import ModelConfig, get_config
    cfg = (model_name_or_cfg
           if isinstance(model_name_or_cfg, ModelConfig)
           else get_config(model_name_or_cfg))
    seq = max_seq or min(cfg.max_seq_len, 1024)
    key = jax.random.PRNGKey(seed)
    # one decoder serves every family (models/llama.py); what a family
    # brings of its own is its parameter tree, and the configuration's
    # fields say which family it is
    family = (deepseek if cfg.latent
              else nemotron_h if cfg.sublayers
              else lfm2 if cfg.layer_types is not None
              else mixtral if cfg.is_moe else llama)
    params = family.init_params(cfg, key)
    if paged is None:
        paged = os.environ.get("SWARMDB_PAGED", "0") == "1"
    if (cfg.stateful or cfg.latent) and not paged:
        llama.refuse_state(cfg, "the dense slab engine")
    fwd = lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c)
    fwd_last = lambda p, t, pos, c, at: llama.forward(
        p, cfg, t, pos, c, logits_at=at)
    init_cache = lambda b, s: llama.init_kv_cache(cfg, b, s)
    # two-segment chunked decode — the cache (dense slot buffer OR
    # paged pool) stays frozen per chunk; see Engine._decode /
    # ops.layers. A page pool's triple rides its PagedKV, the dense
    # slab's goes to the engine as ``chunked_fns``.
    # ONE prefix-cache enablement flag shared by paged pool sizing and
    # prefix_fns wiring (review finding: duplicated conditions drift)
    prefix_enabled = (
        os.environ.get("SWARMDB_PREFIX", "1") != "0"
        and seq % page_size == 0
    )
    chunk_fwd = (llama.forward_paged_chunked if paged
                 else llama.forward_chunked)
    if paged:
        merge = llama.merge_paged_chunk
    elif os.environ.get("SWARMDB_MERGE", "einsum") == "scatter":
        # scatter-form chunk merge: numerically identical
        # (ops/layers.merge_chunk_kv_scatter); raced against the
        # einsum form on silicon by scripts/profile_merge.py
        merge = llama.merge_chunk_scatter
    else:
        merge = llama.merge_chunk
    chunked_fns = (
        lambda p, t, pos, c, hkv, s: chunk_fwd(p, cfg, t, pos, c, hkv, s),
        lambda b, k: llama.init_chunk_kv(cfg, b, k),
        merge,
    )

    paged_spec = None
    if paged:
        from ..ops.paged_kv import make_page_allocator, pages_per_slot

        maxp = pages_per_slot(seq, page_size)
        if kv_pool_tokens is None and "SWARMDB_KV_POOL_TOKENS" in os.environ:
            kv_pool_tokens = int(os.environ["SWARMDB_KV_POOL_TOKENS"])
        pool_tokens = kv_pool_tokens or max_batch * maxp * page_size
        if kv_pool_tokens is None and prefix_enabled:
            # prefix caching shares this pool: cached pages compete
            # with slot footprints, so grow the default by the prefix
            # budget or admissions starve once the cache warms up
            pool_tokens += int(os.environ.get(
                "SWARMDB_PREFIX_TOKENS", max_batch * seq // 2))
        num_pages = 1 + -(-pool_tokens // page_size)  # +1 trash page
        paged_spec = PagedKV(
            chunked_fns=chunked_fns,
            init_pool=lambda: llama.init_paged_cache(
                cfg, max_batch, seq, num_pages, page_size),
            page_size=page_size,
            num_pages=num_pages,
            allocator=make_page_allocator(num_pages, page_size, seq,
                                          max_batch),
        )
        if not cfg.ffn_drops:
            # packed ragged admission waves (ISSUE 11): one no-padding
            # token stream per wave, prefix KV read in place from the
            # pool, for every family whose token's result does not depend
            # on what shares its call: a dense FFN, and a routed one that
            # computes every choice (models/lfm2.py; its conv state rides
            # the wave as ``seed``). The family whose FFN drops keeps the
            # row-bucketed ladder: ``mixtral.moe_block`` reckons its
            # capacity over the tokens of the call, padding included, so
            # a packed stream and a row-bucketed wave would drop
            # different tokens (ROADMAP Design 13 has what is left).
            paged_spec.prefill_ragged = (
                lambda p, toks, trow, tpos, tables, st, ln, pl, pk, pv,
                *seed: llama.forward_ragged_prefill(
                    p, cfg, toks, trow, tpos, tables, st, ln, pl, pk, pv,
                    *seed))
            from ..ops.layers import ragged_wave_max_width

            paged_spec.ragged_max_width = ragged_wave_max_width(
                cfg.n_heads, cfg.n_kv_heads)

    # Automatic prefix caching: chat serving re-prefills each
    # conversation's history every turn, so reuse of page-aligned
    # prompt KV is the dominant serve-mode lever (round-4 profile:
    # prefill FLOPs ~15:1 over decode). Default ON; SWARMDB_PREFIX=0
    # disables. DENSE engines keep a side pool (SWARMDB_PREFIX_TOKENS,
    # default max_batch*max_seq/2 — half the decode cache's footprint,
    # so enabling the feature never doubles an existing deployment's
    # KV HBM; benches size it up). PAGED engines reuse the main pool
    # in place (grown above by the same budget).
    prefix_fns = None
    prefix_pages = 0
    if prefix_enabled:
        if paged:
            # paged mode reuses the MAIN pool in place; only the
            # suffix-forward core is needed (no side pool, no lane)
            prefix_fns = (
                lambda p, t, tab, pl, pk, pv, logits_at=None:
                    llama.forward_prefix_pages(p, cfg, t, tab, pl, pk, pv,
                                               logits_at=logits_at),
                None,
            )
        else:
            prefix_tokens = int(os.environ.get(
                "SWARMDB_PREFIX_TOKENS", max_batch * seq // 2))
            prefix_pages = 1 + -(-prefix_tokens // page_size)  # +1 trash
            prefix_fns = (
                lambda p, t, tab, pl, pk, pv, lp, logits_at=None:
                    llama.forward_prefix_lane(p, cfg, t, tab, pl, pk, pv,
                                              lp, logits_at=logits_at),
                lambda n, ps: llama.init_prefix_pool(cfg, n, ps),
            )

    tokenizer = default_tokenizer(cfg.vocab_size, tokenizer_path)
    engine = Engine(
        fwd, init_cache, params,
        max_batch=max_batch, max_seq=seq,
        eos_id=tokenizer.eos_id, pad_id=tokenizer.pad_id, seed=seed,
        metrics=metrics, decode_chunk=decode_chunk, paged=paged_spec,
        prefill_batch=prefill_batch,
        chunked_fns=None if paged else chunked_fns,
        pipeline_depth=int(os.environ.get("SWARMDB_PIPELINE", "2")),
        prefix_fns=prefix_fns, prefix_pages=prefix_pages,
        prefix_page_size=page_size, forward_last_fn=fwd_last,
        flight_dir=flight_dir,
        # a configuration that routes: its forwards return their routing
        # last and the engine carries it to GenRequest.routing
        routed=family.routing_shape(cfg) if cfg.is_moe else None,
        # ... of which this chip's weights hold a share (first, count)
        held_experts=((cfg.first_held_expert, cfg.experts_held)
                      if cfg.n_experts_held else None),
    )
    return engine, tokenizer


class ServingService:
    """Owns one Engine + its broker consumer; routes messages → generation."""

    def __init__(
        self,
        db: SwarmDB,
        engine: Engine,
        tokenizer: Tokenizer,
        backend_id: str = "tpu-0",
        poll_interval: float = 0.05,
    ) -> None:
        self.db = db
        self.engine = engine
        self.tokenizer = tokenizer
        self.backend_id = backend_id
        self.poll_interval = poll_interval
        # point the runtime's SLO sentinel at THIS engine: breach alerts
        # auto-dump the engine's flight rings + the process trace, and
        # the engine loop drives window closes even when no sends flow
        db.sentinel.bind(flight=engine.flight, tracer=engine.tracer,
                         flight_dir=engine._flight_dir)
        engine.sentinel = db.sentinel
        # lane supervision + retry/deadline budgets (ISSUE 9,
        # backend/supervisor.py): every served request is adopted —
        # deadline (SWARMDB_REQ_DEADLINE_S) + retry budget
        # (SWARMDB_REQ_RETRIES) stamped, retryable engine losses
        # (RETRYABLE_REASONS) requeued with jittered backoff, and lane
        # groups get quarantine/migration/re-admission. SWARMDB_SUPERVISE=0
        # restores the bare watchdog-restart behavior.
        self.supervisor = None
        if os.environ.get("SWARMDB_SUPERVISE", "1") != "0":
            from .supervisor import LaneSupervisor

            if getattr(engine, "lanes", None) is not None:
                self.supervisor = engine.attach_supervisor(
                    metrics=db.metrics)
            else:
                self.supervisor = LaneSupervisor(
                    engine, metrics=db.metrics).start()
        self._consumer_thread: Optional[threading.Thread] = None
        self._procwatch = None
        self._stop = threading.Event()
        # Reply emission (tokenizer decode + send_message + persistence
        # hooks) runs on its own worker, NOT the engine thread: at 32-128
        # retirements per decode chunk, inline emission serializes ~100s of
        # broker sends into the decode loop and the device sits idle the
        # whole time (round-4 profile: the engine loop, not the compiled
        # chunk, was the round-3 bottleneck).
        self._reply_queue: "queue.Queue" = queue.Queue()
        self._reply_thread: Optional[threading.Thread] = None
        # n>1 fan-out groups: completion-0 rid -> all member rids, so a
        # cancel reaches every alternative (popped at aggregate emission)
        self._fanout: Dict[str, List[str]] = {}
        # rolling-KV conversation registry (SWARMDB_ROLLING_KV=1, paged):
        # (sender, receiver) -> {pages, len, tail, msg_count, epoch,
        # in_flight, last}. Custody of the listed pages belongs HERE
        # between turns (the engine only references them during a resumed
        # request). StreamingLLM-style: outputs drift from a re-prefill
        # baseline because the reply's KV is the model's own continuation
        # rather than a re-tokenization of its text.
        self._rolling: Optional[Dict[Tuple[str, str], Dict[str, Any]]] = None
        self._rolling_lock = make_lock("backend.service.ServingService._rolling_lock")
        # EMA of per-turn suffix length (tokens), sizing the restart
        # reserve (see _rolling_plan / serve_message keep-trim). Seeded
        # relative to the window: an absolute seed larger than a small
        # window's budget would size the reserve before any evidence
        self._rolling_delta_ema = min(64.0, engine.max_seq / 8.0)
        # sink-anchored window heads (see _trim_prompt): conversation pair
        # -> the page-aligned FIRST tokens of its prompt, captured at the
        # first budget overflow and immutable after. Insertion order is
        # the LRU order for the size cap.
        self._anchors: Dict[Tuple[str, str], List[int]] = {}
        self._anchor_lock = make_lock("backend.service.ServingService._anchor_lock")
        self._anchor_cap = _env_int("SWARMDB_ANCHOR_MAX", 4096)
        # fixed elision marker between head and tail — constant tokens, so
        # it can never destabilize the prefix
        self._anchor_sep = self.tokenizer.encode("\n[…]\n", add_bos=False)
        # leadership-pinned conversation locality (ISSUE 14): attached by
        # bind_partition_leadership when this process embeds an HA node
        # running partition leadership — shard hints then come from the
        # conversation's partition LEADER, not the bare pair hash
        self._locality = None
        # swarmmem conversation-temperature ledger (ISSUE 17): touched
        # once per served message / retirement — the evidence layer the
        # tiered-KV hierarchy (ROADMAP item 3) is sized against. Flag
        # off -> the shared NullConvLedger.
        from ..obs.memprof import memprof

        self._mem = memprof().conv_ledger()
        rolling_wanted = os.environ.get("SWARMDB_ROLLING_KV") == "1"
        if (rolling_wanted and self.engine.paged is not None
                and getattr(self.engine.paged.allocator,
                            "n_shards", 1) > 1):
            # DP-sharded pool: a kept conversation's pages pin it to ONE
            # shard, but admission assigns any free slot — resume would
            # need shard-affine slot routing that isn't wired yet
            # (parallel/serving.build_sharded_paged docstring)
            logger.warning("SWARMDB_ROLLING_KV=1 ignored: rolling resume "
                           "is not supported on a DP-sharded page pool")
            rolling_wanted = False
        if rolling_wanted and self.engine.supports_rolling():
            # paged engines resume by page-custody transfer; DENSE engines
            # roll too (round 5): retirement copies the lane KV into
            # prefix-pool pages (Engine._dense_keep_extract), resume
            # composes them back mid-page (_prefill_dense_resume_batch)
            self._rolling = {}
            # low-memory hook (ADVICE r4 #1): when paged admission (or a
            # dense retirement extraction) cannot allocate, evict idle
            # conversations' kept pages instead of stalling/not rolling —
            # non-rolling traffic must never starve behind parked KV
            self.engine.on_pool_pressure = self._on_pool_pressure
        # swarmtier (ISSUE 19): the three-tier conversation-state
        # hierarchy — hot device pages, warm host-RAM spill, cold
        # log-replay resume. Engages on the same preconditions as
        # rolling resume itself (warm custody IS registry custody):
        # single-shard paged engine, no pod. SWARMDB_TIER=0 disables.
        self._tier = None
        if (self._rolling is not None and self.engine.paged is not None
                and self.engine._mh is None):
            from .tiering import TierManager, tiering_enabled

            if tiering_enabled():
                self._tier = TierManager(self, self.engine)

    def bind_partition_leadership(self, ha_node) -> None:
        """Ride partition leadership (ISSUE 14): every conversation's
        ``shard_hint`` is derived from its log partition's CURRENT
        leader (``ConversationLocality``), and the lane group is
        subscribed to the node's rebalance stream so a leadership move
        (drain handover, failover promotion) deterministically re-pins
        the conversation's lane — its anchor head and prefix pages
        re-register on the new lane at the next turn, and ``ha.repin``
        instants let the analyzer attribute TTFT spikes to leadership
        churn. No-op unless the node runs partition leadership; without
        a bind the PR 8 pair-hash hint is used, bit-identical."""
        if ha_node is None or not getattr(ha_node, "partition_leadership",
                                          False):
            return
        from .locality import ConversationLocality

        n_lanes = (getattr(self.engine.paged.allocator, "n_shards", 1)
                   if self.engine.paged is not None else 1)
        self._locality = ConversationLocality(
            topic=self.db.topic_name, n_lanes=n_lanes,
            leadership=ha_node.assignment_of,
            num_partitions=self.db.num_partitions,
            local_node=ha_node.node_id,
            metrics=self.db.metrics, flight=self.engine.flight)
        ha_node.add_rebalance_listener(self._locality.on_rebalance)

    # ------------------------------------------------------------ lifecycle

    @classmethod
    def from_model_name(
        cls,
        db: SwarmDB,
        model_name: str,
        backend_id: str = "tpu-0",
        max_batch: int = 8,
        max_seq: Optional[int] = None,
        seed: int = 0,
        tokenizer_path: Optional[str] = None,
        decode_chunk: int = 8,
        paged: Optional[bool] = None,
        page_size: int = 16,
        kv_pool_tokens: Optional[int] = None,
        prefill_batch: Optional[int] = None,
    ) -> "ServingService":
        """Build model + engine for a registry config. Weights are randomly
        initialized unless a checkpoint is loaded afterwards
        (``utils/checkpoint.py``) — shapes/compute are identical either way.

        ``paged`` switches the decode cache to the block-paged pool
        (ops/paged_kv.py; default = SWARMDB_PAGED env, off otherwise);
        ``kv_pool_tokens`` bounds pool HBM (default: full max_batch*max_seq
        coverage, i.e. no savings but no admission stalls — benches pass a
        budget to realize the savings).
        """
        engine, tokenizer = build_backend_engine(
            model_name, max_batch=max_batch, max_seq=max_seq, seed=seed,
            decode_chunk=decode_chunk, paged=paged, page_size=page_size,
            kv_pool_tokens=kv_pool_tokens, prefill_batch=prefill_batch,
            metrics=db.metrics, tokenizer_path=tokenizer_path,
            # watchdog restarts auto-dump the flight record here (see
            # obs/flight.py; SWARMDB_FLIGHT_DIR overrides)
            flight_dir=os.path.join(db.save_dir, "flight"),
        )
        engine.flight.meta.update({"backend_id": backend_id,
                                   "model": model_name})
        return cls(db, engine, tokenizer, backend_id=backend_id)

    def start(self, warmup: Optional[bool] = None) -> None:
        """Bring up the engine, reply emitter, and broker consumer.

        ``warmup`` pre-compiles every decode/prefill variant before traffic
        (Engine.warmup); default = SWARMDB_PREWARM env. It runs before the
        consumer thread starts so no request can race the idle-engine
        requirement.
        """
        if warmup is None:
            warmup = os.environ.get("SWARMDB_PREWARM", "0") == "1"
        if warmup:
            self.engine.warmup()
        else:
            # swarmprof (ISSUE 15): an operator who skipped prewarm still
            # gets harvested cost-model facts (pure lowering — no
            # compiles, no execution) and a duty-cycle clock anchored at
            # serving start instead of engine construction. First-traffic
            # compile stalls DO ride the device-time ledger on this path
            # — prewarm is the clean-numbers configuration (README
            # "Profiling").
            try:
                from ..obs.profiler import NullLane

                for eng in getattr(self.engine, "lanes", [self.engine]):
                    if (hasattr(eng, "profile_harvest")
                            and not isinstance(eng._prof, NullLane)):
                        eng.profile_harvest()
                    prof = getattr(eng, "_prof", None)
                    if prof is not None:
                        prof.resume()
            except Exception:
                logger.exception("swarmprof startup harvest failed")
        self.engine.start()
        # the consumer's duty, at 0 from the start: 0 is then a reading
        # and absence an older program
        for name in ("serve_poll_rounds", "serve_poll_rounds_idle",
                     "serve_poll_sleep_us"):
            self.db.metrics.counters[name].inc(0)
        if self._procwatch is None:
            # one a process, counted (obs/procwatch.py); None where
            # SWARMDB_TRACE=0 turned every span off
            self._procwatch = procwatch.acquire(
                self.db.metrics, getattr(self.engine, "lanes", [self.engine]))
        if self._reply_thread is None:
            self._reply_thread = threading.Thread(
                target=self._reply_loop, daemon=True,
                name=f"tpu-replies-{self.backend_id}",
            )
            self._reply_thread.start()
        if self._consumer_thread is None:
            self._consumer_thread = threading.Thread(
                target=self._consume_loop, daemon=True,
                name=f"tpu-backend-{self.backend_id}",
            )
            self._consumer_thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._consumer_thread is not None:
            self._consumer_thread.join(timeout=10)
            self._consumer_thread = None
        if self._tier is not None:
            # stop tier planning before the engine: a demotion order
            # queued after engine shutdown would never drain
            self._tier.stop()
        if self.supervisor is not None:
            # stop supervision BEFORE the engine: a lane going dead
            # during shutdown must not trigger a restart/migration race
            self.supervisor.stop()
        if self._procwatch is not None:
            procwatch.release(getattr(self.engine, "lanes", [self.engine]))
            self._procwatch = None
        self.engine.stop()
        if self._reply_thread is not None:
            self._reply_queue.put(None)  # sentinel AFTER engine drained
            self._reply_thread.join(timeout=10)
            self._reply_thread = None

    # --------------------------------------------------- broker consumption

    def _consume_loop(self) -> None:
        """Poll the inboxes of LLM-backed agents and serve new requests.

        Uses the same partition-affine receive path as any agent
        (SwarmDB.receive_messages), so backend serving respects broker
        ordering, offsets, and visibility; one consumer per backend drains
        all of its assigned agents.
        """
        counters = self.db.metrics.counters
        slept = (0, 0)      # the last idle sleep, on time.monotonic_ns
        while not self._stop.is_set():
            # watchdog (SURVEY §5.3): a dead decode loop strands every
            # in-flight and queued request — restart it, failing them fast
            # so lineage/resend applies instead of silent timeouts. With a
            # supervisor attached, recovery (and per-lane quarantine) is
            # ITS job — engine.alive() then only reads dead when every
            # lane is gone AND the supervisor's own restarts failed.
            if self.supervisor is None and not self.engine.alive():
                logger.error("engine loop dead; restarting backend %s",
                             self.backend_id)
                try:
                    self.engine.restart()
                except Exception:
                    logger.exception("engine restart failed; backing off")
                    self._stop.wait(1.0)
                    continue
            agents = self.db.agents_for_backend(self.backend_id)
            counters["serve_poll_rounds"].inc()
            served = 0
            for agent in agents:
                if self._stop.is_set():
                    break
                try:
                    msgs = self.db.receive_messages(agent, max_messages=8,
                                                    timeout=0.0)
                except Exception:
                    logger.exception("backend receive failed for %s", agent)
                    continue
                for msg in msgs:
                    if msg.type in (MessageType.CHAT, MessageType.FUNCTION_CALL):
                        # one bad message must not kill the consumer thread
                        try:
                            self._trace_pickup(msg, slept, served,
                                               len(agents))
                            self.serve_message(msg)
                        except Exception:
                            logger.exception("serve_message failed for %s", msg.id)
                            self.db.update_message_status(msg.id, "failed")
                            self.db.metrics.counters["backend_serve_errors"].inc()
                        served += 1
                    else:
                        # non-servable types stay available via the inbox /
                        # query APIs (a backend-owned agent's broker stream
                        # belongs to the backend); count them for visibility
                        logger.debug("backend skipping %s message %s for %s",
                                     msg.type.value, msg.id, agent)
                        self.db.metrics.counters["backend_skipped_messages"].inc()
            if served == 0:
                # counted, not traced: an empty round every poll_interval
                # would fill the ring the window's spans have to stay in
                t_sleep = time.monotonic_ns()
                self._stop.wait(self.poll_interval)
                slept = (t_sleep, time.monotonic_ns())
                counters["serve_poll_rounds_idle"].inc()
                counters["serve_poll_sleep_us"].inc(
                    (slept[1] - t_sleep) // 1000)

    def _trace_pickup(self, msg: Message, slept: Tuple[int, int],
                      behind: int, agents: int) -> None:
        """``serve.pickup``: from the message's ``enqueued`` stamp (wall
        clock, put onto the rings' clock) to now, when the consumer hands
        it to ``serve_message``. ``slept_us`` is the part of it that the
        consumer's last idle sleep ``slept`` covers, ``behind`` the
        messages this round served before it, ``agents`` the inboxes a
        round walks."""
        if not TRACER.enabled:
            return
        enqueued = msg.metadata.get("stages", {}).get("enqueued")
        if enqueued is None:
            return
        now = time.monotonic_ns()
        t0 = min(TRACER.mono_of_epoch(enqueued), now)
        overlap = min(now, slept[1]) - max(t0, slept[0])
        TRACER.span_end(t0, "serve.pickup", cat="serving", rid=msg.id,
                        args={"slept_us": max(0, overlap) // 1000,
                              "behind": behind, "agents": agents})

    # ------------------------------------------------------ rolling KV

    def _rolling_epoch(self) -> int:
        """Engine restarts rebuild the page pool; registry entries from
        an older epoch hold dangling page ids and must never be resumed
        OR add_free'd (the reset already reclaimed the pool). Keyed on the
        allocator's own pool generation (bumped inside reset(), ADVICE r4
        #2): the restart counter incremented on a different schedule than
        the pool rebuild, leaving a race window, and the in-loop error
        recovery rebuilt the pool without touching it at all."""
        return self.engine.pool_epoch()

    def _on_pool_pressure(self, need: int) -> None:
        """Engine thread, paged admission failed to allocate ``need``
        pages: spill the coldest idle conversations to the warm tier
        first (their KV survives and comes back via promotion), then
        LRU-evict to nothing for any shortfall — the pre-tier
        behavior, and still the only option with SWARMDB_TIER=0."""
        with self._rolling_lock:
            if self._tier is not None:
                need -= self._tier.demote_now(need)
            if need > 0:
                self._rolling_evict(need)

    # swarmlint: holds[self._rolling_lock]
    def _rolling_evict(self, need_free: int) -> None:
        """LRU-evict idle conversations until the pool can cover
        ``need_free`` pages (caller holds _rolling_lock)."""
        eng = self.engine
        epoch = self._rolling_epoch()
        idle = sorted(
            (k for k, st in self._rolling.items()
             if not st.get("in_flight") and st.get("pages")),
            key=lambda k: self._rolling[k]["last"])
        for k in idle:
            if eng.rolling_free_count() >= need_free:
                break
            st = self._rolling.pop(k)
            if st["epoch"] == epoch:
                eng.rolling_free(st["pages"])
            self._mem.drop(k)
            self.db.metrics.counters["rolling_evictions"].inc()
            if self._tier is not None:
                # evicted to NOTHING — the conversation's next turn is
                # a cold resume (re-prefill from the broker log)
                self._tier.note_cold(k, len(st["pages"]))

    def _rolling_plan(self, key, msg: Message, sampling: SamplingParams,
                      pre_count: int = 0):
        """Decide how this turn uses the rolling registry.

        Returns (mode, resume, prompt_tokens):
          - ("resume", (pages, len), tokens): continue the kept pages.
          - ("keep", None, None): fresh prefill, but the turn claims the
            conversation (keep_pages set; retirement replaces the state).
          - ("plain", None, None): fresh prefill, registry untouched — a
            concurrent turn of the same conversation owns the claim, and
            setting keep_pages here would hand over pages that a later
            on_pages overwrite would leak.
        """
        eng = self.engine
        ps = eng.rolling_page_size()
        if eng._mh is not None:
            # pod mode supports paged/prefix serving but not rolling
            # resume (engine.submit rejects it): registry page custody
            # cannot survive the pod's restart-based failure recovery
            return "plain", None, None
        with self._rolling_lock:
            epoch = self._rolling_epoch()
            st = self._rolling.get(key)
            if (st is not None and st["epoch"] != epoch
                    and st.get("pages")):
                # stale epoch: pool was rebuilt, page ids are dangling.
                # WARM (host-resident) entries hold no device ids and
                # survive pool resets by design — the payload re-enters
                # whatever pool exists at promotion time (ISSUE 19)
                self._rolling.pop(key, None)
                st = None
            if st is not None and st.get("in_flight"):
                return "plain", None, None
            # pending_count = the caller's PRE-prompt-fetch stream
            # length: stamping it at store/retirement time would count
            # mid-generation arrivals as rendered (silently omitting
            # them from every future suffix — measured: near zero
            # resumes); stamping it after build_prompt's window fetch
            # would drop a message landing between fetch and stamp.
            # Before-fetch is the safe direction: late arrivals render
            # next turn, at worst duplicated once if they also made
            # this turn's window.
            placeholder = {"pages": None, "len": 0, "tail": [],
                           "msg_count": 0, "reply_ids": [],
                           "pending_count": pre_count,
                           "epoch": epoch, "in_flight": True,
                           # cleared by _rolling_store; if still set at
                           # finalize, the turn's KV was never adopted
                           # (dense extraction bailed) and the state must
                           # restart — keeping it would exclude the reply
                           # BY ID from future suffixes while its tokens
                           # exist in neither the KV nor the prompt
                           "await_store": True,
                           "last": time.time()}
            # warm hit (ISSUE 19): the conversation's pages were spilled
            # to the host store; the resume path below runs unchanged
            # (st["len"]/tail/msg_count are tier-independent) and the
            # actual reservation + payload pop happen only after every
            # delta/fit check has passed
            warm = (st is not None and not st.get("pages")
                    and st.get("host") and self._tier is not None)
            if st is None or (not st.get("pages") and not warm):
                self._rolling[key] = placeholder
                if self._tier is not None:
                    msg.metadata["tier_origin"] = (
                        "cold" if self._tier.take_cold(key) else "fresh")
                return "keep", None, None

            # atomic (total, delta) — a split length+fetch pair can drop
            # the oldest unseen message under concurrent sends
            total, delta = self.db.get_conversation_delta(
                key[0], key[1], st["msg_count"])
            if not any(m.id == msg.id for m in delta):
                # registry out of sync with the stream (e.g. snapshot
                # restore): restart the conversation fresh
                logger.debug("rolling restart %s: msg %s not in delta "
                             "(msg_count=%d total=%d)", key, msg.id,
                             st["msg_count"], total)
                if st.get("pages") and st["epoch"] == epoch:
                    eng.rolling_free(st["pages"])
                elif warm:
                    # the warm payload is obsolete (the restart rebuilds
                    # the prompt from the full window) — discard it
                    self._tier.drop_warm(key)
                self._rolling[key] = placeholder
                self.db.metrics.counters["rolling_restarts"].inc()
                return "keep", None, None
            lines = []
            for m in delta:
                if m.id == msg.id or m.id in st["reply_ids"]:
                    # the current message renders last; replies are in
                    # the KV as the model's own generated tokens
                    continue
                lines.append(_history_line(m))
            lines.extend(_current_lines(msg))
            suffix = "".join("\n" + ln for ln in lines)
            ptoks = list(st["tail"]) + self.tokenizer.encode(
                suffix, add_bos=False)
            fits = (
                st["len"] + len(ptoks) + sampling.max_new_tokens
                + eng.decode_chunk < eng.max_seq
                and -(-st["len"] // ps) <= eng._prefix_pp_buckets[-1]
                and len(ptoks) > 0
            )
            if not fits:
                # conversation outgrew the window: restart fresh (the
                # caller's trimmed prompt) and release the kept pages.
                # The delta EMA must update HERE too: in a restart-locked
                # regime resumes never happen, so an EMA fed only by
                # resumes could never grow the reserve that breaks the
                # lock
                self._rolling_delta_ema = (0.8 * self._rolling_delta_ema
                                           + 0.2 * len(ptoks))
                logger.debug("rolling restart %s: doesn't fit (len=%d "
                             "ptoks=%d max_new=%d max_seq=%d)", key,
                             st["len"], len(ptoks),
                             sampling.max_new_tokens, eng.max_seq)
                if st.get("pages") and st["epoch"] == epoch:
                    eng.rolling_free(st["pages"])
                elif warm:
                    # the warm payload is obsolete (the restart rebuilds
                    # the prompt from the full window) — discard it
                    self._tier.drop_warm(key)
                self._rolling[key] = placeholder
                self.db.metrics.counters["rolling_restarts"].inc()
                return "keep", None, None
            # pool headroom. Paged: only the FRESH pages beyond the kept
            # ones are allocated at admission (kept pages are referenced
            # in place) — evicting to the full footprint would destroy
            # other conversations' kept KV for nothing. DENSE: retirement
            # extraction wants the FULL new page set; provision it here
            # when others' idle state can cover it, but shortage is not
            # fatal — the extraction releases this conversation's own
            # superseded pages first and reuses them (engine
            # _dense_keep_extract escalation ladder)
            total_pages = -(-(st["len"] + len(ptoks)
                              + sampling.max_new_tokens
                              + eng.decode_chunk) // ps)
            # kept pages by COUNT, not list: a warm entry's pages are
            # host-resident (st["pages"] is None) but cover exactly
            # ceil(len/ps) device pages once promoted — same count a
            # hot entry's kept list holds (engine _retire invariant)
            kept_n = -(-st["len"] // ps)
            if warm:
                # promotion draws the kept pages from the pool TOO (a
                # hot resume references them in place)
                need = total_pages
            else:
                need = (total_pages - kept_n if eng.paged
                        else total_pages)
            # claim THIS conversation before evicting: _rolling_evict
            # skips in_flight entries, and without the claim a
            # pool-pressure eviction here could LRU-free the very pages
            # the plan returns below (review r5: freed pages re-allocated
            # by a concurrent admission while the resume prefill composes
            # from them — silent cross-conversation KV aliasing)
            st["in_flight"] = True
            if need > 0:
                # shortage after evicting others is survivable downstream:
                # paged admission break-retries with the pressure hook,
                # and the dense retirement extraction self-reuses the
                # conversation's own superseded pages (_dense_keep_extract)
                self._rolling_evict(need)
            st["pending_count"] = total
            st["await_store"] = True  # see placeholder comment
            st["last"] = time.time()
            self.db.metrics.counters["rolling_resumes"].inc()
            # typical per-turn suffix size (EMA): sizes the restart
            # reserve in serve_message so a restarted conversation always
            # has room for a few turns before the next overflow — a fixed
            # restart fraction can land the kept length EXACTLY at
            # max_seq minus one turn, locking the conversation into a
            # restart-every-turn loop (measured: 12:1 restarts:resumes on
            # the serve mix at S=256 with ~105-token turn deltas)
            self._rolling_delta_ema = (0.8 * self._rolling_delta_ema
                                       + 0.2 * len(ptoks))
            payload = None
            if warm:
                got = self._tier.begin_promote(key, st, epoch)
                if got is None:
                    # warm copy lost (store capacity eviction raced) or
                    # the pool cannot host it even after evicting: the
                    # conversation resumes COLD — the fresh prefill
                    # re-derives its KV from the broker log, which PR 8
                    # proved bit-identical at every chunk boundary
                    self._rolling[key] = placeholder
                    msg.metadata["tier_origin"] = (
                        "cold" if self._tier.take_cold(key) else "fresh")
                    self.db.metrics.counters["rolling_restarts"].inc()
                    return "keep", None, None
                ids, payload = got
                st["pages"] = list(ids)
                st["epoch"] = epoch
                st["host"] = False
            if self._tier is not None:
                msg.metadata["tier_origin"] = "warm" if warm else "hot"
            # the observed epoch travels WITH the plan: submit/admission
            # re-validate it against the live pool generation, so a pool
            # reset in the plan->admit window fails the request instead
            # of resuming dangling page ids (ADVICE r4 #2)
            return "resume", (st["pages"], st["len"], epoch,
                              payload), ptoks

    def _rolling_store(self, key, pages, written, tail) -> None:
        """on_pages (engine thread, at retirement): adopt the turn's
        pages as the conversation's new state. A replaced predecessor's
        pages were already released by _rolling_plan (fresh-restart) or
        are a PREFIX of ``pages`` (resume) — never double-freed."""
        with self._rolling_lock:
            prev = self._rolling.get(key, {})
            self._rolling[key] = {
                "pages": pages, "len": written, "tail": list(tail),
                # everything at stream index < msg_count is in the KV (or
                # was deliberately trimmed by the fresh window); replies
                # are excluded BY ID, so interleaved foreign messages can
                # never be skipped by a count race. pending_count was
                # stamped at PLAN time (see _rolling_plan) — the
                # length-read fallback only covers store calls that
                # bypassed a plan (not a serving path)
                "msg_count": prev.get("pending_count",
                                      self.db.conversation_length(*key)),
                "reply_ids": list(prev.get("reply_ids", ())),
                "epoch": self._rolling_epoch(),
                "in_flight": True, "last": time.time(),
            }
        self._mem.resident(key, len(pages))

    def _rolling_finalize(self, key, msg: Message, reason: str) -> None:
        """After the reply message is SENT (reply worker): record the
        reply id (excluded from future suffixes — its tokens are already
        in the KV as the model's own continuation); non-clean finishes
        drop the state instead."""
        with self._rolling_lock:
            st = self._rolling.get(key)
            if st is None:
                return
            if (reason in ("length", "eos") and st.get("pages")
                    and not st.get("await_store")):
                rid = (msg.metadata or {}).get("reply_id")
                if rid:
                    # only replies at stream index >= msg_count matter
                    # (older ones fall below the next delta); cap the
                    # list so a conversation never accumulates ids
                    st["reply_ids"] = st["reply_ids"][-3:] + [rid]
                st["in_flight"] = False
                st["last"] = time.time()
            else:
                # non-clean finish, or a clean finish whose KV was never
                # adopted (await_store still set: dense extraction
                # bailed) — drop the state so the next turn rebuilds the
                # prompt from the full window instead of excluding a
                # reply that exists in neither the KV nor the suffix
                if st.get("await_store") and reason in ("length", "eos"):
                    self.db.metrics.counters["rolling_restarts"].inc()
                self._rolling.pop(key, None)
                self._mem.drop(key)
                if (st.get("pages")
                        and st["epoch"] == self._rolling_epoch()):
                    self.engine.rolling_free(st["pages"])
                elif st.get("host") and self._tier is not None:
                    # host-resident state dropped non-clean: the warm
                    # payload no longer matches the stream — discard
                    self._tier.drop_warm(key)

    # ------------------------------------------------------- window trimming

    def _hysteresis_trim(self, prompt: List[int], budget: int,
                         ps: int) -> List[int]:
        """Legacy sliding-window trim: drop the front in page-aligned
        hysteresis steps (~half the budget). Epochs last step/delta turns,
        so when the per-turn token delta approaches the step — exactly the
        short-S regime (S=128 serves ~1.6 turns total) — the anchor moves
        EVERY turn and the prefix cache goes dark (dpserve r5: 3.9% hit
        vs swarm100's 40%). Kept as the fallback for no-prefix engines
        and SWARMDB_ANCHOR_HEAD=0."""
        frac = _env_float("SWARMDB_TRIM_STEP", 0.5)
        frac = min(0.9, max(0.1, frac))
        step = max(ps, int(budget * frac) // ps * ps)
        drop = -(-(len(prompt) - budget) // step) * step
        if len(prompt) - drop >= 16:
            return prompt[drop:]
        return prompt[-budget:]

    def _trim_prompt(self, msg: Message, prompt: List[int],
                     budget: int) -> List[int]:
        """Sink-anchored two-segment window (the short-S prefix fix,
        VERDICT r5 #4): once a conversation overflows the token budget,
        its prompt becomes

            [HEAD: first page-aligned tokens, captured ONCE, immutable]
            + [fixed elision marker]
            + [TAIL: newest tokens, trimmed in page-aligned hysteresis
               steps]

        The head occupies positions 0..len(head) in EVERY subsequent turn,
        so its pages hit the prefix cache unconditionally — a hit-rate
        floor of head/prompt that survives any tail churn. This is what a
        pure sliding window cannot provide at short S: with per-turn
        deltas comparable to the whole budget, ANY recompute-from-length
        trim re-anchors every turn and invalidates every cached page
        (measured: S=128 dpserve at 3.9% hit). StreamingLLM's
        attention-sink observation applied at the PROMPT level: keep the
        conversation opening verbatim, elide the middle, keep the recent
        turns. The tail keeps the old hysteresis so mid-epoch turns also
        reuse tail pages at longer S (serve/swarm100).
        SWARMDB_ANCHOR_HEAD sets the head size in pages (default 4;
        0 restores the sliding trim)."""
        eng = self.engine
        if eng._prefix is None:
            # no prefix cache -> keep the maximum recent history
            return prompt[-budget:]
        ps = eng._prefix_ps
        head_pages = _env_int("SWARMDB_ANCHOR_HEAD", 4)
        # head must leave at least half the budget to the tail (the
        # recent turns are what the model answers from)
        hb = min(head_pages * ps, (budget // 2) // ps * ps)
        if head_pages <= 0 or msg.receiver_id is None or hb < ps:
            return self._hysteresis_trim(prompt, budget, ps)
        key = (msg.sender_id, msg.receiver_id)
        with self._anchor_lock:
            head = self._anchors.get(key)
            if head is None:
                head = prompt[:hb]
                while len(self._anchors) >= self._anchor_cap:
                    self._anchors.pop(next(iter(self._anchors)))
                self._anchors[key] = head
                self._mem.anchor(key, len(head))
                self.db.metrics.counters["window_heads_anchored"].inc()
            else:
                # LRU touch (size-capped dict, insertion order = LRU)
                self._anchors[key] = self._anchors.pop(key)
        tail_budget = budget - len(head) - len(self._anchor_sep)
        if tail_budget < max(ps, budget // 4):
            # budget shrank since capture (larger max_new_tokens this
            # turn): the split leaves no useful tail — slide this turn
            return self._hysteresis_trim(prompt, budget, ps)
        step = max(ps, (tail_budget // 2) // ps * ps)
        drop = -(-(len(prompt) - tail_budget) // step) * step
        tail = prompt[drop:] if 0 < len(prompt) - drop <= tail_budget \
            else prompt[-tail_budget:]
        self.db.metrics.counters["window_tail_trims"].inc()
        return list(head) + list(self._anchor_sep) + tail

    # ------------------------------------------------------------- serving

    def serve_message(
        self,
        msg: Message,
        on_token=None,
        on_done=None,
    ) -> str:
        """Submit one message for generation; reply is emitted on completion.
        Returns the engine request id."""
        t_serve = TRACER.span_begin()
        msg.stage_stamp("admitted")
        # rolling-KV bookkeeping reads the stream length BEFORE the
        # prompt-window fetch: a message landing between the two reads
        # then has index >= pre_count (rendered next turn; at worst
        # duplicated once if it also made this turn's window) instead of
        # being counted as rendered while absent from the prompt —
        # which would drop it from the conversation forever
        pre_count = (self.db.conversation_length(msg.sender_id,
                                                 msg.receiver_id)
                     if self._rolling is not None and msg.receiver_id
                     else 0)
        prompt = build_prompt(self.db, msg, self.tokenizer,
                              history_limit=_history_limit_for(
                                  self.engine.max_seq))
        if msg.receiver_id:
            # temperature ledger: one touch per served message, stamped
            # with the UNTRIMMED prompt length (what a cold resume would
            # re-prefill from the log)
            self._mem.touch((msg.sender_id, msg.receiver_id), len(prompt))
        sampling = sampling_from_message(msg)
        priority = int(msg.priority.value if hasattr(msg.priority, "value")
                       else msg.priority)

        g = msg.metadata.get("generation", {}) if isinstance(
            msg.metadata, dict) else {}
        want_logprobs = bool(g.get("logprobs"))
        # n parallel completions (OpenAI-style): alternatives occupy their
        # own engine slots but SHARE the prompt's KV through the prefix
        # cache, so extra completions cost ~decode only. Completion 0 is
        # the reply body (and the streamed one); 1..n-1 ride metadata.
        n = min(4, max(1, int(g.get("n", 1))))

        # rolling KV: chat and tool-call turns continue the
        # conversation's kept pages (prefill = new tokens only; the
        # current message renders via the same _current_lines in both
        # the fresh and resume builders). Excluded: fan-out (n>1 —
        # alternatives would fight over the pages) and stop sequences
        # (the truncated reply text would diverge from the model's KV
        # memory).
        rolling_key = resume = None
        rolling_mode = "plain"
        if (self._rolling is not None and msg.receiver_id and n == 1
                and not sampling.stop
                and msg.type in (MessageType.CHAT,
                                 MessageType.FUNCTION_CALL)):
            key = (msg.sender_id, msg.receiver_id)
            rolling_mode, resume, rtoks = self._rolling_plan(
                key, msg, sampling, pre_count)
            if rolling_mode != "plain":
                # "plain": a concurrent turn of this conversation owns
                # the registry claim — keep_pages here would let a later
                # on_pages overwrite leak its pages
                rolling_key = key
            if resume is not None:
                prompt = rtoks
            if rolling_key is not None:
                user_on_done = on_done

                def on_done(rid, toks, reason, _u=user_on_done,
                            _k=rolling_key, _m=msg):
                    # reply worker, AFTER _emit_reply: the reply id it
                    # stamped into msg.metadata is recorded for suffix
                    # exclusion
                    self._rolling_finalize(_k, _m, reason)
                    if _u is not None:
                        _u(rid, toks, reason)

        try:
            if resume is None:
                # Long-running conversations grow the prompt without bound;
                # keep the TAIL (most recent turns) so a pair's history can
                # never exceed the engine's window (engine.submit rejects
                # len >= max_seq outright). The front is dropped in
                # page-aligned HYSTERESIS steps (~half the budget), not
                # token-exactly: a trim that slides every turn gives
                # consecutive prompts no common prefix, so the prefix cache
                # could never hit on bounded windows (measured: 13% hit rate
                # with exact trimming vs ~anchored reuse).
                budget = max(16,
                             self.engine.max_seq - 1 - sampling.max_new_tokens)
                budget = min(budget, self.engine.max_seq - 1)
                if rolling_mode == "keep":
                    # rolling restart: leave HEADROOM or the very next turn
                    # overflows max_seq and the conversation restarts every
                    # turn instead of rolling (measured: restarts 3:1 over
                    # resumes with a full-budget restart). StreamingLLM-style
                    # half-window restart; anchor-stable trimming is moot —
                    # subsequent turns resume by identity, not hash match.
                    # The fixed fraction is additionally capped by an
                    # ADAPTIVE reserve of ~2.5 typical turn deltas: at
                    # small windows / large turns, half the window can sit
                    # within one delta of max_seq and lock the
                    # conversation into restarting every turn (measured:
                    # 12:1 restarts:resumes at S=256 with ~105-token
                    # deltas). The fraction stays the UPPER bound; a
                    # quarter-window floor keeps some history even when
                    # the measured deltas say the window fits barely one
                    # turn
                    frac = _env_float("SWARMDB_ROLL_RESTART", 0.5)
                    # EMA is written under _rolling_lock (_rolling_plan);
                    # read it under the same lock (swarmlint SWL303)
                    with self._rolling_lock:
                        delta_ema = self._rolling_delta_ema
                    reserve = (int(2.5 * delta_ema)
                               + self.engine.decode_chunk)
                    budget = max(16, min(
                        int(budget * min(0.9, max(0.1, frac))),
                        max(budget // 4, budget - reserve)))
                    if len(prompt) > budget:
                        prompt = prompt[-budget:]
                elif len(prompt) > budget:
                    prompt = self._trim_prompt(msg, prompt, budget)

            def _done(rid: str, tokens: List[int], reason: str) -> None:
                # engine thread: just hand off — emission runs on _reply_loop.
                # Logprobs travel IN the queue tuple (not via msg.metadata,
                # which a client could pre-populate — review finding)
                msg.stage_stamp("done")
                lps = (list(req.metadata.get("logprobs", []))
                       if want_logprobs else None)
                self._reply_queue.put((msg, rid, tokens, reason, sampling.stop,
                                       lps, None, on_done,
                                       time.monotonic_ns()))

            # stop-sequence watch (host-side): keep a bounded tail of decoded
            # text and CANCEL the engine request at the first match — the
            # remaining lane work is at most one chunk of discarded garbage.
            # Final truncation happens at reply emission regardless, so a
            # match straddling a chunk boundary still yields a clean reply.
            stop_tail: List[int] = []
            stop_chars = max((len(s) for s in sampling.stop), default=0)
            # window in TOKENS: a char is up to 4 UTF-8 bytes and the byte
            # tokenizer is one token per byte, so a char-sized window could
            # never match multi-byte stop strings (review finding)
            stop_window = 4 * stop_chars + 8
            stop_hit = False

            def _watch_stop(rid: str, token: int) -> None:
                nonlocal stop_hit
                if stop_hit:
                    return
                stop_tail.append(token)
                if len(stop_tail) > stop_window:
                    del stop_tail[0]
                text = self.tokenizer.decode(stop_tail)
                if any(s in text for s in sampling.stop):
                    stop_hit = True
                    self.engine.cancel(rid)

            def _tok(rid: str, token: int) -> None:
                if "first_token" not in msg.metadata.get("stages", {}):
                    msg.stage_stamp("first_token")
                    stages = msg.metadata["stages"]
                    if "enqueued" in stages:
                        ttft = stages["first_token"] - stages["enqueued"]
                        self.db.metrics.latencies["send_to_first_token_s"].observe(ttft)
                        # per-priority evidence that CRITICAL beats LOW under
                        # load (the engine's priority admission, bench swarm100)
                        self.db.metrics.latencies[
                            f"send_to_first_token_prio{priority}_s"].observe(ttft)
                        # per-tier TTFT (ISSUE 19): warm-hit vs
                        # cold-resume is THE number swarm1M reports
                        origin = (msg.metadata or {}).get("tier_origin")
                        if origin:
                            self.db.metrics.latencies[
                                f"tier_ttft_{origin}_s"].observe(ttft)
                if sampling.stop:
                    _watch_stop(rid, token)
                if on_token is not None:
                    on_token(rid, token)

            req = GenRequest(
                prompt=prompt, sampling=sampling, priority=priority,
                on_token=_tok, on_done=_done,
                metadata={"message_id": msg.id},
            )
            n_shards = (getattr(self.engine.paged.allocator, "n_shards", 1)
                        if self.engine.paged is not None else 1)
            if self._locality is not None and msg.receiver_id:
                # leadership-pinned locality (ISSUE 14): the lane pin
                # follows the conversation's partition LEADER, so log
                # ownership and serving compute coincide — and a
                # leadership move re-pins deterministically (ha.repin)
                lpin = self._locality.pin(msg.sender_id, msg.receiver_id)
                if n_shards > 1:
                    req.shard_hint = lpin.lane
            elif n_shards > 1:
                # DP-sharded pool: pin the conversation to one shard so
                # its prefix-cache pages (same-shard-only reuse) stay
                # hittable across turns — the order-insensitive pair key
                # matches get_conversation's identity
                pair = "|".join(sorted((msg.sender_id,
                                        msg.receiver_id or "")))
                req.shard_hint = stable_partition(pair, n_shards)
            if rolling_key is not None:
                req.keep_pages = True
                req.on_pages = (lambda rid, pages, written, tail,
                                _k=rolling_key:
                                self._rolling_store(_k, pages, written, tail))
                if resume is not None:
                    req.resume_pages = list(resume[0])
                    req.resume_len = resume[1]
                    req.resume_epoch = resume[2]
                    # warm-tier promotion payload (ISSUE 19): the host
                    # bytes admission bulk-inserts into the reserved
                    # pages before the resume prefill reads them
                    req.promote_payload = resume[3]
            t_submit = TRACER.span_begin()
            if n > 1:
                rid = self._serve_n(msg, req, prompt, sampling, priority, n,
                                    want_logprobs, on_done)
            else:
                rid = self._submit(req)
            # the span covers prompt build + trim + submit; args link the
            # message id to the ENGINE request id so one export joins the
            # runtime/broker spans (rid = msg.id) to the engine spans
            # (rid = engine request id), and split the span at the submit:
            # the history read before it, the engine's lock after
            if t_serve:
                TRACER.span_end(
                    t_serve, "serve.request", cat="serving", rid=msg.id,
                    args={"engine_rid": rid, "prompt_tokens": len(prompt),
                          "build_us": (t_submit - t_serve) // 1000,
                          "submit_us": (time.monotonic_ns() - t_submit)
                          // 1000})
            return rid
        except Exception:
            # the in-flight claim taken by _rolling_plan must not leak on
            # ANY failure between the plan and the submit (ADVICE r4 low
            # #3: trim arithmetic, GenRequest construction, closure setup)
            # or the conversation never rolls again and its resumed pages
            # stay referenced by nothing
            if rolling_key is not None:
                self._rolling_finalize(rolling_key, msg, "submit_error")
            raise

    def _serve_n(self, msg: Message, req0: GenRequest, prompt: List[int],
                 sampling: SamplingParams, priority: int, n: int,
                 want_logprobs: bool, on_done) -> str:
        """Fan ``n`` completions over engine slots; emit ONE reply whose
        body is completion 0 and whose metadata carries the alternatives.
        Distinctness: alternatives get derived seeds (seed+i when the
        request is seeded, else drawn fresh) — without them two
        completions landing on the same slot would replay identical PRNG
        folds and collapse into copies. Greedy (temperature=0) duplicates
        by definition; allowed, documented."""
        base_seed = sampling.seed
        if base_seed is None and sampling.temperature > 0:
            base_seed = int.from_bytes(os.urandom(8), "little")
        results: Dict[int, Tuple[List[int], str, Optional[List[float]]]] = {}
        lock = make_lock("backend.service.ServingService._serve_n.lock")

        def mk_done(idx: int, reqs: List[GenRequest]):
            def _done_i(rid: str, tokens: List[int], reason: str) -> None:
                lps = (list(reqs[idx].metadata.get("logprobs", []))
                       if want_logprobs else None)
                with lock:
                    results[idx] = (tokens, reason, lps)
                    if len(results) < n:
                        return
                # last completion: emit the aggregate
                self._fanout.pop(reqs[0].request_id, None)
                msg.stage_stamp("done")
                toks0, reason0, lps0 = results[0]
                alts = [results[i] for i in range(1, n)]
                self._reply_queue.put(
                    (msg, reqs[0].request_id, toks0, reason0, sampling.stop,
                     lps0, alts, on_done, time.monotonic_ns()))
            return _done_i

        reqs: List[GenRequest] = []
        for i in range(n):
            sp = dataclasses.replace(
                sampling, seed=None if base_seed is None else base_seed + i)
            # EVERY completion watches its own stop match (each alternative
            # stops independently); completion 0 also keeps the original
            # token/TTFT callback — it is the streamed one
            watch = self._make_stop_watch(sp)
            prev = req0.on_token if i == 0 else None

            def on_tok(rid, token, watch=watch, prev=prev):
                if watch is not None:
                    watch(rid, token)
                if prev is not None:
                    prev(rid, token)

            reqs.append(GenRequest(
                prompt=list(prompt), sampling=sp, priority=priority,
                on_token=on_tok, metadata=dict(req0.metadata, alt=i),
            ))
        for i, r in enumerate(reqs):
            r.on_done = mk_done(i, reqs)
        # cancel_request(rid0) must reach every member (client disconnects
        # would otherwise leave n-1 slots decoding to max_new_tokens)
        self._fanout[reqs[0].request_id] = [r.request_id for r in reqs]
        submitted = []
        try:
            for r in reqs:
                self._submit(r)
                submitted.append(r)
        except Exception:
            # a later member failed to submit: without the full group the
            # aggregate (len(results) == n) would never emit — cancel the
            # submitted members and surface the error to the caller
            self._fanout.pop(reqs[0].request_id, None)
            for r in submitted:
                self.engine.cancel(r.request_id)
            raise
        return reqs[0].request_id

    def _make_stop_watch(self, sampling: SamplingParams):
        """Host-side stop-sequence watcher bound to one engine request
        (see serve_message's inline twin); None when no stop configured."""
        if not sampling.stop:
            return None
        tail: List[int] = []
        window = 4 * max(len(s) for s in sampling.stop) + 8
        hit = [False]

        def _watch(rid: str, token: int) -> None:
            if hit[0]:
                return
            tail.append(token)
            if len(tail) > window:
                del tail[0]
            text = self.tokenizer.decode(tail)
            if any(s in text for s in sampling.stop):
                hit[0] = True
                self.engine.cancel(rid)

        return _watch

    def _submit(self, req: GenRequest) -> str:
        """One submission seam: through the supervisor when attached
        (adoption + health-aware routing), straight to the engine
        otherwise."""
        if self.supervisor is not None:
            return self.supervisor.submit(req)
        return self.engine.submit(req)

    def cancel_request(self, rid: str) -> None:
        """Cancel a serve_message request INCLUDING any n>1 fan-out
        members (engine.cancel alone only reaches completion 0). The
        supervisor is consulted first: a request parked on a retry
        timer lives in no engine's queue."""
        for r in self._fanout.pop(rid, [rid]):
            if self.supervisor is not None and self.supervisor.cancel(r):
                continue
            self.engine.cancel(r)

    def _reply_loop(self) -> None:
        """Drain completed generations into reply messages (worker thread).

        Retryable produce failures (``LeaderChangedError`` from a
        partition-routed broker mid-failover) get the PR 8 retry
        treatment: bounded attempts (``SWARMDB_REPLY_RETRIES``) with
        jittered exponential backoff off ``SWARMDB_RETRY_BACKOFF_S`` —
        the failover re-seats the partition within the detector budget,
        so the generated reply lands on the new leader instead of being
        stranded as a FAILED message awaiting an admin resend."""
        retries = _env_int("SWARMDB_REPLY_RETRIES", 3)
        backoff = _env_float("SWARMDB_RETRY_BACKOFF_S", 0.05)
        while True:
            item = self._reply_queue.get()
            if item is None:
                return
            msg, rid, tokens, reason, stop, lps, alts, on_done, t_done = item
            t_reply = TRACER.span_begin()
            decode_us = send_us = 0
            for attempt in range(retries + 1):
                try:
                    decode_us, send_us = self._emit_reply(
                        msg, tokens, reason, stop, lps, alts)
                    break
                except Exception as exc:
                    if (getattr(exc, "retryable", False)
                            and attempt < retries
                            and not self._stop.is_set()):
                        self.db.metrics.counters["reply_retries"].inc()
                        time.sleep(backoff * (2 ** attempt)
                                   * (1.0 + random.random()))
                        continue
                    logger.exception("failed to emit reply for %s", msg.id)
                    break
            # one a message whatever n: the wait in _reply_queue since the
            # engine's on_done, then the emit (its last attempt's parts)
            TRACER.span_end(
                t_reply, "serve.reply", cat="serving", rid=msg.id,
                args={"queued_us": (t_reply - t_done) // 1000,
                      "decode_us": decode_us, "send_us": send_us,
                      "tokens": len(tokens), "attempts": attempt + 1})
            if on_done is not None:
                try:
                    on_done(rid, tokens, reason)
                except Exception:
                    logger.exception("on_done callback failed for %s", msg.id)

    def _finish_completion(self, tokens: List[int], reason: str,
                           stop: tuple,
                           logprobs: Optional[List[float]]
                           ) -> Tuple[str, str, Optional[List[float]]]:
        """Decode + stop-truncate one completion (text, reason, logprobs
        kept parallel to the VISIBLE text)."""
        text = self.tokenizer.decode(tokens)
        if stop:
            # truncate at the FIRST occurrence of any stop string (the
            # engine cancel lags by up to a chunk of extra tokens)
            cut = min((i for i in (text.find(s) for s in stop) if i >= 0),
                      default=-1)
            if cut >= 0:
                text = text[:cut]
                reason = "stop"
                if logprobs is not None:
                    # largest token prefix whose decode fits text[:cut]
                    n = 0
                    while (n < len(tokens)
                           and len(self.tokenizer.decode(tokens[:n + 1]))
                           <= cut):
                        n += 1
                    logprobs = logprobs[:n]
        return text, reason, logprobs

    def _emit_reply(self, msg: Message, tokens: List[int], reason: str,
                    stop: tuple = (), logprobs: Optional[List[float]] = None,
                    alts: Optional[List[Tuple]] = None) -> Tuple[int, int]:
        """Send the reply and mark ``msg`` processed. Returns the
        microseconds the completions' decode took and those
        ``db.send_message`` took, for ``serve.reply``."""
        t_decode = time.monotonic_ns()
        text, reason, logprobs = self._finish_completion(
            tokens, reason, stop, logprobs)
        reply_type = (
            MessageType.FUNCTION_RESULT
            if msg.type == MessageType.FUNCTION_CALL
            else MessageType.CHAT
        )
        reply_meta = {
            "reply_to": msg.id,
            "backend_id": self.backend_id,
            "finish_reason": reason,
            "completion_tokens": len(tokens),
        }
        if logprobs is not None:
            reply_meta["logprobs"] = [round(x, 6) for x in logprobs]
        if alts:
            rendered = []
            for toks_i, reason_i, lps_i in alts:
                text_i, reason_i, lps_i = self._finish_completion(
                    toks_i, reason_i, stop, lps_i)
                entry = {"text": text_i, "finish_reason": reason_i,
                         "completion_tokens": len(toks_i)}
                if lps_i is not None:
                    entry["logprobs"] = [round(x, 6) for x in lps_i]
                rendered.append(entry)
            reply_meta["alternatives"] = rendered
        t_send = time.monotonic_ns()
        reply_id = self.db.send_message(
            msg.receiver_id or self.backend_id,
            msg.sender_id,
            text,
            message_type=reply_type,
            priority=msg.priority,
            metadata=reply_meta,
        )
        t_sent = time.monotonic_ns()
        msg.metadata["reply_id"] = reply_id
        self.db.mark_message_as_processed(msg.id)
        # north-star gauge: completed chat messages/sec
        self.db.metrics.rates["completed_messages"].mark()
        self.db.metrics.counters["completed_messages"].inc()
        return (t_send - t_decode) // 1000, (t_sent - t_send) // 1000

    async def stream_reply(self, msg: Message) -> AsyncIterator[str]:
        """Async token-text stream for SSE (api/app.py). Bridges engine-
        thread callbacks into this loop's queue."""
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()

        def _post(item) -> None:
            # the client's event loop closes on disconnect while in-flight
            # engine callbacks still land here; the cancel is already on
            # its way, so a closed loop is expected — not traceback spam
            try:
                loop.call_soon_threadsafe(q.put_nowait, item)
            except RuntimeError:
                pass

        def on_token(rid: str, token: int) -> None:
            _post(("token", token))

        def on_done(rid: str, tokens: List[int], reason: str) -> None:
            _post(("done", reason))

        stop = sampling_from_message(msg).stop
        held = ""  # seen but not yet released (possible stop-match prefix)

        def _guard(piece: str, flush: bool = False) -> Tuple[str, bool]:
            """Release text so the STREAM never shows a stop string (the
            engine cancel lags by up to a chunk — without this the stream
            and the stored reply would disagree). Any released suffix that
            could still begin a stop match is HELD BACK until disproven —
            a match straddling two pieces must never leak its first half
            (review finding). Returns (text to yield, matched)."""
            nonlocal held
            if not stop:
                return piece, False
            buf = held + piece
            cut = min((i for i in (buf.find(s) for s in stop) if i >= 0),
                      default=-1)
            if cut >= 0:
                held = ""
                return buf[:cut], True
            if flush:
                held = ""
                return buf, False
            # longest suffix of buf that is a proper prefix of any stop
            hold = 0
            for s in stop:
                for n in range(min(len(s) - 1, len(buf)), hold, -1):
                    if buf.endswith(s[:n]):
                        hold = n
                        break
            held = buf[len(buf) - hold:] if hold else ""
            return buf[:len(buf) - hold], False

        rid = self.serve_message(msg, on_token=on_token, on_done=on_done)
        pending: List[int] = []
        try:
            while True:
                kind, value = await q.get()
                if kind == "token":
                    pending.append(value)
                    # decode greedily; UTF-8 continuation bytes may be
                    # incomplete, so flush only when decode round-trips
                    text = self.tokenizer.decode(pending)
                    if text and not text.endswith("�"):
                        out, matched = _guard(text)
                        if out:
                            yield out
                        if matched:
                            return
                        pending = []
                else:
                    tail = self.tokenizer.decode(pending) if pending else ""
                    out, _ = _guard(tail, flush=True)
                    if out:
                        yield out
                    return
        finally:
            # client disconnect closes this generator mid-stream: stop the
            # generation (and any n>1 fan-out members) instead of burning
            # slots to max_new_tokens (no-op if already finished)
            self.cancel_request(rid)

    async def stream_group(self, msgs: List[Message]) -> AsyncIterator[Dict[str, Any]]:
        """Fan-out streaming: serve every group message concurrently (they
        occupy distinct engine slots => one data-parallel decode batch) and
        interleave token events tagged by message id."""
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()
        remaining = 0
        rids: List[str] = []

        try:
            # submit INSIDE the try: if a later member's submit raises,
            # the finally still cancels the already-running ones (review
            # finding — otherwise they'd decode to max_new_tokens with no
            # consumer)
            for msg in msgs:
                if msg is None:
                    continue
                remaining += 1
                stop = sampling_from_message(msg).stop

                def _post(item) -> None:
                    try:
                        loop.call_soon_threadsafe(q.put_nowait, item)
                    except RuntimeError:
                        pass  # loop closed on disconnect; cancel in flight

                def mk(msg_id: str, stop: tuple):
                    def on_token(rid: str, token: int) -> None:
                        _post({"event": "token", "message_id": msg_id,
                               "token": token})

                    def on_done(rid: str, tokens: List[int],
                                reason: str) -> None:
                        # mirror _emit_reply's stop truncation so the
                        # stream's final text and the stored reply agree
                        text = self.tokenizer.decode(tokens)
                        if stop:
                            cut = min((i for i in (text.find(s)
                                                   for s in stop)
                                       if i >= 0), default=-1)
                            if cut >= 0:
                                text = text[:cut]
                                reason = "stop"
                        _post({"event": "reply_done",
                               "message_id": msg_id,
                               "finish_reason": reason, "text": text})

                    return on_token, on_done

                on_token, on_done = mk(msg.id, stop)
                rids.append(self.serve_message(msg, on_token=on_token,
                                               on_done=on_done))

            while remaining > 0:
                item = await q.get()
                if item.get("event") == "reply_done":
                    remaining -= 1
                yield item
        finally:
            for rid in rids:  # client disconnect: stop all fan-out members
                self.cancel_request(rid)

    # --------------------------------------------------------------- health

    def health(self) -> Dict[str, Any]:
        """Device liveness probe (SURVEY §5.3): run a tiny jitted op and
        report engine state."""
        try:
            t0 = time.time()
            probe = _HEALTH_PROBE(jnp.ones((8, 8)))
            val = probe.block_until_ready()
            device_ok = bool(val == 128.0)
            probe_ms = (time.time() - t0) * 1000
            # device identity from the probe array itself: the device
            # that answered is the one reported
            device = str(next(iter(probe.devices())))
        except Exception as exc:
            return {"status": "unhealthy", "error": str(exc)}
        return {
            "status": "healthy" if device_ok else "degraded",
            "device": device,
            "probe_ms": round(probe_ms, 3),
            "backend_id": self.backend_id,
            "engine": self.engine.stats(),
            "tier": (self._tier.status() if self._tier is not None
                     else {"enabled": False}),
            # swarmfleet (ISSUE 20): pool map + handoff counters, flag-
            # independent like "tier" — {"enabled": false} when colocated
            "fleet": (dict(enabled=True, **fleet.stats())
                      if (fleet := getattr(self.engine, "fleet", None))
                      is not None else {"enabled": False}),
        }
