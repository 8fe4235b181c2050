"""Continuous-batching generation engine.

The TPU serving core the north star demands (SURVEY §7 step 4): a
fixed-shape decode loop under ``jax.jit`` with slot management —

- ``max_batch`` slots; each slot holds one in-flight sequence with its own
  absolute position, sampling params, and PRNG stream.
- ONE compiled decode step serves every population of slots: inactive slots
  run masked garbage that is ignored host-side (shapes never change, so XLA
  never recompiles).
- Decode runs in CHUNKS of ``decode_chunk`` steps under one ``lax.scan``
  per host round-trip: the sampled token feeds the next step entirely
  on-device, and the host fetches a [K+1, B] token block with ONE sync.
  This amortizes host<->device latency: a synchronous fetch per token
  would cap the whole engine at the host's round-trip rate regardless of
  batch. Slots that finish (EOS /
  max_new_tokens) mid-chunk compute garbage for the remainder; the host
  discards it. Their KV lanes are fully overwritten at next admission, so
  the garbage is never read.
- Prefill runs per-sequence at bucketed lengths (powers of two) to bound
  the number of compiled variants, then the prefix cache is inserted into
  the slot's rows of the batch KV cache. Single-shard PAGED engines
  instead pack each admission round into ragged token streams with no
  row or length buckets (``_prefill_ragged_waves``: per-row (start, len,
  prefix_len) descriptors, prefix KV read in place from the page pool,
  widths off a power-of-two ladder, chosen as the cheapest cover of the
  round where a wave under the chip's ridge costs its pass over the
  weights: one padded wave where that beats a second pass —
  ``SWARMDB_RAGGED_PREFILL=0`` restores the bucketed waves). Prefill
  never syncs: its sampled first token is scattered into the on-device
  ``last_tokens`` vector and reaches the host as row 0 of the next
  chunk's token block.
- Admission is priority-ordered (MessagePriority: CRITICAL first — the
  reference stores priorities but never uses them, SURVEY §2.2).
- Tokens stream to per-request callbacks as they are sampled; the HTTP
  layer bridges these to SSE (asyncio) queues.

The engine is model-agnostic: it takes a ``forward(params, tokens,
positions, cache)`` callable (Llama or Mixtral) plus cache constructors.
"""

from __future__ import annotations

import concurrent.futures
import functools
import heapq
import itertools
import logging
import os
import queue
import threading
import time
import uuid
import zlib
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from ..models.lfm2 import seed_state
from ..models.mixtral import routing_dropped, routing_experts
from ..models.nemotron_h import wave_segments
from ..obs import TRACER, FlightRecorder
from ..obs.metrics import (HIST_DECODE_CHUNK, HIST_QUEUE_WAIT, HIST_TTFT)
from ..obs.profiler import (NullLane, platform_peaks,
                            profiler as kernel_profiler)
from ..utils.metrics import MetricsRegistry
from ..utils.sync import make_condition
from .sampling import (SamplingParams, make_slot_keys,
                       sample_tokens, token_logprob)

logger = logging.getLogger("swarmdb_tpu.engine")

#: Finish reasons a client (or the lane supervisor) may transparently
#: retry: the request itself was fine — the ENGINE lost it (loop death,
#: lane quarantine, transient dispatch failure) or deliberately returned
#: it (pool-pressure shedding, a stale rolling-resume epoch). Mirrors the
#: ``BrokerError.retryable`` contract from the HA control plane: the
#: failure names itself retryable instead of every caller keeping a
#: private list. Non-retryable reasons ("eos", "length", "cancelled",
#: "deadline") are final.
RETRYABLE_REASONS = frozenset({
    "engine_error", "engine_restart", "lane_quarantined", "shed",
    "stale_resume",
})


def is_retryable_reason(reason: str) -> bool:
    """True when a finish reason is safe to requeue (see
    :data:`RETRYABLE_REASONS`)."""
    return reason in RETRYABLE_REASONS


def weights_ridge_tokens(params) -> float:
    """Tokens a prefill wave carries before its matmuls cost more than
    the pass over its weights: a weight element is ``itemsize`` bytes
    read and 2 FLOPs a token, so the chip's ridge (peak FLOP/s over peak
    bytes/s, ``obs/profiler.platform_peaks`` of the device that holds
    the weights) times ``itemsize / 2``. 240 for bf16 on a v5e, 2.5-5 on
    the CPU row. Read from the largest parameter: it is a matmul's."""
    big = max(jax.tree_util.tree_leaves(params), key=lambda a: a.size)
    dev = next(iter(big.devices()))
    peaks = platform_peaks(dev.platform, dev.device_kind)
    return peaks["ridge_flops_per_byte"] * big.dtype.itemsize / 2


def plan_ragged_waves(n: int, ladder: Sequence[int],
                      ridge_tokens: float) -> List[int]:
    """Cheapest cover of ``n`` pending prefill tokens by ladder rungs,
    in dispatch order. A wave of width ``w`` is priced
    ``max(w, ridge_tokens)``: under the ridge it costs its pass over the
    weights whatever it holds, over it its tokens. At each step *round
    up to the smallest rung >= n* is compared with *the largest rung
    <= n, then the plan of the rest*; a tie goes to the single wave.
    With the ridge under the smallest rung a wave costs its width and
    the plan pads no more than largest-fit does; with the ridge at 240
    a round of 170 is one wave of 256 where largest-fit made four."""
    up = next((w for w in ladder if w >= n), None)
    down = next((w for w in reversed(ladder) if w <= n), None)
    if down is None or down == up:
        return [up]
    rest = plan_ragged_waves(n - down, ladder, ridge_tokens)
    if up is not None and max(up, ridge_tokens) <= sum(
            max(w, ridge_tokens) for w in (down, *rest)):
        return [up]
    return [down, *rest]


# ---- swarmprof variant naming (obs/profiler.py, ISSUE 15) ----------------
# One compiled program = one profiler key. The decode/resident families
# have a single shape each; prefill families key on the shapes that pick
# the compiled variant (rows x token bucket, + the prefix-gather width
# where it is a compile axis). The SAME helper names warmup-harvest
# entries and runtime dispatches, so cost-model facts and device-time
# accounting join by construction.

PROF_DECODE_KEYS = ("decode.full", "decode.fast", "decode.greedy")
PROF_RESIDENT_KEYS = ("resident.full", "resident.fast", "resident.greedy")
# The same variants as the jitted programs are called in a device trace
# (``jit_<name>``) and as the ``jax.named_scope`` round the model forward
# inside each: every name holds "decode" and none holds "prefill", which
# is how the benchmark's readers tell the two families apart.
DECODE_PROGRAM_NAMES = ("decode_scan_full", "decode_scan_fast",
                        "decode_scan_greedy")
RESIDENT_PROGRAM_NAMES = ("decode_resident_full", "decode_resident_fast",
                          "decode_resident_greedy")
# (use_filters, assume_greedy) per variant, in the order of the names
_DECODE_VARIANT_FLAGS = ((True, False), (False, False), (False, True))


# how long either half of a resident session waits for the other before
# it looks for itself. The engine thread, on the FIFO, then asks whether
# the device program is still running: the normal end of a session is the
# block the callback marked last, so this only bounds how late a failed
# program (or a wrong mirror of its ``cond``) is noticed. The callback, on
# the session's credit, then votes without it: a consumer that is gone
# cannot hang the device program.
_RESIDENT_POLL_S = 0.25


def _pack_resident_block(all_toks, all_lps, n, done, routing=None):
    """What a resident chunk sends to the host, as ONE int32 buffer: on
    the chip every operand of a callback is a transfer of its own, and an
    operand's trip is a latency, not its bytes (some tens of KB here).
    Layout, flat: tokens ``[K+1, B]``, logprobs ``[K+1, B]`` (their
    float32 bits), the chunk's index, the loop's ``done`` row ``[B]``
    and, where the configuration routes, the chunk's routing ``[K, B,
    L_routed, k]`` int16, two to a word (padded by one where the count is
    odd). ``_unpack_resident_block`` is its inverse on the host."""
    parts = [all_toks.reshape(-1),
             jax.lax.bitcast_convert_type(all_lps, jnp.int32).reshape(-1),
             n[None], done.astype(jnp.int32)]
    if routing is not None:
        flat = routing.reshape(-1)
        flat = jnp.pad(flat, (0, flat.size % 2))
        parts.append(jax.lax.bitcast_convert_type(
            flat.reshape(-1, 2), jnp.int32))
    return jnp.concatenate(parts)


def _unpack_resident_block(buf: np.ndarray, k1: int, b: int,
                           routed: Optional[Tuple[int, int, int]]):
    """Views of one packed block (``_pack_resident_block``), no copy:
    tokens, logprobs, the chunk's index, ``done`` and the routing (None
    for a dense configuration, whose buffer has no such part)."""
    o = k1 * b
    block = buf[:o].reshape(k1, b)
    lps = buf[o:2 * o].view(np.float32).reshape(k1, b)
    n = int(buf[2 * o])
    done = buf[2 * o + 1:2 * o + 1 + b] != 0
    routing = None
    if routed is not None:
        l_routed, k, _e = routed
        rows = (k1 - 1) * b * l_routed * k
        routing = buf[2 * o + 1 + b:].view(np.int16)[:rows].reshape(
            k1 - 1, b, l_routed, k)
    return block, lps, n, done, routing


class _ResidentBlock(NamedTuple):
    """One chunk on its way from the callback to the engine thread."""
    block: np.ndarray            # [K+1, B] tokens
    lps: np.ndarray              # [K+1, B] logprobs
    routing: Optional[np.ndarray]  # [K, B, L_routed, k] or None
    n: int                       # the chunk's index in its session
    stamp_ns: int                # monotonic, when the callback was entered
    vote: bool                   # what the callback answered
    queued: int                  # the queue's length the vote saw
    last: bool                   # the device loop ends after this chunk


class _ResidentSession:
    """What one resident session's votes are taken from: the snapshot and,
    per lane ``[B]``, what the host knew when it was built."""
    __slots__ = ("snap", "pos0", "left", "first", "alive", "max_chunks",
                 "prev_ns", "failed", "credit", "consuming")

    def __init__(self, snap, pos0, left, first, alive, max_chunks):
        self.snap = snap            # [(slot, request, start position)]
        self.pos0 = pos0            # start positions
        self.left = left            # tokens each request may still emit
        self.first = first          # 1 where the prefill sample is pending
        self.alive = alive          # lanes the votes so far expect live
        self.max_chunks = int(max_chunks)
        self.prev_ns = time.monotonic_ns()  # the chunk boundary before
        self.failed = False         # a block's processing raised
        # one block in flight between the halves: the callback takes the
        # credit before it votes, the engine thread gives it back when a
        # block is processed, so the device runs ONE chunk ahead of the
        # emission and no further (where a chunk outlasts its block's
        # processing, as on the chip, nobody ever waits here)
        self.credit = threading.Semaphore(1)
        # the engine thread is back from the dispatch and takes blocks
        # off the FIFO; until then (for good, on a backend that runs a
        # program with a host callback on the calling thread, as the
        # CPU's does) the callback has nobody to hand a block to
        self.consuming = False


# on the resident FIFO, between a session's blocks: a request was queued
# while the engine thread waits there, and its plan can be made now
_PLAN_WAKE = object()


class _AdmissionPlan:
    """What admission has decided for the requests it took off the queue,
    before anything is packed: each request's heap entry (a requeue puts
    it back as it was), its slot and page-table row (paged; ``rows`` is
    parallel to ``popped``), its prefix hits and their routing, and the
    slots whose rows the device's table already holds. One round makes
    one; the part of it made while a resident session still ran is held
    on ``Engine._held_plan`` until the boundary's round takes it up."""
    __slots__ = ("entries", "popped", "rows", "sent", "plans",
                 "hit_routing", "resume_rows", "free")

    def __init__(self) -> None:
        self.entries: List[Tuple] = []
        self.popped: List["GenRequest"] = []
        self.rows: List[Tuple[int, np.ndarray]] = []
        self.sent: set = set()
        self.plans: Dict[int, Tuple] = {}        # slot -> (hits, chains)
        self.hit_routing: Dict[int, List[Any]] = {}
        self.resume_rows: Dict[int, np.ndarray] = {}
        self.free: List[int] = []    # dense: paired with popped by position


class _Retired:
    """A retirement as ``_settle_retire`` left it for ``_deliver_retired``:
    what the occupant's last callbacks and its records need, taken off the
    slot, which admission may have filled again by then."""
    __slots__ = ("req", "reason", "generated", "logprobs", "admitted_at",
                 "first_token_at", "host_syncs", "routing",
                 "routing_complete", "cached_parts")


class _SlotEmit:
    """What one slot takes of one block (``_settle_block``), for
    ``_deliver_block``: the tokens to stream in order, whether the first
    of them is its request's first (with what ``engine.first_token``
    says of the admission), and its retirement if the block ends it."""
    __slots__ = ("slot_id", "req", "tokens", "first", "retired")

    def __init__(self, slot_id: int, req: "GenRequest") -> None:
        self.slot_id = slot_id
        self.req = req
        self.tokens: List[int] = []
        self.first: Optional[Tuple] = None
        self.retired: Optional[_Retired] = None


class _SettledBlock:
    """One decode block after ``_settle_block``: the slots' state is the
    block's, nothing of it has been told to anybody yet."""
    __slots__ = ("snapshot", "emits", "live_rows", "n_live",
                 "pages_reserved", "pages_written", "t_dispatch_ns",
                 "t_begin_ns", "chunk", "stamp_ns", "settle_us")


def _named_partial(fn: Callable, name: str, **kwargs) -> Callable:
    """``functools.partial(fn, **kwargs)`` with a ``__name__``: jax names
    a jitted partial's program ``jit__unknown``, which a trace cannot
    tell from any other."""
    part = functools.partial(fn, scope=name, **kwargs)
    part.__name__ = part.__qualname__ = name
    return part


def prof_key(family: str, tok_shape, ppb: Optional[int] = None) -> str:
    """Profiler variant key for a prefill family + its shape axes."""
    if len(tok_shape) == 1:
        return f"{family}[w{tok_shape[0]}]"
    r, b = tok_shape
    if ppb is None:
        return f"{family}[r{r}xb{b}]"
    return f"{family}[r{r}xb{b}xp{ppb}]"


@dataclass
class GenRequest:
    prompt: List[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    priority: int = 1
    request_id: str = field(default_factory=lambda: str(uuid.uuid4()))
    # on_token(request_id, token_id) fires per sampled token (engine thread!)
    on_token: Optional[Callable[[str, int], None]] = None
    # on_done(request_id, token_ids, finish_reason)
    on_done: Optional[Callable[[str, List[int], str], None]] = None
    submitted_at: float = field(default_factory=time.time)
    metadata: Dict[str, Any] = field(default_factory=dict)
    # ---- rolling-KV conversation continuation (paged engines only) ----
    # resume_pages: page ids already holding this conversation's KV (the
    # CALLER keeps custody — the engine only references them; see
    # ServingService's rolling registry). resume_len: tokens already in
    # those pages; ``prompt`` then carries ONLY the new suffix tokens and
    # decode continues at resume_len + len(prompt).
    resume_pages: Optional[List[int]] = None
    resume_len: int = 0
    # keep_pages: at retirement, transfer the slot's fresh pages out of
    # engine custody and fire on_pages(request_id, pages, written_len,
    # tail_tokens) instead of freeing — the caller may resume from them
    # next turn. tail_tokens are emitted tokens whose K/V is not yet
    # written (host-confirmed extent is chunk-granular); prepend them to
    # the next resume's prompt.
    keep_pages: bool = False
    on_pages: Optional[Callable[[str, List[int], int, List[int]],
                                None]] = None
    # resume_epoch: the allocator pool generation the resume_pages were
    # handed out in (Engine.pool_epoch() at plan time). submit() AND
    # admission re-validate it: a pool reset between plan and admission
    # reclaims every page, so resuming stale ids would alias another
    # slot's pages — cross-conversation KV corruption (ADVICE r4 #2).
    resume_epoch: Optional[int] = None
    # promote_payload: warm-tier promotion (ISSUE 19) — the host-RAM
    # raw page payload ((k, v) pool_gather_pages outputs) that must be
    # bulk-inserted into resume_pages BEFORE the resume prefill reads
    # them. resume_pages were freshly RESERVED by the tier manager;
    # admission performs the H2D insert on the engine thread (the pools
    # are donated by engine jits — no other thread may touch them) and
    # clears this field. None for ordinary (hot) resumes.
    promote_payload: Optional[Any] = None
    # shard_hint: DP-sharded paged pools only — admission prefers a free
    # slot on this shard (mod n_shards). Prefix-cache pages are only
    # usable by same-shard slots, so routing a conversation's turns to
    # one shard keeps its cached prefix hittable; without the hint the
    # load-spreading rotation would scatter turns (and their
    # registrations) across shards. Advisory: any free slot still admits.
    shard_hint: Optional[int] = None
    # ---- fault-tolerant serving (ISSUE 9) -----------------------------
    # deadline: absolute wall-clock time past which this request must not
    # be served. The engine fails expired QUEUED requests with reason
    # "deadline" during admission (never a half-served stream); the lane
    # supervisor enforces it end to end and refuses retries that cannot
    # fit before it. None = no deadline.
    deadline: Optional[float] = None
    # retries_left: how many times a RETRYABLE failure (see
    # RETRYABLE_REASONS) may transparently requeue this request before
    # the failure surfaces. Consumed by the supervisor, not the engine.
    retries_left: int = 0
    # ---- routing record (a configuration that routes; else None) ------
    # routing: written by the engine before on_done fires, one row for
    # every position of prompt + generated whose hidden state went
    # through the stack and whose output was read (all but the last
    # generated token; the token that sampled eos has a row too):
    # [positions, L_routed, k] int16 in models.mixtral.encode_routing's
    # format — the expert, or ~expert where the choice was dropped.
    # Cached prefix positions hold the rows their pages were registered
    # with. routing_complete: every position has its row. False where
    # the context came by a path that does not carry routing — a rolling
    # resume (resume_pages: tiering promotions and fleet handoffs too) —
    # and the rows are then those of the positions this request computed
    # — or where the record holds fewer rows than positions, whatever the
    # path (counted: routing_incomplete_requests).
    routing: Optional[np.ndarray] = None
    routing_complete: bool = False


class WaveRouting:
    """The routing one prefill dispatch computed ([rows, T, L_routed, k],
    or [W, L_routed, k] for a packed stream), on its way to the host.
    Prefill never syncs, so what a slot's record and a registered page
    keep at dispatch is a ``part`` of the wave; the first ``get`` of any
    part lands the wave (its copy was started at dispatch, and the decode
    that sampled a token from it has long run) and hands every part its
    own rows, so the wave's array is not kept alive by one cached page."""

    def __init__(self, dev) -> None:
        self._dev = dev
        self._parts: List["RoutingRows"] = []
        self._lock = threading.Lock()
        dev.copy_to_host_async()

    def part(self, index) -> "RoutingRows":
        rows = RoutingRows(self, index)
        self._parts.append(rows)
        return rows

    def land(self) -> None:
        # retirements land it, on the engine thread (the resident
        # session's consumer, a cancellation, the scan path)
        with self._lock:
            if self._dev is None:
                return
            host = np.asarray(self._dev)
            for rows in self._parts:
                rows.rows = np.array(host[rows.index])
            self._dev, self._parts = None, []


class RoutingRows:
    """``[n, L_routed, k]`` rows of a ``WaveRouting``: what a slot's
    record and a registered page (``PrefixLRU`` keeps it as it is given)
    hold until a retirement reads them (``Engine._finish_routing``)."""

    __slots__ = ("wave", "index", "rows")

    def __init__(self, wave: WaveRouting, index) -> None:
        self.wave, self.index, self.rows = wave, index, None

    def get(self) -> np.ndarray:
        wave = self.wave      # read once: another thread may clear it
        if wave is not None:
            wave.land()       # idempotent; returns once the rows are set
            self.wave = None
        return self.rows


class _SuffixRows:
    """Rows ``[a, b)`` of a suffix that a split prompt spread over the
    parts of several packed waves (``Engine._prefill_ragged_waves``): what
    a page registered from such a row keeps."""

    __slots__ = ("parts", "a", "b")

    def __init__(self, parts: List[RoutingRows], a: int, b: int) -> None:
        self.parts, self.a, self.b = parts, a, b

    def get(self) -> np.ndarray:
        out, at = [], 0
        for part in self.parts:      # only the parts [a, b) lies in
            rows = part.get()
            lo, hi = max(self.a - at, 0), min(self.b - at, len(rows))
            if lo < hi:
                out.append(rows[lo:hi])
            at += len(rows)
        return np.concatenate(out)


@dataclass
class _Slot:
    active: bool = False
    request: Optional[GenRequest] = None
    position: int = 0           # next absolute position to write
    generated: List[int] = field(default_factory=list)
    logprobs: List[float] = field(default_factory=list)  # parallel to generated
    # the fed token in ``_last_tokens[i]`` has not been surfaced to the
    # host: a prefill's sample (the request's first token) or the token a
    # running row sampled as a rider of a prefill wave; either way it is
    # row 0 of the slot's next block
    pending_token: bool = False
    cancelled: bool = False      # retire at the next processed block
    first_token_at: Optional[float] = None
    admitted_at: Optional[float] = None  # prefill start (flight timeline)
    # engine-local host-sync count stamped at admission: retirement
    # records how many sanctioned syncs this request's lifetime spanned
    # (flight request timelines -> the host_syncs-per-request contract)
    admit_syncs: int = 0
    # device-side next write position: advances by K at each DISPATCH
    # (pipelined chunks are issued before the previous block is read);
    # ``position`` stays the host-confirmed value, advanced at processing
    dispatched_position: int = 0
    # what admission found, for the occupant's spans and the page gauge:
    # prompt tokens served from cached or kept pages / computed by the
    # prefill, and the pages its table row references (owned + shared)
    cached_tokens: int = 0
    new_tokens: int = 0
    row_pages: int = 0
    # the occupant's page-table row as admission wrote it to the device
    # (paged; shared prefix pages, then its own): what a later wave needs
    # to read and extend the slot's context (_wave_riders)
    table_row: Optional[np.ndarray] = None
    # routed configurations: the occupant's routing so far, in position
    # order — its cached pages' rows and its prefill's (arrays or
    # RoutingRows), then its [K, L_routed, k] of each decode chunk (the
    # last one cut at retirement to the steps whose output was read).
    # Set at admission, handed to the request and cleared at
    # retirement. None on a dense engine.
    routing: Optional[List[Any]] = None
    cached_parts: int = 0        # how many of them came with cached pages
    routing_complete: bool = True


@dataclass
class PagedKV:
    """Block-paged KV mode wiring (VERDICT r1 missing #2 -> fixed).

    The engine's main cache becomes a shared page pool + page table
    (ops/paged_kv.py): HBM ∝ num_pages*page_size instead of
    max_batch*max_seq. Prefill still runs on dense bucket-sized temp caches
    (`Engine.forward_fn`); ``init_pool`` builds the {"k","v","page_table"}
    cache dict and ``chunked_fns`` is the chunk triple that pool is
    decoded with (e.g. ``llama.forward_paged_chunked``,
    ``llama.init_chunk_kv``, ``llama.merge_paged_chunk``): the pool stays
    frozen for a chunk's K steps and is written once at its end
    (``Engine._decode``). The two travel together because the forward
    must match the pool's layout. Admission allocates pages via the
    host-side allocator and stalls (keeps requests queued) when the pool
    cannot cover a request's worst-case footprint.
    """

    # (forward(params, tokens, positions, cache, chunk_kv, step),
    #  init_chunk(batch, K), merge(cache, chunk_kv, start_positions))
    chunked_fns: Tuple[Callable, Callable, Callable]
    init_pool: Callable         # () -> {"k", "v", "page_table"}
    page_size: int
    num_pages: int
    allocator: Any              # ops.paged_kv.PageAllocator
    # DP-sharded pools only: shard_map'd collective-free PLAIN prefill
    # (parallel/serving.build_sharded_paged) over waves packed into
    # per-shard row blocks (Engine._packed_geometry sizes the blocks).
    # None = the generic GSPMD prefill (single-chip, or prefix waves).
    prefill_packed: Optional[Callable] = None
    # Single-shard pools only (lane engines included): packed RAGGED
    # prefill (ISSUE 11) — (params, tokens[W], tok_row[W], tok_pos[W],
    # row_tables[R, maxp], starts[R], lens[R], prefix_lens[R], k_pool,
    # v_pool) -> ([R, V] last-token logits, sfx_k, sfx_v [L, W, Hkv, D]).
    # One packed token stream per admission wave; prefix KV (cache
    # hits and earlier chunks of a split prompt) is read straight from
    # the page pool. None = the row-bucketed dense-bucket prefill.
    prefill_ragged: Optional[Callable] = None
    # the widest wave ``prefill_ragged`` is compiled for (None: up to
    # ``max_seq``); a round with more tokens takes more waves
    ragged_max_width: Optional[int] = None


class Engine:
    """Slot-based continuous batching over a jitted decode step."""

    # Cross-thread / device-state contracts, machine-checked by swarmlint
    # (python -m swarmdb_tpu.analysis — see analysis/ and README):
    # swarmlint: guarded-by[self._cv]: _queue, _admitting, _cancel_pending, _stop
    # swarmlint: device-state: _last_tokens, _last_lps, cache, base_keys

    def __init__(
        self,
        forward_fn: Callable,            # forward(params, tokens, positions, cache)
        init_cache_fn: Callable,         # (batch, max_seq) -> cache pytree
        params: Any,
        *,
        max_batch: int = 8,
        max_seq: int = 1024,
        eos_id: int = 2,
        pad_id: int = 0,
        seed: int = 0,
        prefill_buckets: Optional[Sequence[int]] = None,
        metrics: Optional[MetricsRegistry] = None,
        donate_cache: bool = True,
        decode_chunk: int = 8,
        paged: Optional[PagedKV] = None,
        prefill_batch: Optional[int] = None,
        chunked_fns: Optional[Tuple[Callable, Callable, Callable]] = None,
        pipeline_depth: int = 2,
        prefix_fns: Optional[Tuple[Callable, Callable]] = None,
        prefix_pages: int = 0,
        prefix_page_size: int = 16,
        forward_last_fn: Optional[Callable] = None,
        flight_dir: Optional[str] = None,
        aging_s: Optional[float] = None,
        routed: Optional[Tuple[int, int, int]] = None,
        held_experts: Optional[Tuple[int, int]] = None,
    ) -> None:
        # routed = (L_routed, k, E) of a configuration that routes
        # (models.mixtral.routing_shape; None = dense). Its forwards
        # return their routing last ([.., T, L_routed, k]), every
        # compiled program returns it after its other outputs, and the
        # engine carries the rows to the request that owns each position
        # (GenRequest.routing). A dense engine's programs, callbacks and
        # records are as they were.
        self._routed = routed
        # held_experts = (first, count): the weights hold that share of a
        # routed layer's E experts (one chip of those that share a
        # layer). A negative entry of the routing is then a choice of an
        # expert another chip holds, nothing dropped: counted by
        # ``moe_held_assignments`` beside ``moe_assignments``, and the
        # reach of a step (``moe_expert_hits`` of
        # ``moe_expert_step_slots``) is over the held experts
        self._held_experts = held_experts
        # what the last prefill dispatch returned after its other outputs:
        # [] for a dense configuration, [routing] for one that routes
        self._wave_routing: List[Any] = []
        # forward_last_fn(params, tokens, positions, cache, last_pos) ->
        # ([B, V] logits at each row's last_pos, cache): prefill only ever
        # samples the LAST position, so computing the LM head there alone
        # (same math — head columns are position-independent) skips the
        # full-bucket fp32 logits (0.5 GB per wave at Bp=16, T=255, V=32k)
        # and ~7% of prefill FLOPs. Absent -> full forward + gather.
        self.forward_fn = forward_fn
        self._forward_last = forward_last_fn
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.metrics = metrics or MetricsRegistry()
        # latency sinks bound ONCE: hot-marked paths must never pay a
        # defaultdict lookup — or allocate a fresh histogram — per
        # observation (swarmlint SWL503)
        self._lat_queue_wait = self.metrics.latencies["queue_wait_s"]
        self._lat_prefill = self.metrics.latencies["prefill_s"]
        self._lat_first_token = self.metrics.latencies["first_token_s"]
        # observability: request spans ride the process-global tracer;
        # the flight recorder (last-N engine steps + last-M request
        # timelines) is per-engine and auto-dumped on restart/error —
        # see swarmdb_tpu/obs/ and GET /admin/flight
        self.tracer = TRACER
        self.flight = FlightRecorder()
        self._flight_dir = flight_dir
        self._flight_last_had_work = False
        # phase spans (obs/tracer.py phase_begin/phase_end, cat="engine"):
        # every one carries ``step``, this loop-iteration counter, so a
        # request's spans and a device trace's gaps name the iteration
        # that caused them. ``_wave_n`` counts prefill dispatches;
        # ``_compiled_seen`` is the compiled-program count at the last
        # flight step (engine.compile instants)
        self._loop_step = 0
        self._wave_n = 0
        self._compiled_seen = 0
        # swarmprof lane handle (obs/profiler.py): per-variant device-
        # time attribution + this lane's duty cycle. SWARMDB_PROFILE=0
        # hands back the shared NullLane — dispatch sites then pay one
        # attribute read (enabled == False), nothing else (type identity
        # pinned by test). ShardLaneGroup relabels lanes "lane<i>".
        self._prof = kernel_profiler().lane()
        self._prof_resident_key = PROF_RESIDENT_KEYS[0]
        # swarmfleet role (ISSUE 20): None = colocated (default, full
        # warmup), "prefill" = admission/ragged-prefill waves only,
        # "decode" = resident decode + rolling-resume only. The role
        # restricts WARMUP (compile count + VMEM), not capability — an
        # off-role request still runs, it just cold-compiles.
        self._role: Optional[str] = None
        # ShardLaneGroup sets this to the lane index: lanes share ONE
        # flight recorder, and step records carry which lane wrote them
        self.flight_shard: Optional[int] = None
        # online SLO sentinel (obs/sentinel.py): the runtime owns it (one
        # per process, shared metrics registry); ServingService points it
        # here so the engine loop drives window closes and breach dumps
        # read THIS engine's flight rings. None = unmonitored engine.
        self.sentinel = None
        # priority aging (anti-starvation, see _age_queue): seconds a
        # queued request waits per effective-priority-class bump; <= 0
        # disables (strict priority, LOW can starve under saturation)
        if aging_s is None:
            try:
                aging_s = float(os.environ.get("SWARMDB_AGING_S", "5.0"))
            except ValueError:
                logger.warning("SWARMDB_AGING_S=%r is not a float; "
                               "using 5.0",
                               os.environ.get("SWARMDB_AGING_S"))
                aging_s = 5.0
        self._aging_s = aging_s

        self.decode_chunk = max(1, int(decode_chunk))
        # How many decode chunks may be in flight before the host reads
        # the oldest block. Depth 2 issues chunk N+1 BEFORE device_get of
        # chunk N, hiding the host<->device round-trip (not measured on
        # an attached chip) behind the
        # next chunk's compute. Token math is unchanged: dispatch order
        # and device state evolution are identical; only when the host
        # READS each block moves. Slots that retire mid-flight compute
        # one extra chunk of garbage their snapshot tells the host to
        # discard. Depth 1 = the round-3 lockstep behavior.
        self.pipeline_depth = max(1, int(pipeline_depth))
        self.paged = paged
        # runtime page sanitizer (SWARMDB_PAGECHECK=1, obs/pagecheck.py):
        # non-None only when the allocator came from the checked factory
        # — one attr read on the flag-off path, nothing else
        self._pagecheck = (getattr(paged.allocator, "pagecheck", None)
                           if paged else None)
        if self._pagecheck is not None:
            from ..obs.pagecheck import registry as _pagecheck_registry

            _pagecheck_registry().attach_flight(self.flight)
        # interpreter-mode kernel sanitizer (SWARMDB_KERNCHECK=1,
        # obs/kerncheck.py): same one-env-read gate; attaching the flight
        # recorder arms violation instants + the atexit crash dump
        from ..obs.kerncheck import enabled as _kerncheck_enabled

        self._kerncheck = _kerncheck_enabled()
        if self._kerncheck:
            from ..obs.kerncheck import registry as _kerncheck_registry

            _kerncheck_registry().attach_flight(self.flight)
        # main decode cache: paged pool or dense slot buffer; prefill always
        # uses dense bucket-sized temp caches from init_cache_fn
        self.cache = paged.init_pool() if paged else init_cache_fn(max_batch, max_seq)
        # state beside pages: a configuration whose conv layers carry
        # recurrent state (models/lfm2.py) has, in its pool, ``state``
        # ([L_conv, max_batch, rows, D], what each slot's live sequence
        # carries: it rides the chunked decode and the resident loop with
        # the cache, and a ragged wave seeds and rewrites its rows' slots,
        # so an admission is the reset a retired slot needs) and
        # ``page_state`` ([L_conv, num_pages, rows, D], the state at each
        # page's end, under the page's own id: a prefix-cache hit seeds
        # its row from its last hit page's). The pool's shapes size both;
        # no serving key does.
        self._stateful = isinstance(self.cache, dict) and "state" in self.cache
        # snapshots rarer than a page (models/nemotron_h.py): a Mamba-2
        # layer's state is hundreds of times a page's keys and values, so
        # ``state`` and ``page_state`` are ``{"ssm", "conv"}`` and the rows
        # of ``page_state`` are not pages but SNAPSHOT slots (row 0 the
        # bin), far fewer than pages. The prefix cache says which cached
        # page owns which (``PrefixLRU.keep_state_slots``); a wave is told
        # where each row resumes from and where the state at its last
        # page end goes; a prefix hit resumes behind the deepest hit page
        # that still owns one and the rest is forgone. A running row that
        # rides a wave (``_wave_riders``) resumes from its own slot and
        # takes no snapshot: a one-token row is 13 us a layer under
        # ``ssm_pallas.ssm_wave_scan``, 0.3 ms a rider a wave (scripts/
        # race_ssm_wave.py), and 73 us, 1.7 ms, where ``ssm_segments``'
        # loop runs instead (no bf16 state, odd widths, the CPU)
        self._snapshots = 0
        # slot -> the snapshot slot its admission resumes from (plan time)
        self._snap_src: Dict[int, int] = {}
        if self._stateful and isinstance(self.cache["state"], dict):
            self._snapshots = self.cache["page_state"]["ssm"].shape[1] - 1
            # ``ssm_state_rows_walked`` / ``ssm_state_rows_held``: of the
            # slots' state, the share a decode chunk reads a step and
            # rewrites at its end (the live slots', ``nemotron_h.
            # chunk_mixers`` and ``merge_state``). ``ssm_wave_segments`` /
            # ``ssm_wave_segment_tokens``: the live segments a wave's scan
            # walks a layer and the tokens in them, from the wave's plan
            # (``nemotron_h.wave_segments``): segments a wave, and the
            # live share of a segment's ``SCAN_CHUNK``
            for name in ("ssm_snapshots_taken", "ssm_snapshots_evicted",
                         "ssm_snapshot_slots", "ssm_snapshot_slots_live",
                         "ssm_state_tokens_resumed", "ssm_state_rows_walked",
                         "ssm_state_rows_held", "ssm_wave_segments",
                         "ssm_wave_segment_tokens"):
                self.metrics.counters[name].inc(0)
        # latent pages (models/deepseek.py): the pool under ``"k"`` is one
        # of rows ``[L, num_pages, ps, Wd]`` with no heads axis, and
        # ``"v"`` is a ``NoValuePool``, the format's type, which the
        # configuration chose where the cache was built. The programs
        # below hand both on as they hand on keys and values (an empty
        # pytree: nothing is donated or written for it), and page tables, the allocator, the pins and
        # the prefix cache manage a page by its id whatever it holds. The
        # paths that would index a heads axis are the ones a
        # configuration with conv state is kept from, for the same
        # reason: they are not built for it
        from ..ops.paged_kv import NoValuePool

        self._latent = (isinstance(self.cache, dict)
                        and isinstance(self.cache.get("v"), NoValuePool))
        if (self._stateful or self._latent) and (
                paged.prefill_ragged is None
                or getattr(paged.allocator, "n_shards", 1) > 1
                or os.environ.get("SWARMDB_RAGGED_PREFILL", "auto") == "0"):
            raise NotImplementedError(
                "a configuration with conv state or latent pages is served "
                "by the paged engine's ragged prefill and chunked decode on "
                "one shard: the row-bucketed prefill "
                "(SWARMDB_RAGGED_PREFILL=0) and a sharded pool (lanes) do "
                "not carry conv state, nor pages without a heads axis")
        if self._latent:
            from ..ops.layers import latent_kernels_enabled

            latent_kernels_enabled()   # a TPU with SWARMDB_PALLAS=0 refuses
        if paged is not None:
            # swarmmem (ISSUE 17): KV bytes per pool page — prices the
            # warm-tier model's re-admission device_put
            from ..obs.memprof import memprof as _memprof

            try:
                _memprof().set_page_bytes(self._page_bytes())
            except Exception:  # cache layouts without nbytes (stubs)
                pass
        self._prefill_cache_fn = init_cache_fn
        self._seed = seed
        self.base_keys = make_slot_keys(seed, max_batch)
        # host copy for admission-time row gathers: indexing the device
        # array from the host is an eager dispatch (a device round-trip)
        # per admission;
        # numpy fancy-indexing is free and the result rides the jit call
        # WRITABLE host copy (np.asarray of a device array is read-only):
        # per-request seeds rewrite rows in place
        self._base_keys_np = np.array(self.base_keys)
        # pristine per-slot keys: a request with an explicit seed rewrites
        # its slot's row for its lifetime; the next occupant without one
        # restores the default (reproducible replays either way — the
        # per-step key is fold_in(row, absolute position))
        self._default_keys_np = self._base_keys_np.copy()
        self.slots = [_Slot() for _ in range(max_batch)]
        # device-resident fed-token vector: slot i's next input token lives
        # here between chunks so decode->decode and prefill->decode handoffs
        # never touch the host
        self._last_tokens = jnp.zeros((max_batch,), jnp.int32)
        # raw-model logprob of each slot's fed token (same lifecycle)
        self._last_lps = jnp.zeros((max_batch,), jnp.float32)

        # ONE long-context policy flag, read by the bucket ladder here and
        # both prefix-PP width sites below — retune the threshold in one
        # place only
        self._long_context = max_seq >= 512
        if prefill_buckets is None:
            if self._long_context:
                # long-context: x4 bucket growth. Every compiled variant
                # costs a quarter of a minute or more at 8B widths and
                # warmup compiles |buckets| x (1 + |PP widths|) prefill
                # variants — at S=1024 the x2 ladder put ~31 compiles in
                # warmup and blew the bench's 1500 s watchdog. Padding
                # waste from the coarser ladder is bounded by prefill
                # being batch-fused (padding rows ride along) and by the
                # prefix cache absorbing most long-prompt re-prefill.
                ladder = (64, 256, 1024, 4096)
            else:
                ladder = (16, 32, 64, 128, 256)
            prefill_buckets = [b for b in ladder if b <= max_seq]
        prefill_buckets = sorted(prefill_buckets)
        # the largest bucket must hold the longest admissible prompt
        # (max_seq - 1). Append max_seq itself — not max_seq - 1 — so the
        # top (hottest) bucket stays tile/page aligned when max_seq is
        # a power of two or page multiple
        if not prefill_buckets or prefill_buckets[-1] < max_seq - 1:
            prefill_buckets.append(max_seq)
        self.prefill_buckets = prefill_buckets

        # host-side per-slot sampling params. These are handed to the jitted
        # calls as RAW numpy arrays: an explicit jnp.asarray(host) is a
        # blocking transfer of its own, while the same transfer folded into
        # a jit call's argument path rides the dispatch — so
        # the engine never calls jnp.asarray/device_put on the hot path.
        self._temp = np.zeros(max_batch, np.float32)
        self._topk = np.zeros(max_batch, np.int32)
        self._topp = np.ones(max_batch, np.float32)

        # ---- lane supervision signal (backend/supervisor.py) -------------
        # Per-step liveness beat: a plain monotonic float slot written by
        # the engine/emission threads and read by the supervisor — the
        # same single-writer-stamp discipline as the HA failure detector
        # (ha/detector.py). A wedged device dispatch stops the loop from
        # iterating, so the beat goes stale while the thread stays alive:
        # exactly the two-signal split the supervisor's state machine
        # (ALIVE -> SUSPECT -> QUARANTINED) distinguishes.
        self._beat_mono = time.monotonic()
        # True while the loop is inside an engine step (admission /
        # dispatch / block processing). A first-traffic XLA compile can
        # legitimately stall a step for tens of seconds with no beats —
        # the supervisor grants in-step stalls a compile grace window
        # (SWARMDB_LANE_DISPATCH_GRACE_S) before quarantining, while a
        # stall OUTSIDE a step (the chaos wedge seam, a stuck lock) gets
        # none. Single-writer bool slot, loop thread only.
        self._in_step = False
        # Fault-injection seam (backend/chaos.py): called once per engine
        # loop iteration, on the engine thread, BEFORE admission. A kill
        # fault raises LaneKilled (a BaseException, so the loop's error
        # recovery cannot swallow it and the thread dies for real); wedge
        # and slow faults block/sleep here, starving the beat. None in
        # production.
        self.chaos_step: Optional[Callable[["Engine"], None]] = None

        # ---- pool-watermark backpressure (paged engines) ------------------
        # Page-pool exhaustion used to block admission indefinitely with
        # no signal. Watermarks over NON-RECLAIMABLE pool utilization
        # (free + evictable prefix-cache pages count as headroom):
        # admission pauses at the high watermark and resumes at the low
        # one (hysteresis — no admit/fail thrash at the boundary), and
        # past the hard SHED watermark the lowest-priority queued work is
        # returned with retryable reason "shed" so higher-priority work
        # drains first. SWARMDB_POOL_HIGH >= 1 disables.
        def _env_frac(name: str, default: float) -> float:
            try:
                return float(os.environ.get(name, default))
            except ValueError:
                logger.warning("%s=%r is not a float; using %g", name,
                               os.environ.get(name), default)
                return default

        self._bp_high = _env_frac("SWARMDB_POOL_HIGH", 0.92)
        self._bp_low = min(_env_frac("SWARMDB_POOL_LOW", 0.80),
                           self._bp_high)
        self._bp_shed = max(_env_frac("SWARMDB_POOL_SHED", 0.98),
                            self._bp_high)
        self._bp_paused = False
        # tiered-KV demote watermark (ISSUE 19): BELOW the pause
        # watermark — the gate starts signalling the tier manager to
        # spill cold conversations to host RAM before admission ever
        # has to pause, with the same hysteresis band (active until
        # util falls back to the low watermark). SWARMDB_TIER_DEMOTE
        # >= 1 disables the early signal (demote_now still fires on
        # hard allocation failure via on_pool_pressure).
        _d = _env_frac("SWARMDB_TIER_DEMOTE", 0.85)
        self._bp_demote = (_d if _d >= 1.0
                           else max(self._bp_low, min(_d, self._bp_high)))
        self._tier_demoting = False

        self._queue: List[Tuple[int, float, int, GenRequest]] = []  # heap
        # rotates the DP-shard interleave in _free_slot_ids (engine
        # thread only)
        self._admit_rr = 0
        # requests popped from the queue but not yet activated into slots
        # (prefill in flight): cancel() can neither find them queued nor
        # active, so it flags them here and _activate retires them at the
        # next processed block (review finding — a disconnect during a
        # first-bucket compile otherwise orphans the request)
        self._admitting: set = set()
        self._cancel_pending: set = set()
        self._tiebreak = itertools.count()
        self._cv = make_condition("backend.engine.Engine._cv")
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # the kernel's id of the loop thread, by which obs/procwatch.py
        # reads its scheduler account (/proc/self/task/<id>/schedstat)
        self._native_id: Optional[int] = None
        # low-memory hook (ADVICE r4 medium #1): invoked (need_pages) from
        # the engine thread, OUTSIDE the engine lock, when paged admission
        # cannot allocate and nothing was admitted this round. The serving
        # layer evicts idle rolling conversations here; without it, idle
        # conversations could hold the pool while a queued request
        # break-retries forever (admission only retried after retirements,
        # and a fully-idle engine has none).
        self.on_pool_pressure: Optional[Callable[[int], None]] = None
        # tiered-KV hooks (ISSUE 19, wired by TierManager when rolling
        # KV is active on a single-shard paged engine):
        # - on_tier_pressure(need): engine thread, backpressure gate —
        #   the demote watermark tripped; the tier WORKER plans victims
        #   (non-blocking signal, no device work here);
        # - on_tier_drain(): engine thread, start of each admission
        #   round (after the pending-free flush) — execute planned
        #   demotions; their D2H gathers ride the flush wave the
        #   engine already syncs on.
        self.on_tier_pressure: Optional[Callable[[int], None]] = None
        self.on_tier_drain: Optional[Callable[[], None]] = None

        self._donate_cache = donate_cache
        donate = (4,) if donate_cache else ()
        K = self.decode_chunk

        # ---- compiled chunk: K decode steps per host round-trip -----------
        # Two variants: the full sampler, and a sort-free one used whenever
        # no ACTIVE slot has top-k/top-p enabled (sampling.py use_filters —
        # the [B, V] sort is the most expensive op in a large-batch decode
        # step). _dispatch_decode picks per chunk from host-side slot state.
        # Two chunk-loop shapes:
        # - a chunk triple (a page pool brings its own, PagedKV.chunked_fns;
        #   the dense slab's comes as ``chunked_fns``): the main cache
        #   stays FROZEN across the K steps; each step's K/V lands in a
        #   small [B, K, ...] buffer (uniform dynamic_update_slice) and is
        #   folded into the cache ONCE per chunk — a full-cache rewrite
        #   (dense) or bulk page scatter (paged) per chunk instead of per
        #   step. Profiling on the v5e showed the per-step rewrite cost
        #   ~2x the model matmuls.
        # - fallback (a dense slab built without a triple, nothing else):
        #   ``forward_fn`` threads the slab through every step.
        if paged is not None and chunked_fns is not None:
            raise ValueError(
                "Engine(paged=..., chunked_fns=...): a page pool is decoded "
                "with the chunk triple its PagedKV carries "
                "(PagedKV.chunked_fns); the chunked_fns argument is the "
                "dense slab's")
        self._chunked_fns = paged.chunked_fns if paged else chunked_fns

        def _decode(params, last_tokens, last_lps, positions, cache,
                    base_keys, temp, topk, topp, *, scope, use_filters,
                    assume_greedy=False):
            # last_tokens [B] fed tokens, last_lps [B] their raw-model
            # logprobs (computed where they were sampled — prefill or the
            # previous chunk), positions [B] next write positions.
            # Logprobs are computed UNCONDITIONALLY: the per-step
            # log_softmax is ~0.3% of a measured decode chunk and the
            # extra host block is 8 KB/chunk, while gating it would double
            # the compiled variant count (each a quarter of a minute or
            # more at 8B widths) for a flag most requests leave off.
            # A routed configuration's forward returns its routing last
            # ([B, 1, L_routed, k]): the step's row a slot rides the scan
            # beside the sampled token, and the chunk's [K, B, L_routed,
            # k] is returned after the cache. Dense: no such output.
            if self._chunked_fns is not None:
                chunk_fwd, init_chunk, merge_chunk = self._chunked_fns
                chunk_kv = init_chunk(self.max_batch, K)

                def body(carry, step):
                    tok, pos, chunk_kv = carry
                    with jax.named_scope(scope):
                        logits, chunk_kv, *routing = chunk_fwd(
                            params, tok[:, None], pos[:, None], cache,
                            chunk_kv, step,
                        )
                    nxt = sample_tokens(logits[:, -1], base_keys, pos, temp,
                                        topk, topp, use_filters=use_filters,
                                        assume_greedy=assume_greedy)
                    lp = token_logprob(logits[:, -1], nxt)
                    return (nxt, pos + 1, chunk_kv), (
                        nxt, lp, *(r[:, 0] for r in routing))

                (last, _, chunk_kv), (sampled, lps, *routing) = jax.lax.scan(
                    body, (last_tokens, positions, chunk_kv),
                    jnp.arange(K, dtype=jnp.int32),
                )
                new_cache = merge_chunk(cache, chunk_kv, positions)
                all_toks = jnp.concatenate([last_tokens[None], sampled], axis=0)
                all_lps = jnp.concatenate([last_lps[None], lps], axis=0)
                all_toks, all_lps, *routing = self._replicate_block(
                    all_toks, all_lps, *routing)
                last, last_lp = self._pin_slot_state(last, lps[-1])
                return (all_toks, all_lps, last, last_lp, new_cache,
                        *routing)

            def body(carry, _):
                tok, pos, cache = carry
                with jax.named_scope(scope):
                    logits, cache, *routing = self.forward_fn(
                        params, tok[:, None], pos[:, None], cache
                    )
                nxt = sample_tokens(logits[:, -1], base_keys, pos, temp,
                                    topk, topp, use_filters=use_filters,
                                    assume_greedy=assume_greedy)
                lp = token_logprob(logits[:, -1], nxt)
                return (nxt, pos + 1, cache), (
                    nxt, lp, *(r[:, 0] for r in routing))

            (last, _, cache), (sampled, lps, *routing) = jax.lax.scan(
                body, (last_tokens, positions, cache), None, length=K
            )
            # row 0 = the fed tokens (surfaces prefill samples the host has
            # never seen); rows 1..K = this chunk's samples
            all_toks = jnp.concatenate([last_tokens[None], sampled], axis=0)
            all_lps = jnp.concatenate([last_lps[None], lps], axis=0)
            all_toks, all_lps, *routing = self._replicate_block(
                all_toks, all_lps, *routing)
            last, last_lp = self._pin_slot_state(last, lps[-1])
            return all_toks, all_lps, last, last_lp, cache, *routing

        # ordered by parallel.multihost VARIANT_* codes
        self._decode_variants = tuple(
            jax.jit(_named_partial(_decode, name, use_filters=uf,
                                   assume_greedy=ag),
                    donate_argnums=donate)
            for name, (uf, ag) in zip(DECODE_PROGRAM_NAMES,
                                      _DECODE_VARIANT_FLAGS))

        # ---- device-resident decode sessions (emission ring) -------------
        # One jitted ``lax.while_loop`` runs MANY decode chunks per host
        # visit. At each chunk's end the body sends ONE int32 buffer
        # host-ward through an ORDERED ``io_callback`` (the chunk's
        # [K+1, B] tokens and logprobs, its index, the loop's ``done`` row
        # and a routed configuration's routing: ``_pack_resident_block``)
        # and ``cond`` reads the callback's boolean before the next chunk
        # may start: the device WAITS at every chunk boundary, for that
        # buffer's trip to the host, a vote and the answer's trip back.
        # It does not wait for the host's work: ``_resident_emit`` votes
        # from what the buffer and the session's snapshot show (new
        # admissible work, every lane done, stop) and hands the block to
        # the engine thread over a FIFO, and that thread emits, retires
        # and writes the chunk's spans while the device runs the next
        # chunk, one chunk behind it (``_resident_consume``). The host
        # touches the device ONCE per session: the drain read of the
        # chunk counter after the last block. Single-shard PAGED engines
        # only: the shard_map'd multi-device program and the pod control
        # plane keep the per-chunk scan+pipeline path
        # (SWARMDB_EMIT_RING=0 forces that path everywhere).
        self._resident_variants: Optional[Tuple[Any, ...]] = None
        self._resident: Optional[_ResidentSession] = None
        # callback thread -> engine thread, one entry a chunk, in order
        # (None: the callback itself failed); between them ``_PLAN_WAKE``
        # from ``submit()``, one a request queued while the engine thread
        # waits here
        self._resident_fifo: "queue.SimpleQueue[Any]" = queue.SimpleQueue()
        # the boundary of two sessions (ISSUE 45). The plan for what was
        # queued while a session still ran, made between its blocks
        # (``_plan_ahead``): the boundary's round starts from it. Guarded
        # by ``_cv`` (a vote counts it as queued, a cancel takes a request
        # out of it). And the last block of a session that ended for
        # work to admit, settled (``_settle_block``) and not yet
        # delivered: the round's wave is dispatched first
        # (``_deliver_pending``; the engine thread's alone).
        self._held_plan: Optional[_AdmissionPlan] = None
        self._undelivered: Optional[_SettledBlock] = None
        self._lane_busy = False
        self._host_sync_n = 0  # engine-LOCAL sync count (registry
        # counters are shared across lanes, so per-request deltas must
        # not absorb sibling engines' syncs)
        if (paged is not None
                and getattr(paged.allocator, "n_shards", 1) <= 1
                and os.environ.get("SWARMDB_EMIT_RING", "1") != "0"):

            def _decode_resident(params, last_tokens, last_lps, positions,
                                 cache, base_keys, temp, topk, topp,
                                 stop_pos, live, max_chunks, *, scope,
                                 use_filters, assume_greedy=False):
                # stop_pos [B]: first position at/after which the slot
                # needs no more tokens (max_new_tokens bound; the host
                # remains the authority on exact retirement — the device
                # estimate only decides when the LOOP may stop). live
                # [B]: slots participating in this session; dead lanes
                # start done and compute discarded garbage, exactly like
                # the scan path.
                def cond(carry):
                    n, done, cont = carry[0], carry[1], carry[2]
                    return (n < max_chunks) & cont & ~jnp.all(done)

                def body(carry):
                    n, done, cont, lt, llp, pos, cache = carry
                    all_toks, all_lps, lt, llp, cache, *routing = _decode(
                        params, lt, llp, pos, cache, base_keys, temp,
                        topk, topp, scope=scope, use_filters=use_filters,
                        assume_greedy=assume_greedy)
                    pos = pos + K
                    # eos anywhere in the block (row 0 = fed token covers
                    # an eos prefill sample) marks the lane done; done
                    # lanes keep computing garbage the host discards
                    done = (done | (pos >= stop_pos)
                            | jnp.any(all_toks == self.eos_id, axis=0))
                    cont = io_callback(
                        self._resident_emit,
                        jax.ShapeDtypeStruct((), jnp.bool_),
                        _pack_resident_block(all_toks, all_lps, n, done,
                                             *routing),
                        ordered=True)
                    return (n + 1, done, cont, lt, llp, pos, cache)

                init = (jnp.int32(0), ~live, jnp.bool_(True), last_tokens,
                        last_lps, positions, cache)
                n, _done, _cont, lt, llp, _pos, cache = jax.lax.while_loop(
                    cond, body, init)
                return n, lt, llp, cache

            # registered at 0, so a reader tells "no vote went stale"
            # from a program that does not count them
            self.metrics.counters["resident_votes_stale"].inc(0)
            # requests whose plan was held when their round began; only
            # an engine with resident sessions makes plans ahead
            self.metrics.counters["admission_planned_ahead"].inc(0)
            self._resident_variants = tuple(
                jax.jit(_named_partial(_decode_resident, name,
                                       use_filters=uf, assume_greedy=ag),
                        donate_argnums=donate)
                for name, (uf, ag) in zip(RESIDENT_PROGRAM_NAMES,
                                          _DECODE_VARIANT_FLAGS))
        # set by ShardLaneGroup: returns True when a SIBLING lane has a
        # decode session in flight while this lane admits — the overlap
        # the per-shard lanes exist to create (flight/SLO counter
        # ``engine_admission_overlap_steps``)
        self.overlap_probe: Optional[Callable[[], bool]] = None
        # single-device lane placement (ShardLaneGroup): state rebuilds
        # (restart / in-loop error recovery) must land on THIS device,
        # not the process default
        self._home_device = None
        # multi-host control plane (parallel/multihost.py): set by
        # enable_multihost(); when active, every device call is published
        # so worker hosts replay it in lockstep
        self._mh = None

        # ---- compiled prefill, BATCHED: one variant per bucket ------------
        # Prefill at small T is HBM-bound (a full parameter read), so
        # prefilling up to ``prefill_batch`` admitted prompts in ONE call
        # costs nearly the same as one. Rows beyond the real group are
        # padding (length 1) whose results the host discards.
        if prefill_batch is None:
            prefill_batch = 8
        self.prefill_batch = max(1, min(prefill_batch, max_batch))
        # ---- row-bucketed waves (per-shard admission lanes) ---------------
        # A lane's waves are small (<= slots-per-lane) and often partial;
        # padding the ROW dimension to prefill_batch unconditionally made
        # the lane path pay ~4x its real prefill compute (measured 78%
        # grid padding on the dp8 bench vs 11% at dp1). Small-batch PAGED
        # engines therefore pad rows to the smallest power-of-two bucket
        # covering the admission count instead. Each row bucket is a
        # compiled variant (warmup covers rows x buckets x widths), so
        # the ladder is gated to prefill_batch <= 4 — exactly the lane
        # geometry — unless SWARMDB_PREFILL_ROWS forces it (1) or off (0).
        rows_env = os.environ.get("SWARMDB_PREFILL_ROWS", "auto")
        row_bucketed = (paged is not None
                        and getattr(paged.allocator, "n_shards", 1) <= 1
                        and (self.prefill_batch <= 4
                             if rows_env == "auto" else rows_env != "0"))
        if row_bucketed:
            ladder = [1]
            while ladder[-1] < self.prefill_batch:
                ladder.append(min(self.prefill_batch, ladder[-1] * 2))
            self._row_buckets = ladder
        else:
            self._row_buckets = [self.prefill_batch]

        # ---- fused dense prefill: forward + sample + cache insert + fed-
        # token scatter in ONE compiled dispatch per admission group.
        # The round-3 bench collapse (BENCH_r03: 4.8 msg/s while the
        # compiled chunk alone sustains 40x that) traced in part to the
        # dense admission path running ~6 eager device ops per group — two
        # of them full-cache `.at[].set` copies executed OUTSIDE jit, each
        # an un-donated copy of the whole decode cache plus a host round
        # trip. Here the temp prefill cache is
        # created inside the trace, the slot insert donates the main cache,
        # and padding rows carry slot_id == max_batch so mode="drop"
        # discards their writes (they never touch live lanes).
        def _forward_last_of(params, tokens, positions, cacheB, lengths):
            # [Bp, V] logits at each row's final prompt position — via the
            # head-at-last forward when the model provides one (see
            # forward_last_fn above), else full logits + gather
            # (a routed forward's routing [Bp, T, L_routed, k] rides last)
            if self._forward_last is not None:
                return self._forward_last(params, tokens, positions, cacheB,
                                          lengths - 1)
            logits, cacheB, *routing = self.forward_fn(
                params, tokens, positions, cacheB)
            return (logits[jnp.arange(tokens.shape[0]), lengths - 1], cacheB,
                    *routing)

        self._forward_last_of = _forward_last_of

        def _prefill_insert(params, tokens, lengths, slot_ids, cache,
                            last_tokens, last_lps, base_keys, temp, topk,
                            topp):
            Bp, T = tokens.shape
            positions = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None], (Bp, T)
            )
            cacheB = self._prefill_cache_fn(Bp, T)
            last, cacheB, *routing = _forward_last_of(
                params, tokens, positions, cacheB, lengths)
            next_tok = sample_tokens(
                last, base_keys, lengths - 1, temp, topk, topp
            )
            lp = token_logprob(last, next_tok)
            cache = jax.tree.map(
                lambda full, fresh: full.at[:, slot_ids, :T].set(
                    fresh, mode="drop"),
                cache, cacheB,
            )
            last_tokens = last_tokens.at[slot_ids].set(next_tok, mode="drop")
            last_lps = last_lps.at[slot_ids].set(lp, mode="drop")
            last_tokens, last_lps = self._pin_slot_state(last_tokens,
                                                         last_lps)
            return (cache, last_tokens, last_lps,
                    *self._replicate_block(*routing))

        self._prefill_fused = jax.jit(_prefill_insert,
                                      donate_argnums=(4, 5, 6))

        # ---- fused PAGED prefill: forward + sample + page scatter + fed-
        # token scatter in ONE dispatch, pool-donating. The unfused path
        # (temp-cache zeros + jitted prefill + eager pad + insert + token
        # scatter) cost ~5 device round-trips per admission group, which
        # made paged prefill several times slower than the dense fused
        # path when last compared (round 4, a record since removed).
        def _prefill_paged_insert(params, tokens, lengths, target_pages,
                                  slot_ids, k_pool, v_pool, last_tokens,
                                  last_lps, base_keys, temp, topk, topp):
            # tokens [Bp, T]; target_pages [Bp, chunks] physical page ids
            # (padding rows and short-prompt tail chunks -> trash page 0);
            # slot_ids [Bp] fed-token scatter targets (padding -> max_batch,
            # dropped).
            Bp, T = tokens.shape
            positions = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None], (Bp, T)
            )
            cacheB = self._prefill_cache_fn(Bp, T)
            last, cacheB, *routing = _forward_last_of(
                params, tokens, positions, cacheB, lengths)
            next_tok = sample_tokens(
                last, base_keys, lengths - 1, temp, topk, topp
            )
            lp = token_logprob(last, next_tok)
            ck, cv = cacheB                             # [L, Bp, T, Hkv, D]
            ps = self.paged.page_size
            chunks = target_pages.shape[1]
            pad_to = chunks * ps
            if pad_to != T:
                # pad region is prompt padding — length-masked, never read
                pad = [(0, 0), (0, 0), (0, pad_to - T), (0, 0), (0, 0)]
                ck = jnp.pad(ck, pad)
                cv = jnp.pad(cv, pad)
            L = ck.shape[0]
            tail = ck.shape[3:]
            kc = ck.reshape((L, Bp * chunks, ps) + tail)
            vc = cv.reshape((L, Bp * chunks, ps) + tail)
            flat = target_pages.reshape(-1)             # [Bp*chunks]
            # pool_insert_pages quantizes whole pages on write for the
            # int8 QuantPool (scale from per-page-per-head amax); plain
            # pools keep the old cast-and-scatter
            from ..ops.paged_kv import pool_insert_pages

            k_pool = pool_insert_pages(k_pool, flat, kc)
            v_pool = pool_insert_pages(v_pool, flat, vc)
            last_tokens = last_tokens.at[slot_ids].set(next_tok, mode="drop")
            last_lps = last_lps.at[slot_ids].set(lp, mode="drop")
            last_tokens, last_lps = self._pin_slot_state(last_tokens,
                                                         last_lps)
            return (k_pool, v_pool, last_tokens, last_lps,
                    *self._replicate_block(*routing))

        if paged is not None:
            self._prefill_paged_fused = jax.jit(
                _prefill_paged_insert, donate_argnums=(5, 6, 7, 8)
            )
            self._prefill_paged_packed = None
            if paged.prefill_packed is not None:
                # same argument order as _prefill_paged_insert, same
                # donation; rows = n_shards * prefill_batch per wave so
                # any admission skew still fits one dispatch. The pin is
                # a no-op resharding (shard_map's out_specs already put
                # the fed-token vectors on the canonical P('data')), so
                # the packed program stays collective-free.
                _packed_body_fn = paged.prefill_packed

                def _prefill_packed_pinned(params, tokens, lengths, target,
                                           scatter, k_pool, v_pool,
                                           last_tokens, last_lps, keys,
                                           temp, topk, topp):
                    (k_pool, v_pool, last_tokens, last_lps,
                     *routing) = _packed_body_fn(
                        params, tokens, lengths, target, scatter, k_pool,
                        v_pool, last_tokens, last_lps, keys, temp, topk,
                        topp)
                    last_tokens, last_lps = self._pin_slot_state(
                        last_tokens, last_lps)
                    return (k_pool, v_pool, last_tokens, last_lps,
                            *self._replicate_block(*routing))

                self._prefill_paged_packed = jax.jit(
                    _prefill_packed_pinned, donate_argnums=(5, 6, 7, 8)
                )

        # ---- RAGGED packed prefill (ISSUE 11 tentpole) --------------------
        # One token stream per admission wave, no row or length buckets:
        # rows concatenate back to back, per-row (start, len, prefix_len)
        # descriptors ride the dispatch, and attention reads each row's
        # prefix KV straight from the page pool
        # (ops/layers.ragged_prefill_dispatch — the Pallas
        # ragged-paged-prefill kernel on TPU). Wave widths come off a
        # power-of-two ladder from SWARMDB_RAGGED_MIN_WIDTH (default 8)
        # up to max_seq; which rungs a round takes is plan_ragged_waves'
        # least-cost cover, priced by ``_ragged_ridge_tokens``: where the
        # ridge lies under the smallest rung (a CPU) a round pads no more
        # than its binary decomposition does, and on a chip whose ridge
        # is some hundred tokens the tail of a round is rounded up into
        # one wave, because a padded token under the ridge is free and a
        # second wave is a second pass over the weights. The floor sits
        # at 8 (one TPU sublane quantum) rather than 1: rungs below 8
        # each compile a program that the dispatcher immediately pads
        # back up to width 8, so they add compiled variants and per-wave
        # dispatch overhead while moving zero extra real tokens
        # (PROFILE.md round 11 A/B). The ladder is the ONLY
        # compiled-variant axis:
        # |widths| programs replace |buckets| x |row buckets| (+ the whole
        # prefix-variant family, since a cache hit is just a nonzero
        # prefix_len here). SWARMDB_RAGGED_PREFILL=0 restores the
        # row-bucketed waves.
        self._prefill_ragged_fused = None
        self._ragged_widths: List[int] = []
        self._ragged_ridge_tokens = 0.0
        self._last_wave_kind: Optional[str] = None
        # which decode-attention path serves this engine's waves (paged
        # only): stamped on flight-step records so kernel-vs-gather
        # regressions are attributable from a dump alone
        self._decode_kernel: Optional[str] = None
        if paged is not None:
            from ..ops.layers import decode_kernel_choice

            self._decode_kernel = decode_kernel_choice(
                paged.allocator.maxp * paged.page_size)
        if (paged is not None and paged.prefill_ragged is not None
                and getattr(paged.allocator, "n_shards", 1) <= 1
                and os.environ.get("SWARMDB_RAGGED_PREFILL", "auto") != "0"):
            try:
                min_w = int(os.environ.get("SWARMDB_RAGGED_MIN_WIDTH", "8"))
            except ValueError:
                logger.warning("SWARMDB_RAGGED_MIN_WIDTH=%r is not an int; "
                               "using 8",
                               os.environ.get("SWARMDB_RAGGED_MIN_WIDTH"))
                min_w = 8
            widest = min(max_seq, paged.ragged_max_width or max_seq)
            ladder = [max(1, min(min_w, widest))]
            while ladder[-1] < widest:
                ladder.append(min(widest, ladder[-1] * 2))
            self._ragged_widths = ladder
            self._ragged_ridge_tokens = weights_ridge_tokens(params)
            # registered at 0, so a reader tells "nobody rode" from a
            # program whose waves take no riders; beside it the running
            # rows a round's plan had no seat for (``_riders_that_fit``)
            self.metrics.counters["wave_rider_tokens"].inc(0)
            self.metrics.counters["wave_riders_unseated"].inc(0)
            if self._latent:
                self.metrics.counters["latent_prefix_tokens_reused"].inc(0)
            _ragged_body_fn = paged.prefill_ragged

            def _prefill_ragged_insert(params, tokens, tok_row, tok_pos,
                                       starts, lens, plens, row_tables,
                                       scatter, k_pool, v_pool,
                                       last_tokens, last_lps, base_keys,
                                       temp, topk, topp, *state):
                # tokens/tok_row/tok_pos [W] packed stream (padding:
                # row >= R, pos >= table coverage -> trash writes);
                # descriptors [R]; scatter [R] fed-token targets —
                # max_batch (dropped) for padding rows AND rows whose
                # prompt continues in a later wave of the same round.
                # state (a configuration with conv state, else empty):
                # (src [R], slots [R], slot_state, page_state). A row is
                # seeded from page ``src``'s state (a prefix hit's last
                # page), from zeros (src 0: a cold row) or from its own
                # slot's (src < 0: a later chunk of a split prompt); its
                # state after this wave lands in slot ``slots`` (max_batch
                # drops a padding row) and the state at each page end it
                # computed under that page's id. With snapshots
                # (``self._snapshots``) ``src`` names a snapshot slot, a
                # third vector ``dst`` [R] the slot that takes the state
                # at the row's last page end (0: the bin), and the body
                # says which rows crossed a page end at all.
                # The large part of such a state (``"ssm"``) the body
                # reads from and writes to the two pools in place; only
                # the conv rows are seeded and scattered here.
                from ..ops.paged_kv import paged_write_ragged

                seed, big = (), None
                if state:
                    src, slots, *dst, slot_state, page_state = state
                    if dst:
                        big = (slot_state["ssm"], page_state["ssm"])
                        slot_state = slot_state["conv"]
                        page_state = page_state["conv"]
                    seed = seed_state(src, slots, slot_state, page_state)
                    seed = ({"conv": seed, "ssm": (src, slots, *dst, *big)}
                            if dst else seed,)
                last, sk, sv, *routing = _ragged_body_fn(
                    params, tokens, tok_row, tok_pos, row_tables, starts,
                    lens, plens, k_pool, v_pool, *seed)
                if state:
                    row_state, ends_state, end_pages, *routing = routing
                    if dst:
                        end_pages = jnp.where(end_pages > 0, dst[0], 0)
                        big, routing = routing[:2], routing[2:]
                    state = (
                        slot_state.at[:, slots].set(row_state, mode="drop"),
                        page_state.at[:, end_pages].set(ends_state))
                    if dst:
                        state = tuple({"ssm": b, "conv": c}
                                      for b, c in zip(big, state))
                # absolute-position PRNG fold == the bucketed paths'
                # (prefix_lens + lengths - 1): identical sampling for an
                # identical prompt whichever path admitted it
                next_tok = sample_tokens(
                    last, base_keys, jnp.maximum(plens + lens - 1, 0),
                    temp, topk, topp)
                lp = token_logprob(last, next_tok)
                k_pool, v_pool = paged_write_ragged(
                    k_pool, v_pool, sk, sv, tok_row, tok_pos, row_tables)
                last_tokens = last_tokens.at[scatter].set(next_tok,
                                                          mode="drop")
                last_lps = last_lps.at[scatter].set(lp, mode="drop")
                last_tokens, last_lps = self._pin_slot_state(last_tokens,
                                                             last_lps)
                return (k_pool, v_pool, last_tokens, last_lps, *state,
                        *self._replicate_block(*routing))

            self._prefill_ragged_fused = jax.jit(
                _prefill_ragged_insert,
                donate_argnums=(9, 10, 11, 12)
                + (((20, 21) if self._snapshots else (19, 20))
                   if self._stateful else ()))

        # ---- automatic prefix caching --------------------------------------
        # Chat serving re-prefills each conversation's WHOLE history every
        # turn (prefill dominated decode ~15:1 on the round-4 serve
        # profile). The prefix cache reuses page-aligned prompt KV across
        # requests: admission matches the longest cached prefix and
        # prefills only the suffix. Dense mode keeps a SIDE pool and
        # copies reused pages into slot lanes; paged mode reuses pool
        # pages IN PLACE (pinning them while referenced). See
        # ops/prefix_cache.py for chain hashing + eviction safety.
        self._prefix = None
        self._prefix_fns = prefix_fns
        # paged mode: pages each live slot keeps pinned (matched hits +
        # pages it registered); unpinned at retirement
        self._slot_prefix_pins: Dict[int, List[int]] = {}
        if prefix_fns is not None and paged is not None:
            # PAGED mode: reuse IN PLACE — the main pool holds the cached
            # pages, hit pages are pinned while a slot's table row
            # references them, suffix KV scatters straight into the
            # slot's fresh pages (page-aligned: reuse is page-granular),
            # and registration is free (no copy — custody of the slot's
            # full prompt pages just moves to the cache at registration).
            if max_seq % paged.page_size:
                raise ValueError("max_seq must be a page-size multiple "
                                 "for prefix caching")
            from ..ops.prefix_cache import make_prefix_lru

            self._prefix_ps = paged.page_size
            # paged mode shares the allocator's pool (and, under
            # SWARMDB_PAGECHECK=1, its shadow state — obs/pagecheck.py)
            self._prefix = make_prefix_lru(paged.num_pages,
                                           paged.page_size,
                                           manage_free=False,
                                           pool=paged.allocator)
            if self._snapshots:
                self._prefix.keep_state_slots(self._snapshots)
            pages_fwd = prefix_fns[0]
            maxp_row = paged.allocator.maxp
            self._prefix_pp_buckets = self._pp_widths(maxp_row)

            def _prefill_paged_prefix_insert(params, tokens, lengths,
                                             prefix_lens, prefix_table,
                                             target_pages, slot_ids, k_pool,
                                             v_pool, last_tokens, last_lps,
                                             base_keys, temp, topk, topp):
                # tokens [Bp, T] SUFFIX tokens; prefix_table [Bp, PP] live
                # pool pages (gather); target_pages [Bp, chunks] fresh
                # pages for the suffix (page-aligned since the reused
                # prefix is page-granular; trash 0 for padding)
                Bp, T = tokens.shape
                ps = self.paged.page_size
                logits, sk, sv, *routing = pages_fwd(
                    params, tokens, prefix_table, prefix_lens, k_pool,
                    v_pool, logits_at=lengths - 1,
                )
                last = (logits if logits.ndim == 2
                        else logits[jnp.arange(Bp), lengths - 1])
                next_tok = sample_tokens(
                    last, base_keys, prefix_lens + lengths - 1, temp, topk,
                    topp,
                )
                lp = token_logprob(last, next_tok)
                chunks = target_pages.shape[1]
                pad_to = chunks * ps
                if pad_to != T:
                    pad = [(0, 0), (0, 0), (0, pad_to - T), (0, 0), (0, 0)]
                    sk = jnp.pad(sk, pad)
                    sv = jnp.pad(sv, pad)
                L = sk.shape[0]
                tail = sk.shape[3:]
                kc = sk.reshape((L, Bp * chunks, ps) + tail)
                vc = sv.reshape((L, Bp * chunks, ps) + tail)
                flat = target_pages.reshape(-1)
                from ..ops.paged_kv import pool_insert_pages

                k_pool = pool_insert_pages(k_pool, flat, kc)
                v_pool = pool_insert_pages(v_pool, flat, vc)
                last_tokens = last_tokens.at[slot_ids].set(next_tok,
                                                           mode="drop")
                last_lps = last_lps.at[slot_ids].set(lp, mode="drop")
                last_tokens, last_lps = self._pin_slot_state(last_tokens,
                                                             last_lps)
                return (k_pool, v_pool, last_tokens, last_lps,
                        *self._replicate_block(*routing))

            self._prefill_paged_prefix_fused = jax.jit(
                _prefill_paged_prefix_insert, donate_argnums=(7, 8, 9, 10)
            )

            # ---- rolling-KV resume: suffix prefill continuing a kept
            # conversation MID-PAGE. Same suffix forward as the prefix
            # path (attend kept pages + suffix, positions offset by
            # resume_len), but the suffix K/V is written POSITIONALLY via
            # paged_write_chunk (start = resume_len, arbitrary alignment)
            # into the row's table instead of whole-page scatters — a
            # conversation's length after decode is never page-aligned.
            def _prefill_paged_resume_insert(params, tokens, lengths,
                                             resume_lens, prefix_table,
                                             row_tables, slot_ids, k_pool,
                                             v_pool, last_tokens, last_lps,
                                             base_keys, temp, topk, topp):
                from ..ops.paged_kv import paged_write_chunk, pool_dtype

                Bp, T = tokens.shape
                logits, sk, sv, *routing = pages_fwd(
                    params, tokens, prefix_table, resume_lens, k_pool,
                    v_pool, logits_at=lengths - 1,
                )
                last = (logits if logits.ndim == 2
                        else logits[jnp.arange(Bp), lengths - 1])
                next_tok = sample_tokens(
                    last, base_keys, resume_lens + lengths - 1, temp, topk,
                    topp,
                )
                lp = token_logprob(last, next_tok)
                k_pool, v_pool = paged_write_chunk(
                    k_pool, v_pool, sk.astype(pool_dtype(k_pool)),
                    sv.astype(pool_dtype(v_pool)), resume_lens, row_tables,
                )
                last_tokens = last_tokens.at[slot_ids].set(next_tok,
                                                           mode="drop")
                last_lps = last_lps.at[slot_ids].set(lp, mode="drop")
                last_tokens, last_lps = self._pin_slot_state(last_tokens,
                                                             last_lps)
                return (k_pool, v_pool, last_tokens, last_lps,
                        *self._replicate_block(*routing))

            self._prefill_paged_resume_fused = jax.jit(
                _prefill_paged_resume_insert, donate_argnums=(7, 8, 9, 10)
            )
        elif prefix_fns is not None:
            if max_seq % prefix_page_size:
                raise ValueError("max_seq must be a page-size multiple "
                                 "for prefix caching")
            from ..ops.prefix_cache import make_prefix_lru

            self._prefix_ps = prefix_page_size
            self._prefix = make_prefix_lru(max(2, prefix_pages),
                                           prefix_page_size)
            lane_fwd, init_pool = prefix_fns
            self._prefix_init_pool = init_pool
            self._prefix_pool = init_pool(max(2, prefix_pages),
                                          prefix_page_size)
            maxp_lane = max_seq // prefix_page_size
            self._prefix_pp_buckets = self._pp_widths(maxp_lane)

            def _prefill_prefix_insert(params, tokens, lengths, prefix_lens,
                                       prefix_table, reg_cols, reg_pages,
                                       slot_ids, cache, last_tokens,
                                       last_lps, pool_k, pool_v, base_keys,
                                       temp, topk, topp):
                # tokens [Bp, T] SUFFIX tokens; prefix_table [Bp, PP] pool
                # pages; reg_cols [Bp, RC] lane-page index to register
                # (-1 = none); reg_pages [Bp, RC] target pool ids (0=trash)
                Bp, T = tokens.shape
                ps = self._prefix_ps
                PP = prefix_table.shape[1]
                lane_pages = min(PP + -(-T // ps), self.max_seq // ps)
                logits, lane_k, lane_v, *routing = lane_fwd(
                    params, tokens, prefix_table, prefix_lens, pool_k,
                    pool_v, lane_pages, logits_at=lengths - 1,
                )
                last = (logits if logits.ndim == 2
                        else logits[jnp.arange(Bp), lengths - 1])
                # absolute position keys the PRNG fold => identical
                # sampling to a full (non-cached) prefill of this prompt
                next_tok = sample_tokens(
                    last, base_keys, prefix_lens + lengths - 1, temp, topk,
                    topp,
                )
                lp = token_logprob(last, next_tok)
                ck, cv = cache
                lane_t = lane_pages * ps
                ck = ck.at[:, slot_ids, :lane_t].set(lane_k, mode="drop")
                cv = cv.at[:, slot_ids, :lane_t].set(lane_v, mode="drop")
                # register: extract the named lane pages (one-hot einsum —
                # per-row gathers don't compile well on TPU) into the pool
                L = lane_k.shape[0]
                RC = reg_cols.shape[1]
                sel = (reg_cols[..., None]
                       == jnp.arange(lane_pages)[None, None, :])
                sel = sel.astype(lane_k.dtype)          # [Bp, RC, P_lane]
                lk = lane_k.reshape(L, Bp, lane_pages, ps, *lane_k.shape[3:])
                lv = lane_v.reshape(L, Bp, lane_pages, ps, *lane_v.shape[3:])
                flat = reg_pages.reshape(-1)
                ck_pages = jnp.einsum("brp,lbpshd->lbrshd", sel, lk)
                cv_pages = jnp.einsum("brp,lbpshd->lbrshd", sel, lv)
                pool_k = pool_k.at[:, flat].set(
                    ck_pages.reshape(L, Bp * RC, ps, *lane_k.shape[3:]))
                pool_v = pool_v.at[:, flat].set(
                    cv_pages.reshape(L, Bp * RC, ps, *lane_v.shape[3:]))
                last_tokens = last_tokens.at[slot_ids].set(next_tok,
                                                           mode="drop")
                last_lps = last_lps.at[slot_ids].set(lp, mode="drop")
                last_tokens, last_lps = self._pin_slot_state(last_tokens,
                                                             last_lps)
                return ((ck, cv), last_tokens, last_lps, pool_k, pool_v,
                        *self._replicate_block(*routing))

            self._prefill_prefix_fused = jax.jit(
                _prefill_prefix_insert, donate_argnums=(8, 9, 10, 11, 12)
            )

            # ---- dense rolling-KV retirement extraction: copy a retired
            # slot's lane KV (positions 0..written, page-chunked) into
            # prefix-pool pages whose custody moves to the caller's
            # registry. The dense lane is slot-private (unlike the paged
            # pool, where custody transfer is pure host bookkeeping), so
            # keeping a conversation's KV across turns costs ONE
            # bandwidth-bound copy here and one gather at resume — far
            # cheaper than the full-history prefill it replaces. Padding
            # rows of target_pages are 0: the trash page absorbs them.
            lane_maxp = max_seq // prefix_page_size

            def _extract_lane(cache, pool_k, pool_v, slot_id, target_pages):
                ck, cv = cache
                L = ck.shape[0]
                tail_shape = ck.shape[3:]
                lk = jnp.take(ck, slot_id, axis=1)  # [L, S, Hkv, D]
                lv = jnp.take(cv, slot_id, axis=1)
                lk = lk.reshape((L, lane_maxp, prefix_page_size) + tail_shape)
                lv = lv.reshape((L, lane_maxp, prefix_page_size) + tail_shape)
                pool_k = pool_k.at[:, target_pages].set(
                    lk.astype(pool_k.dtype))
                pool_v = pool_v.at[:, target_pages].set(
                    lv.astype(pool_v.dtype))
                return pool_k, pool_v

            self._extract_lane_fused = jax.jit(
                _extract_lane, donate_argnums=(1, 2)
            )

        self.total_generated = 0
        self.total_requests = 0

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        if self._thread is not None:
            return
        self._compiled_seen = self._compiled_count()
        self._thread = threading.Thread(target=self._loop_thread,
                                        daemon=True, name="swarmdb-engine")
        self._thread.start()

    def _loop_thread(self) -> None:
        self._native_id = threading.get_native_id()
        self._run()

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if self._mh is not None:
            # release worker hosts blocked in worker_loop's receive
            try:
                self._mh.publish_stop()
            except Exception:
                logger.exception("multihost stop broadcast failed")

    def alive(self) -> bool:
        """True while the decode loop thread is running."""
        return self._thread is not None and self._thread.is_alive()

    # ------------------------------------------------- supervision signals

    # swarmlint: heartbeat
    def _beat(self) -> None:
        """Per-step liveness proof (engine loop / a processed block):
        one monotonic read into a single-writer float slot — the
        supervisor's verdict path reads it lock-free."""
        self._beat_mono = time.monotonic()

    def _chaos_pending(self) -> bool:
        cs = self.chaos_step
        return cs is not None and getattr(cs, "pending",
                                          lambda: False)()

    # swarmlint: heartbeat
    def beat_age_s(self, now: float = 0.0) -> float:
        """Seconds since the decode loop last proved progress. Idle
        engines still beat (the admission wait loop stamps every wait
        tick); only a dead or wedged loop lets this grow."""
        return (now or time.monotonic()) - self._beat_mono

    # ---------------------------------------------------------- multi-host

    def place_state(self, mesh) -> None:
        """Re-materialize the engine's replicated device state (fed-token
        vector, PRNG keys) ON the mesh, computed device-side.

        Required before multi-process serving: state built by plain
        ``jnp.zeros`` lives on the process-local default device, and a jit
        over a global mesh cannot mix process-local arrays with global
        ones. Computing the state under ``out_shardings`` avoids any host
        transfer and yields bit-identical values on every host. Idempotent
        and also valid (harmless) for single-process multi-chip meshes.

        Also fixes the CANONICAL sharding of the per-slot state vectors
        (``_state_sharding``, enforced by ``_pin_slot_state`` in every
        jitted body): without it each compiled program hands the fed-token
        vectors back in whatever sharding GSPMD picked for THAT program
        (measured: decode returns them P('data') after place_state made
        them replicated), so the next variant's eager call lowers a
        DIFFERENT HLO than warmup_call_plan's specs and the parallel AOT
        precompile's persistent-cache entries are never read — every
        warmup variant compiled twice on mesh-placed engines (PROFILE r5
        finding d / VERDICT r5 #6)."""
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        # decode-chunk token blocks must come back replicated (see
        # _replicate_block) — set BEFORE the first decode call traces
        self._out_rep = rep
        B = self.max_batch
        # canonical per-slot state sharding: batch over 'data' when it
        # divides evenly (matches the shard_map'd packed prefill's
        # out_specs, so pinning costs no collective there), replicated
        # otherwise. What matters is that it never changes again.
        data = mesh.shape.get("data", 1)
        if data > 1 and B % data == 0:
            self._state_sharding = NamedSharding(mesh,
                                                 PartitionSpec("data"))
        else:
            self._state_sharding = rep
        self._last_tokens, self._last_lps = self._fresh_slot_state()
        self.base_keys = jax.jit(
            lambda: make_slot_keys(self._seed, B), out_shardings=rep)()
        self._base_keys_np = np.array(
            jax.device_get(self.base_keys))
        self._default_keys_np = self._base_keys_np.copy()
        if self._prefix is not None and not self.paged:
            # the dense prefix side pool was built process-local in
            # __init__; a jit over a global mesh cannot mix it with the
            # global cache — rematerialize it (zeros) on the mesh,
            # replicated (every shard reads any page via the lane gather)
            self._prefix_pool = jax.jit(
                lambda: self._prefix_init_pool(self._prefix.num_pages,
                                               self._prefix_ps),
                out_shardings=rep)()

    def enable_multihost(self) -> None:
        """Publish every device call to worker hosts (coordinator side).

        Requires ``jax.distributed.initialize`` to have run and the
        engine's params/cache to live on a global mesh; see
        ``parallel/multihost.py`` and ``Engine.worker_loop``. Paged and
        prefix-cached engines are supported (VERDICT r4 #6): their
        allocator / prefix-table state stays coordinator-local — it only
        COMPUTES the numpy arguments (page rows, gather tables,
        registration columns) of device calls, and every device call is
        published through the generic mirrored-call channel, so worker
        pool state evolves identically. Rolling-KV resume remains refused
        in pod mode at the serving layer (page custody cannot survive a
        pod restart)."""
        from ..parallel.multihost import ControlPlane

        self._mh = ControlPlane(self.max_batch, self.prefill_batch)

    def worker_loop(self) -> None:
        """Run on every NON-coordinator host: replay the coordinator's
        device calls in lockstep until it publishes stop.

        Device state (params, cache, fed-token vector) must be constructed
        identically on every host before entering — deterministic sharded
        init guarantees this (parallel/serving.build_sharded_model). The
        loop issues the exact jit call the coordinator issued, with the
        broadcast numpy arguments, so the SPMD programs rendezvous on
        their collectives; sampled tokens exist on this host's shards but
        only the coordinator reads them."""
        from ..parallel import multihost as mh

        if self._mh is None:
            self._mh = mh.ControlPlane(self.max_batch, self.prefill_batch)
        while True:
            op, args = self._mh.receive()
            if op == mh.OP_STOP:
                return
            if op == mh.OP_DECODE:
                variant, positions, keys, temp, topk, topp = args
                fn = self._decode_variants[variant]
                (all_toks, _lps, self._last_tokens, self._last_lps,
                 self.cache, *_routing) = fn(
                    self.params, self._last_tokens, self._last_lps,
                    positions, self.cache, keys, temp, topk, topp,
                )
            elif op == mh.OP_PREFILL:
                tokens, lengths, scatter, keys, temp, topk, topp = args
                (self.cache, self._last_tokens, self._last_lps,
                 *_routing) = self._prefill_fused(
                        self.params, tokens, lengths, scatter, self.cache,
                        self._last_tokens, self._last_lps, keys, temp, topk,
                        topp,
                    )
            elif op == mh.OP_CALL:
                call_id, call_args = args[0], args[1:]
                self._MH_CALLS[call_id](self, *call_args)

    # Generic mirrored device calls (paged / prefix paths). Each handler
    # consumes ONLY numpy arguments + device state (params, cache, fed
    # tokens, prefix pool) — never the coordinator-local allocator or
    # prefix table — so replaying it on a worker host with the published
    # arguments reproduces the coordinator's device state exactly.
    CALL_PAGED_PREFILL = 0
    CALL_PAGED_PREFIX_PREFILL = 1
    CALL_PAGED_RESUME_PREFILL = 2
    CALL_SET_PT_ROWS = 3
    CALL_DENSE_PREFIX_PREFILL = 4
    CALL_PAGED_PREFILL_PACKED = 5
    CALL_PAGED_PREFILL_RAGGED = 6

    def _replicate_block(self, *blocks):
        """Constrain the chunk's sampled-token block (and, where the
        configuration routes, its routing) to REPLICATED when the
        engine lives on a mesh (``place_state`` sets ``_out_rep``): the
        shard_map'd paged decode leaves it data-sharded, which a pod
        coordinator cannot device_get (the shards span other processes).
        The all-gather this inserts moves [K+1, B] ints — bytes, not
        bandwidth. Traced at first call, AFTER place_state; single-chip
        engines (no mesh) see None and compile unchanged."""
        rep = getattr(self, "_out_rep", None)
        if rep is None:
            return blocks
        return tuple(jax.lax.with_sharding_constraint(b, rep)
                     for b in blocks)

    def _pin_slot_state(self, *arrays):
        """Constrain per-slot [B] state outputs (fed tokens / logprobs) to
        the canonical sharding chosen by ``place_state``, inside every
        jitted body that returns them. Without the pin, each compiled
        program hands the vectors back in whatever sharding GSPMD picked
        for THAT program (decode emitted P('data') where place_state made
        them replicated), so the NEXT variant's eager call lowers a
        different HLO than ``warmup_call_plan``'s specs — the AOT
        persistent-cache mismatch of PROFILE r5 finding d. Traced at first
        call, AFTER place_state; single-chip engines see None and compile
        unchanged (same pattern as ``_replicate_block``)."""
        sh = getattr(self, "_state_sharding", None)
        if sh is None:
            return arrays
        return tuple(jax.lax.with_sharding_constraint(a, sh)
                     for a in arrays)

    def _fresh_slot_state(self):
        """Zeroed fed-token/logprob vectors in the canonical placement —
        on the mesh when place_state has run (restart must not demote the
        state to process-local, or every variant recompiles against the
        unplaced sharding), default device otherwise."""
        B = self.max_batch
        sh = getattr(self, "_state_sharding", None)
        if sh is None:
            with self._device_ctx():
                return (jnp.zeros((B,), jnp.int32),
                        jnp.zeros((B,), jnp.float32))
        return (
            jax.jit(lambda: jnp.zeros((B,), jnp.int32), out_shardings=sh)(),
            jax.jit(lambda: jnp.zeros((B,), jnp.float32),
                    out_shardings=sh)(),
        )

    # swarmlint: borrows[page]: args
    def _mirrored(self, call_id: int, *args) -> None:  # swarmlint: hot
        """Publish (pod mode) then execute one mirrored device call.
        Publish FIRST, matching the decode/prefill pattern: if the local
        execution raises, the pod is already failing loudly through the
        decode loop's fatal-stop path. Under swarmprof the execution is
        wall-timed around the dispatch (the CPU-fallback device-time
        approximation; one key build + two clock reads per admission
        wave, never per token)."""
        if self._mh is not None:
            self._mh.publish_call(call_id, args)
        # every call but the table setter is a prefill dispatch: one
        # device wave, and the phase a compile would show up in
        kind = self._WAVE_KINDS.get(call_id)
        t_wave = (self.tracer.phase_begin("engine.admission.dispatch")
                  if kind else 0)
        prof = self._prof
        if prof.enabled:
            t0 = time.monotonic_ns()
            self._MH_CALLS[call_id](self, *args)
            prof.dispatch(self._PROF_MIRRORED[call_id](args), t0,
                          time.monotonic_ns() - t0)
        else:
            self._MH_CALLS[call_id](self, *args)
        if kind:
            self.tracer.phase_end(
                t_wave, "engine.admission.dispatch", cat="engine",
                args=self._count_wave(kind))

    def _take_wave(self) -> Optional[WaveRouting]:  # swarmlint: hot
        """The routing of the prefill just dispatched, as a wave whose
        parts the rows' records and the pages they register keep; None
        where the configuration is dense."""
        if not self._wave_routing:
            return None
        (dev,), self._wave_routing = self._wave_routing, []
        return WaveRouting(dev)

    # swarmlint: hot
    def _pack_args(self, width: int, filled: int,
                   riders: int = 0) -> Dict[str, Any]:
        """Args of the ``engine.admission.pack`` phase of the wave about
        to be dispatched: its token grid, the admitted tokens in it and
        the running rows that ride it (a ragged wave; a token each)."""
        return {"step": self._loop_step, "wave": self._wave_n + 1,
                "width": int(width), "filled": int(filled),
                "riders": riders}

    def _count_wave(self, kind: str) -> Dict[str, Any]:  # swarmlint: hot
        """Count one prefill dispatch (a device wave); returns the args
        of its ``engine.admission.dispatch`` phase."""
        self._wave_n += 1
        self.metrics.counters["prefill_device_waves"].inc()
        return {"step": self._loop_step, "wave": self._wave_n,
                "kind": kind}

    # swarmlint: hot
    def _call_paged_prefill(self, tokens, lengths, target, scatter, keys,
                            temp, topk, topp) -> None:
        (k_pool, v_pool, self._last_tokens, self._last_lps,
         *self._wave_routing) = self._prefill_paged_fused(
                self.params, tokens, lengths, target, scatter,
                self.cache["k"], self.cache["v"], self._last_tokens,
                self._last_lps, keys, temp, topk, topp,
            )
        self.cache = self._paged_cache_with(k_pool, v_pool)

    # swarmlint: hot
    def _call_paged_prefill_packed(self, tokens, lengths, target, scatter,
                                   keys, temp, topk, topp) -> None:
        (k_pool, v_pool, self._last_tokens, self._last_lps,
         *self._wave_routing) = self._prefill_paged_packed(
                self.params, tokens, lengths, target, scatter,
                self.cache["k"], self.cache["v"], self._last_tokens,
                self._last_lps, keys, temp, topk, topp,
            )
        self.cache = self._paged_cache_with(k_pool, v_pool)

    # swarmlint: hot
    def _call_paged_prefix_prefill(self, tokens, lengths, plens, table,
                                   target, scatter, keys, temp, topk,
                                   topp) -> None:
        (pk, pv, self._last_tokens, self._last_lps,
         *self._wave_routing) = self._prefill_paged_prefix_fused(
                self.params, tokens, lengths, plens, table, target, scatter,
                self.cache["k"], self.cache["v"], self._last_tokens,
                self._last_lps, keys, temp, topk, topp,
            )
        self.cache = self._paged_cache_with(pk, pv)

    # swarmlint: hot
    def _call_paged_resume_prefill(self, tokens, lengths, rlens, table,
                                   row_tables, scatter, keys, temp, topk,
                                   topp) -> None:
        (pk, pv, self._last_tokens, self._last_lps,
         *self._wave_routing) = self._prefill_paged_resume_fused(
                self.params, tokens, lengths, rlens, table, row_tables,
                scatter, self.cache["k"], self.cache["v"],
                self._last_tokens, self._last_lps, keys, temp, topk, topp,
            )
        self.cache = self._paged_cache_with(pk, pv)

    # swarmlint: hot
    def _call_paged_ragged_prefill(self, tokens, tok_row, tok_pos, starts,
                                   lens, plens, row_tables, scatter, keys,
                                   temp, topk, topp, *state_rows) -> None:
        # state_rows: (src, slots) of a configuration with conv state
        state = ((*state_rows, self.cache["state"],
                  self.cache["page_state"]) if state_rows else ())
        (k_pool, v_pool, self._last_tokens, self._last_lps,
         *rest) = self._prefill_ragged_fused(
                self.params, tokens, tok_row, tok_pos, starts, lens,
                plens, row_tables, scatter, self.cache["k"],
                self.cache["v"], self._last_tokens, self._last_lps, keys,
                temp, topk, topp, *state,
            )
        self.cache = self._paged_cache_with(k_pool, v_pool)
        if state_rows:
            self.cache["state"], self.cache["page_state"], *rest = rest
        self._wave_routing = rest

    # swarmlint: hot
    def _call_set_pt_rows(self, rows, vals) -> None:
        from ..ops.paged_kv import set_page_table_rows

        self.cache["page_table"] = set_page_table_rows(
            self.cache["page_table"], rows, vals)

    # swarmlint: hot
    def _call_dense_prefix_prefill(self, tokens, lengths, plens, table,
                                   reg_cols, reg_pages, scatter, keys,
                                   temp, topk, topp) -> None:
        pk, pv = self._prefix_pool
        (self.cache, self._last_tokens, self._last_lps, pk, pv,
         *self._wave_routing) = (
            self._prefill_prefix_fused(
                self.params, tokens, lengths, plens, table, reg_cols,
                reg_pages, scatter, self.cache, self._last_tokens,
                self._last_lps, pk, pv, keys, temp, topk, topp,
            ))
        self._prefix_pool = (pk, pv)

    _MH_CALLS = {
        CALL_PAGED_PREFILL: _call_paged_prefill,
        CALL_PAGED_PREFIX_PREFILL: _call_paged_prefix_prefill,
        CALL_PAGED_RESUME_PREFILL: _call_paged_resume_prefill,
        CALL_SET_PT_ROWS: _call_set_pt_rows,
        CALL_DENSE_PREFIX_PREFILL: _call_dense_prefix_prefill,
        CALL_PAGED_PREFILL_PACKED: _call_paged_prefill_packed,
        CALL_PAGED_PREFILL_RAGGED: _call_paged_ragged_prefill,
    }

    # what ``engine.admission.dispatch`` calls each prefill family
    _WAVE_KINDS = {
        CALL_PAGED_PREFILL: "paged",
        CALL_PAGED_PREFIX_PREFILL: "paged_prefix",
        CALL_PAGED_RESUME_PREFILL: "resume",
        CALL_DENSE_PREFIX_PREFILL: "dense_prefix",
        CALL_PAGED_PREFILL_PACKED: "packed",
        CALL_PAGED_PREFILL_RAGGED: "ragged",
    }

    # swarmprof key per mirrored call (args exclude the call id): the
    # SAME shapes the harvest reads off warmup_call_plan specs, so the
    # runtime key always lands on a harvested variant
    _PROF_MIRRORED = {
        CALL_PAGED_PREFILL:
            lambda a: prof_key("prefill.paged", a[0].shape),
        CALL_PAGED_PREFIX_PREFILL:
            lambda a: prof_key("prefill.paged_prefix", a[0].shape,
                               a[3].shape[1]),
        CALL_PAGED_RESUME_PREFILL:
            lambda a: prof_key("prefill.resume", a[0].shape,
                               a[3].shape[1]),
        CALL_SET_PT_ROWS: lambda a: "table.set_rows",
        CALL_DENSE_PREFIX_PREFILL:
            lambda a: prof_key("prefill.dense_prefix", a[0].shape,
                               a[3].shape[1]),
        CALL_PAGED_PREFILL_PACKED:
            lambda a: prof_key("prefill.packed", a[0].shape),
        CALL_PAGED_PREFILL_RAGGED:
            lambda a: prof_key("prefill.ragged", a[0].shape),
    }

    def restart(self) -> None:
        """Recover from a fatal engine death (SURVEY §5.3 failure
        detection): fail whatever was in flight (callers see
        ``engine_restart`` and the runtime's FAILED/resend machinery takes
        over), rebuild device state, and bring the loop back up.

        Refused in pod mode: worker hosts cannot be told to rebuild their
        shards, so a local restart would silently desynchronize the SPMD
        program — the pod recovers by restarting its processes."""
        if self._mh is not None:
            raise RuntimeError(
                "multi-host engine cannot restart in place; restart the "
                "pod processes (worker state cannot be rebuilt remotely)"
            )
        if self._thread is not None and not self._thread.is_alive():
            self._thread = None
        with self._cv:
            self._stop = False
        # counter first (ADVICE r4 #2): epoch checks racing this restart
        # must fail CLOSED — observing the new epoch with the old pool
        # merely drops reusable state, while the old epoch with a rebuilt
        # pool would bless dangling page ids. (The allocator's own
        # generation stamp — bumped inside reset(), re-validated at
        # submit AND admission — is the authoritative guard; this
        # ordering just keeps the metric-derived view consistent too.)
        self.metrics.counters["engine_restarts"].inc()
        # dump the flight record BEFORE _fail_all mutates slot state: the
        # rings hold the last steps of the DEAD loop, which is exactly
        # the evidence a post-mortem needs (SWARMDB_FLIGHT_DIR or the
        # engine's configured flight_dir; always kept as last_dump too)
        self.flight.auto_dump("engine_restart", self._flight_dir)
        self._fail_all("engine_restart")
        self._last_tokens, self._last_lps = self._fresh_slot_state()
        self.cache = self._fresh_cache()
        if self._prefix is not None:
            # dense: the side pool was donated into the failed dispatch —
            # rebuild it; paged: _fresh_cache rebuilt the main pool. Either
            # way every cached entry now points at zeroed pages: forget all
            if not self.paged:
                self._prefix_pool = self._prefix_init_pool(
                    self._prefix.num_pages, self._prefix_ps)
            self._prefix.reset()
            self._slot_prefix_pins.clear()
        self.start()

    def pool_epoch(self) -> int:
        """Epoch stamp for externally-held page ids (rolling-KV registry):
        the pool's own generation, bumped by every reset — both restart()
        and the in-loop error recovery rebuild the pool through reset, so
        holders can't miss an epoch either way. Paged engines stamp the
        page allocator; dense engines stamp the prefix side pool (its
        acquire() is where dense rolling custody comes from); engines
        with neither have no externally-holdable pages."""
        if self.paged:
            return self.paged.allocator.generation
        if self._prefix is not None:
            return self._prefix.generation
        return self.metrics.counters["engine_restarts"].value

    # ------------------------------------------------------ rolling-KV hooks
    # The serving layer's rolling registry holds page custody between
    # turns; these helpers hide which pool the pages came from (paged main
    # pool vs the dense prefix side pool).

    def supports_rolling(self) -> bool:
        if self._stateful or self._latent:
            # kept pages come back without the state at their end; the
            # resume prefill reads pages by a heads axis
            return False
        if self.paged is not None:
            return (getattr(self, "_prefill_paged_resume_fused", None)
                    is not None
                    and getattr(self.paged.allocator, "n_shards", 1) <= 1)
        return (self._prefix is not None
                and getattr(self, "_prefill_prefix_fused", None) is not None)

    def rolling_page_size(self) -> int:
        return self.paged.page_size if self.paged else self._prefix_ps

    def rolling_free(self, pages) -> None:
        """Return registry-custody pages to their pool (same-epoch only —
        the caller checks pool_epoch before calling)."""
        if self.paged:
            self.paged.allocator.add_free(list(pages))
        else:
            for p in pages:
                self._prefix.release(p)

    def rolling_free_count(self) -> int:
        if self.paged:
            return self.paged.allocator.free_count()
        return self._prefix.free_count()

    def _device_ctx(self):
        """Placement scope for device-state rebuilds: lane engines
        (ShardLaneGroup) live on ONE specific device, and a recovery
        path that rebuilds the pool under the process default device
        would silently mix devices into the next dispatch."""
        if self._home_device is None:
            import contextlib

            return contextlib.nullcontext()
        return jax.default_device(self._home_device)

    def pin_to_device(self, dev) -> None:
        """Commit this engine's device state to ``dev`` (ShardLaneGroup:
        one engine per chip). Arrays made under
        ``jax.default_device(dev)`` sit on ``dev`` but are UNCOMMITTED,
        and a jit call made outside that context runs on the process
        default device and copies them there: every lane then computes
        on the first chip — which four real chips refuse for lack of
        memory, and virtual CPU devices never notice. ``device_put`` to
        the device an array is already on commits it without a copy; a
        program follows its committed arguments, and what it returns is
        committed in turn."""
        self._home_device = dev
        (self.params, self.cache, self._last_tokens,
         self._last_lps) = jax.device_put(
            (self.params, self.cache, self._last_tokens, self._last_lps),
            dev)
        if getattr(self, "_prefix_pool", None) is not None:
            self._prefix_pool = jax.device_put(self._prefix_pool, dev)

    def _fresh_cache(self):
        with self._device_ctx():
            if self.paged:
                self.paged.allocator.reset()
                return self.paged.init_pool()
            return self._prefill_cache_fn(self.max_batch, self.max_seq)

    def warmup(self) -> float:
        """Pre-compile every jitted variant the serving loop can hit and
        return seconds spent (see ``_warmup_impl``). Wraps the compile
        work in a swarmprof suspend/resume bracket: compile stalls must
        not be billed as device time (a 30 s XLA compile would dwarf the
        first MFU window), and the cost-model HARVEST — the one place
        ``lower()``/``cost_analysis()`` may run (swarmlint SWL506) —
        happens here, before serving traffic exists."""
        assert not self._any_active(), "warmup requires an idle engine"
        self._prof.suspend()
        try:
            if not isinstance(self._prof, NullLane):
                try:
                    self.profile_harvest()
                except Exception:
                    logger.exception("swarmprof cost harvest failed")
            return self._warmup_impl()
        finally:
            # resume re-anchors the lane's duty-cycle clock at serving
            # start, so duty = busy / time-since-warmed
            self._prof.resume()

    def profile_harvest(self) -> int:
        """Harvest XLA cost-model facts (FLOPs, bytes accessed) for every
        warmup-plan variant into the process profiler — warmup/compile
        time ONLY (the zero-harvest-post-warmup contract is asserted by
        test and policed by SWL506). ``Lowered.cost_analysis()`` runs the
        cost model on the traced module without compiling or executing,
        so a harvest costs one trace per variant. Lane groups share the
        process registry: the first lane to harvest a variant covers its
        siblings. Returns the number of variants harvested."""
        prof = kernel_profiler()
        try:
            leaf = jax.tree_util.tree_leaves(self.params)[0]
            dev = next(iter(leaf.devices()))
            prof.set_platform(dev.platform,
                              getattr(dev, "device_kind", ""))
        except Exception:  # identity is best-effort (mocked params etc.)
            pass
        fam = self._prof_families()
        harvested = 0
        for fn, specs in self.warmup_call_plan():
            family, tbl = fam.get(id(fn), ("unknown", None))
            if family.startswith(("decode", "resident")):
                key = family
            else:
                ppb = specs[tbl].shape[1] if tbl is not None else None
                key = prof_key(family, specs[1].shape, ppb)
            if prof.harvested(key):
                continue
            ca = None
            try:
                ca = fn.lower(*specs).cost_analysis()
            except Exception:
                logger.debug("cost harvest failed for %s", key,
                             exc_info=True)
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            ca = ca or {}
            meta: Dict[str, Any] = {}
            if self.paged is not None:
                # pool payload dtype joins the variant row so roofline
                # A/Bs (bf16 vs int8 pools) stay like-for-like, and
                # the pool's true HBM price per covered token rides
                # along — XLA's cost model prices the FALLBACK graph
                # (whose dequant materializes f32 pages), not the
                # in-kernel dequant the TPU path runs, so the roofline
                # A/B reads KV traffic off this column instead
                from ..ops.paged_kv import kv_dtype_name

                meta["kv_dtype"] = kv_dtype_name()
                try:
                    ps = int(self.paged.page_size)
                    meta["kv_bytes_per_token"] = (
                        self._page_bytes() // max(1, ps))
                except Exception:  # stub caches without nbytes
                    pass
            if (family.startswith(("decode", "resident"))
                    and self._decode_kernel is not None):
                # which attention path this program lowers to — the
                # flight-step tag, joined onto the variant row
                meta["kernel"] = self._decode_kernel
            elif family == "prefill.ragged":
                from ..ops.layers import prefill_kernel_choice

                meta["kernel"] = prefill_kernel_choice()
            prof.record_variant(key, ca.get("flops"),
                                ca.get("bytes accessed"), meta or None)
            harvested += 1
        return harvested

    def _prof_families(self) -> Dict[int, Tuple[str, Optional[int]]]:
        """id(jitted fn) -> (profiler family, prefix-table spec index)
        for naming warmup-plan entries; the table index names the spec
        whose trailing dim is the prefix-gather width (a compile axis)."""
        fam: Dict[int, Tuple[str, Optional[int]]] = {}
        for i, fn in enumerate(self._decode_variants):
            fam[id(fn)] = (PROF_DECODE_KEYS[i], None)
        if self._resident_variants is not None:
            for i, fn in enumerate(self._resident_variants):
                fam[id(fn)] = (PROF_RESIDENT_KEYS[i], None)
        for name, family, tbl in (
                ("_prefill_fused", "prefill.dense", None),
                ("_prefill_paged_fused", "prefill.paged", None),
                ("_prefill_paged_packed", "prefill.packed", None),
                ("_prefill_ragged_fused", "prefill.ragged", None),
                ("_prefill_paged_prefix_fused", "prefill.paged_prefix", 4),
                ("_prefill_paged_resume_fused", "prefill.resume", 4),
                ("_prefill_prefix_fused", "prefill.dense_prefix", 4)):
            fn = getattr(self, name, None)
            if fn is not None:
                fam[id(fn)] = (family, tbl)
        return fam

    def _warmup_impl(self) -> float:
        """Pre-compile every jitted variant the serving loop can hit — the
        decode chunk plus one prefill per bucket — and return seconds spent.

        BENCH_r03's 4.8 msg/s collapse was largely compile stalls landing
        inside the measured window: as conversations accumulate history,
        prompts graduate to bigger buckets, and each new bucket's first
        admission paid a 10-30 s XLA compile while every in-flight request
        waited. Call this before serving traffic (no slots may be active:
        warmup reuses the live cache/fed-token buffers through donation,
        which is only safe while every lane is dead).

        Warmup inputs are padding: dense prefill rows scatter to slot id
        ``max_batch`` (mode="drop" discards them); the decode chunk writes
        garbage K/V at positions 0..K-1 of dead lanes, which the
        write-before-read invariant makes unreachable to future occupants.
        With a persistent compilation cache (utils/xla_cache.py) the XLA
        work amortizes across processes, so warmup costs seconds, not
        minutes, after the first run.
        """
        assert not self._any_active(), "warmup requires an idle engine"
        t0 = time.time()
        try:
            parallel = int(os.environ.get("SWARMDB_WARMUP_PARALLEL", "1"))
        except ValueError:
            logger.warning("SWARMDB_WARMUP_PARALLEL=%r is not an int; "
                           "warming up sequentially",
                           os.environ.get("SWARMDB_WARMUP_PARALLEL"))
            parallel = 1
        if parallel > 1:
            # AOT-compile every variant concurrently FIRST: the serialized
            # executables land in the persistent cache, so the sequential
            # jit executions below deserialize in seconds instead of
            # compiling one after the other. Without
            # the persistent cache the AOT executables would be discarded
            # and everything would compile TWICE — refuse, loudly.
            if jax.config.jax_compilation_cache_dir:
                self.precompile(parallel)
            else:
                logger.warning(
                    "SWARMDB_WARMUP_PARALLEL=%d ignored: persistent "
                    "compile cache is off (utils/xla_cache."
                    "enable_compile_cache), so parallel AOT results "
                    "could not be reused", parallel)
        positions = np.zeros((self.max_batch,), np.int32)
        if self._role_warms_decode():
            for variant, decode in enumerate(self._decode_variants):
                if self._mh is not None:
                    self._mh.publish_decode(variant, positions,
                                            self._base_keys_np, self._temp,
                                            self._topk, self._topp)
                (all_toks, _lps, self._last_tokens, self._last_lps,
                 self.cache, *_routing) = decode(
                    self.params, self._last_tokens, self._last_lps,
                    positions, self.cache, self._base_keys_np, self._temp,
                    self._topk, self._topp,
                )
                jax.block_until_ready(all_toks)

        if self._use_resident() and self._role_warms_decode():
            # resident-session variants: with live all-False the
            # while_loop body never executes (no emission fires) but the
            # program still compiles; state passes through the donation
            no_live = np.zeros((self.max_batch,), bool)
            for fn in self._resident_variants:
                (_n, self._last_tokens, self._last_lps, self.cache) = fn(
                    self.params, self._last_tokens, self._last_lps,
                    positions, self.cache, self._base_keys_np, self._temp,
                    self._topk, self._topp, positions, no_live,
                    np.int32(0),
                )
            jax.block_until_ready(self._last_tokens)

        Bp = self.prefill_batch
        lengths = np.ones(Bp, np.int32)
        zero_i = np.zeros(Bp, np.int32)
        zero_f = np.zeros(Bp, np.float32)
        ones_f = np.ones(Bp, np.float32)
        keys = self._base_keys_np[np.zeros(Bp, np.int64)]
        if self._ragged_active() and self._role_warms_prefill():
            # packed ragged waves: ONE variant per packed width — every
            # input is padding (dead rows, trash-routed positions)
            R = self.max_batch
            maxp = self.paged.allocator.maxp
            cap = maxp * self.paged.page_size
            for wd in self._ragged_widths:
                self._mirrored(
                    self.CALL_PAGED_PREFILL_RAGGED,
                    np.full(wd, self.pad_id, np.int32),
                    np.full(wd, R, np.int32),
                    np.full(wd, cap, np.int32),
                    np.zeros(R, np.int32),
                    np.zeros(R, np.int32),
                    np.zeros(R, np.int32),
                    np.zeros((R, maxp), np.int32),
                    np.full(R, self.max_batch, np.int32),
                    self._base_keys_np[np.zeros(R, np.int64)],
                    np.zeros(R, np.float32),
                    np.zeros(R, np.int32),
                    np.ones(R, np.float32),
                    # conv state: cold rows into no slot
                    *((np.zeros(R, np.int32),
                       np.full(R, self.max_batch, np.int32))
                      if self._stateful else ()),
                    *((np.zeros(R, np.int32),) if self._snapshots else ()),
                )
        for bucket in self.prefill_buckets:
            if not self._role_warms_prefill():
                break  # fleet decode lanes admit via resume delta-prefill
            tokens = np.full((Bp, bucket), self.pad_id, np.int32)
            if self.paged:
                if self._ragged_active():
                    # ragged waves replace the bucketed (and prefix)
                    # variants entirely — warmed above
                    continue
                # target page 0 = the trash page (absorbs garbage writes);
                # fed-token rows scatter to max_batch (dropped)
                chunks = -(-bucket // self.paged.page_size)
                if self._packed_active():
                    # sharded engines run the packed variant exclusively
                    # on the plain path — warm it, not the dead GSPMD one
                    _, _, R = self._packed_geometry()
                    self._mirrored(
                        self.CALL_PAGED_PREFILL_PACKED,
                        np.full((R, bucket), self.pad_id, np.int32),
                        np.ones(R, np.int32),
                        np.zeros((R, chunks), np.int32),
                        np.full(R, self.max_batch, np.int32),
                        self._base_keys_np[np.zeros(R, np.int64)],
                        np.zeros(R, np.float32), np.zeros(R, np.int32),
                        np.ones(R, np.float32),
                    )
                else:
                    # one variant per ROW bucket too (lane engines pad
                    # waves to the admission count's bucket, not Bp)
                    for rb in self._row_buckets:
                        self._mirrored(
                            self.CALL_PAGED_PREFILL,
                            np.full((rb, bucket), self.pad_id, np.int32),
                            np.ones(rb, np.int32),
                            np.zeros((rb, chunks), np.int32),
                            np.full(rb, self.max_batch, np.int32),
                            self._base_keys_np[np.zeros(rb, np.int64)],
                            np.zeros(rb, np.float32),
                            np.zeros(rb, np.int32),
                            np.ones(rb, np.float32),
                        )
            else:
                drop = np.full(Bp, self.max_batch, np.int32)
                if self._mh is not None:
                    self._mh.publish_prefill(tokens, lengths, drop, keys,
                                             zero_f, zero_i, ones_f)
                (self.cache, self._last_tokens, self._last_lps,
                 *_routing) = self._prefill_fused(
                        self.params, tokens, lengths, drop, self.cache,
                        self._last_tokens, self._last_lps, keys, zero_f,
                        zero_i, ones_f,
                    )
        if self._prefix is not None:
            # prefix-prefill variants: one per (suffix bucket, PP width).
            # Inputs are pure padding — trash-page gathers, drop-scattered
            # rows, no registration (reg_cols all -1 / trash targets)
            drop = np.full(Bp, self.max_batch, np.int32)
            for bucket in self.prefill_buckets:
                for ppb in self._prefix_pp_buckets:
                    tokens = np.full((Bp, bucket), self.pad_id, np.int32)
                    if self.paged:
                        chunks = -(-bucket // self._prefix_ps)
                        if (not self._ragged_active()
                                and self._role_warms_prefill()):
                            # ragged engines serve cache hits through the
                            # ragged waves (a hit is just a prefix_len);
                            # only the rolling-resume variants below stay
                            for rb in self._row_buckets:
                                self._mirrored(
                                    self.CALL_PAGED_PREFIX_PREFILL,
                                    np.full((rb, bucket), self.pad_id,
                                            np.int32),
                                    np.ones(rb, np.int32),
                                    np.zeros(rb, np.int32),
                                    np.zeros((rb, ppb), np.int32),
                                    np.zeros((rb, chunks), np.int32),
                                    np.full(rb, self.max_batch, np.int32),
                                    self._base_keys_np[np.zeros(rb,
                                                                np.int64)],
                                    np.zeros(rb, np.float32),
                                    np.zeros(rb, np.int32),
                                    np.ones(rb, np.float32),
                                )
                        if self._warm_resume():
                            # rolling-KV resume variants (gated: each is
                            # one more big compile and
                            # only SWARMDB_ROLLING_KV deployments hit them)
                            maxp = self.paged.allocator.maxp
                            self._mirrored(
                                self.CALL_PAGED_RESUME_PREFILL, tokens,
                                lengths, np.zeros(Bp, np.int32),
                                np.zeros((Bp, ppb), np.int32),
                                np.zeros((Bp, maxp), np.int32), drop,
                                keys, zero_f, zero_i, ones_f,
                            )
                        continue
                    if not self._role_warms_prefill():
                        continue
                    lane_pages = min(ppb + -(-bucket // self._prefix_ps),
                                     self.max_seq // self._prefix_ps)
                    self._mirrored(
                        self.CALL_DENSE_PREFIX_PREFILL, tokens, lengths,
                        np.zeros(Bp, np.int32),
                        np.zeros((Bp, ppb), np.int32),
                        np.full((Bp, lane_pages), -1, np.int32),
                        np.zeros((Bp, lane_pages), np.int32),
                        drop, keys, zero_f, zero_i, ones_f,
                    )
        jax.block_until_ready(self._last_tokens)
        dt = time.time() - t0
        self.metrics.latencies["warmup_s"].observe(dt)
        logger.info("engine warmup compiled %d prefill buckets + decode "
                    "chunk in %.1fs", len(self.prefill_buckets), dt)
        return dt

    def _page_bytes(self) -> int:
        """HBM bytes one page id occupies across layers (swarmmem's price
        of a page's admission). ``pool_page_bytes`` folds the int8
        QuantPool's scale planes in; a latent cache is one pool of rows
        ``[L, P, ps, Wd]`` and no value pool."""
        from ..ops.paged_kv import pool_page_bytes

        if self._latent:
            return self.cache["k"].nbytes // max(1, self.cache["k"].shape[1])
        return (pool_page_bytes(self.cache["k"])
                + pool_page_bytes(self.cache["v"]))

    def _paged_cache_with(self, k_pool, v_pool):
        """Rebuild the paged cache dict around new k/v pools, carrying
        every non-pool field (page_table, pos0) — ONE site instead of a
        hand-maintained key list at each fused-dispatch return (a
        forgotten key is a KeyError that kills the decode loop)."""
        out = dict(self.cache)
        out["k"] = k_pool
        out["v"] = v_pool
        return out

    def _packed_active(self) -> bool:
        """Whether the PLAIN paged path runs the shard-packed
        collective-free prefill. ONE gate shared by warmup(),
        warmup_call_plan() and _prefill_batch — the three must agree or
        warmup compiles a dead variant while the serving path pays a
        cold compile mid-traffic (same contract as _warm_resume)."""
        return (self.paged is not None
                and getattr(self, "_prefill_paged_packed", None) is not None
                and getattr(self.paged.allocator, "n_shards", 1) > 1)

    def _packed_geometry(self):
        """(n_shards, rows_per_shard, total_rows) of a packed wave. A
        wave holds at most min(prefill_batch, slots_per_shard) DISTINCT
        slots of any one shard (slot ids are unique per wave), so each
        block is sized to that — not to prefill_batch, which would run
        up to slots_per/Bp-fold wasted forward FLOPs per device."""
        n_sh = self.paged.allocator.n_shards
        rows_per = max(1, min(self.prefill_batch, self.max_batch // n_sh))
        return n_sh, rows_per, n_sh * rows_per

    def _ragged_active(self) -> bool:
        """Whether paged admission runs PACKED RAGGED waves (one
        packed token stream per wave, prefix KV read in place)
        instead of row-bucketed dense-bucket prefills. ONE gate shared
        by warmup(), warmup_call_plan() and _admit — the same
        agree-or-cold-compile contract as _packed_active. Off when the
        model has no ragged forward, on sharded pools (the shard-packed
        path owns those), or under SWARMDB_RAGGED_PREFILL=0."""
        return (self._prefill_ragged_fused is not None
                and not self._packed_active())

    def _ragged_width_for(self, n: int) -> int:
        """Width of the next wave for ``n`` pending tokens: the first
        rung of their cheapest cover (``plan_ragged_waves``). A wave is
        full while splitting is cheaper, and the round's tail is rounded
        up into one padded wave where that costs less than another pass
        over the weights."""
        return plan_ragged_waves(n, self._ragged_widths,
                                 self._ragged_ridge_tokens)[0]

    # swarmlint: hot
    def _wave_riders(self) -> List[int]:
        """The slots that may ride this round's ragged wave as one-token
        rows (ISSUE 43), read from what the engine can observe: a running
        row (its first token is out and none is pending) that is not
        cancelled, whose every dispatched chunk has been processed
        (always so between resident sessions; with a chunk in flight on
        the scan path nobody rides: the device is ahead of ``generated``),
        with at least two tokens left (a row with one left would need a
        whole chunk only to surface it) and room for the step under
        ``max_seq``. What holds for the pages holds for a slot's state
        (conv rows, a Mamba-2 layer's ``ssm``): every chunk merges its
        steps into the slot pool at its end, so with every dispatched
        chunk processed the pool holds the row's state at ``position``,
        and the wave reads it from there and writes it back a token on
        (``state_src`` -1). A prefill lane has no running rows of its own.
        A configuration whose FFN drops over a capacity never gets here:
        it has no ragged waves (a rider would compete with the prompt
        tokens for capacity where a decode step's rows do not)."""
        if self._role == "prefill":
            return []
        return [i for i, s in enumerate(self.slots)
                if s.active and not s.cancelled and not s.pending_token
                and s.generated and s.position == s.dispatched_position
                and s.request.sampling.max_new_tokens - len(s.generated) >= 2
                and s.position + 1 < self.max_seq]

    # swarmlint: hot
    def _riders_that_fit(self, total: int, width: int, seats: int) -> int:
        """How many of ``seats`` one-token riders the round's last wave
        takes at the planner's own price: its ``total`` pending tokens
        plan as one wave of ``width``, and with the riders they still
        plan as one wave that costs no more (``max(w, ridge)``,
        ``plan_ragged_waves``): the padding under the rung, and under the
        ridge the wider rungs that cost the same pass over the weights.
        No rung, program or wave the round would not have had."""
        if seats <= 0:
            return 0
        ridge = self._ragged_ridge_tokens
        price = max(width, ridge)
        k = min(seats, max(w for w in self._ragged_widths
                           if max(w, ridge) <= price) - total)
        while k > 0:
            w = self._ragged_width_for(total + k)
            if w >= total + k and max(w, ridge) <= price:
                break
            k -= 1
        return max(k, 0)

    def _role_warms_decode(self) -> bool:
        """Whether this lane's warmup covers the decode-side variants
        (decode chunk, resident sessions). ONE gate shared by warmup()
        and warmup_call_plan() — same agree-or-drift contract as
        _packed_active. Fleet PREFILL lanes skip them."""
        return self._role != "prefill"

    def _role_warms_prefill(self) -> bool:
        """Whether this lane's warmup covers the admission-side prefill
        variants (ragged/packed/bucketed + prefix). Fleet DECODE lanes
        skip them — their only admission path is the rolling-resume
        delta-prefill, which _warm_resume covers."""
        return self._role != "decode"

    def _warm_resume(self) -> bool:
        """Whether warmup covers the rolling-KV resume variants (paged +
        prefix engines, SWARMDB_ROLLING_KV deployments only — plus fleet
        DECODE lanes, whose admission path IS the resume delta-prefill).
        ONE gate shared by warmup() and warmup_call_plan() — they must
        agree or the precompile drift test fails."""
        if self._role == "prefill":
            return False
        return (self.paged is not None
                and getattr(self, "_prefill_paged_resume_fused", None)
                is not None
                and (os.environ.get("SWARMDB_ROLLING_KV") == "1"
                     or self._role == "decode"))

    def warmup_call_plan(self) -> List[Tuple[Any, Tuple[Any, ...]]]:
        """(jitted fn, ShapeDtypeStruct args) for every variant warmup()
        executes — the decode chunk x3 samplers, one prefill per bucket,
        and one prefix prefill per (bucket, PP width). Must mirror
        warmup()'s calls exactly — drift is caught end-to-end by
        `test_precompile_cache_covers_warmup`, which asserts a
        precompiled engine's warmup adds ZERO new persistent-cache
        entries (any shape/dtype/arg-order/donation mismatch shows up
        as a fresh compile)."""
        from jax.sharding import NamedSharding

        def sds(shape, dtype, a=None):
            # mesh-placed device state must carry its NamedSharding into
            # the spec: lowering without it compiles a DIFFERENT program
            # than the eager call on sharded engines, so precompile would
            # populate the persistent cache with executables warmup (and
            # serving) never hit (review r5 drift-guard finding)
            sh = getattr(a, "sharding", None)
            if isinstance(sh, NamedSharding):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
            return jax.ShapeDtypeStruct(shape, dtype)

        def spec(x):
            return jax.tree.map(lambda a: sds(a.shape, a.dtype, a), x)

        B, Bp = self.max_batch, self.prefill_batch
        params_s, cache_s = spec(self.params), spec(self.cache)
        lt_s = spec(self._last_tokens)
        llp_s = spec(self._last_lps)
        keys_B = spec(self._base_keys_np)
        key_dt = self._base_keys_np.dtype
        f32_B, i32_B = sds((B,), np.float32), sds((B,), np.int32)
        plan: List[Tuple[Any, Tuple[Any, ...]]] = []
        if self._role_warms_decode():
            for decode in self._decode_variants:
                plan.append((decode, (params_s, lt_s, llp_s, i32_B,
                                      cache_s, keys_B, f32_B, i32_B,
                                      f32_B)))
        if self._use_resident() and self._role_warms_decode():
            # resident sessions carry host callbacks, which jax refuses
            # to serialize into the persistent cache — the AOT compile
            # still validates the specs, and warmup's jit execution adds
            # zero persistent entries either way (drift test invariant)
            bool_B = sds((B,), np.bool_)
            for fn in self._resident_variants:
                plan.append((fn, (params_s, lt_s, llp_s, i32_B, cache_s,
                                  keys_B, f32_B, i32_B, f32_B, i32_B,
                                  bool_B, sds((), np.int32))))

        keys_Bp = sds((Bp,) + self._base_keys_np.shape[1:], key_dt)
        i32_Bp, f32_Bp = sds((Bp,), np.int32), sds((Bp,), np.float32)
        if self._ragged_active() and self._role_warms_prefill():
            maxp = self.paged.allocator.maxp
            keys_R = sds((B,) + self._base_keys_np.shape[1:], key_dt)
            for wd in self._ragged_widths:
                w_i32 = sds((wd,), np.int32)
                plan.append((self._prefill_ragged_fused, (
                    params_s, w_i32, w_i32, w_i32, i32_B, i32_B, i32_B,
                    sds((B, maxp), np.int32), i32_B, cache_s["k"],
                    cache_s["v"], lt_s, llp_s, keys_R, f32_B, i32_B,
                    f32_B,
                    *((i32_B, i32_B,
                       *((i32_B,) if self._snapshots else ()),
                       cache_s["state"],
                       cache_s["page_state"]) if self._stateful else ()))))
        for bucket in self.prefill_buckets:
            if not self._role_warms_prefill():
                break  # fleet decode lanes admit via resume delta-prefill
            tok = sds((Bp, bucket), np.int32)
            if self.paged:
                if self._ragged_active():
                    continue
                chunks = -(-bucket // self.paged.page_size)
                if self._packed_active():
                    _, _, R = self._packed_geometry()
                    keys_R = sds((R,) + self._base_keys_np.shape[1:],
                                 key_dt)
                    plan.append((self._prefill_paged_packed, (
                        params_s, sds((R, bucket), np.int32),
                        sds((R,), np.int32), sds((R, chunks), np.int32),
                        sds((R,), np.int32), cache_s["k"], cache_s["v"],
                        lt_s, llp_s, keys_R, sds((R,), np.float32),
                        sds((R,), np.int32), sds((R,), np.float32))))
                    continue
                for rb in self._row_buckets:
                    keys_rb = sds((rb,) + self._base_keys_np.shape[1:],
                                  key_dt)
                    i32_rb, f32_rb = (sds((rb,), np.int32),
                                      sds((rb,), np.float32))
                    plan.append((self._prefill_paged_fused, (
                        params_s, sds((rb, bucket), np.int32), i32_rb,
                        sds((rb, chunks), np.int32), i32_rb,
                        cache_s["k"], cache_s["v"], lt_s, llp_s,
                        keys_rb, f32_rb, i32_rb, f32_rb)))
            else:
                plan.append((self._prefill_fused, (
                    params_s, tok, i32_Bp, i32_Bp, cache_s, lt_s, llp_s,
                    keys_Bp, f32_Bp, i32_Bp, f32_Bp)))
        if self._prefix is not None:
            for bucket in self.prefill_buckets:
                for ppb in self._prefix_pp_buckets:
                    tok = sds((Bp, bucket), np.int32)
                    table = sds((Bp, ppb), np.int32)
                    if self.paged:
                        chunks = -(-bucket // self._prefix_ps)
                        if (not self._ragged_active()
                                and self._role_warms_prefill()):
                            for rb in self._row_buckets:
                                keys_rb = sds(
                                    (rb,) + self._base_keys_np.shape[1:],
                                    key_dt)
                                i32_rb, f32_rb = (sds((rb,), np.int32),
                                                  sds((rb,), np.float32))
                                plan.append(
                                    (self._prefill_paged_prefix_fused, (
                                        params_s,
                                        sds((rb, bucket), np.int32),
                                        i32_rb, i32_rb,
                                        sds((rb, ppb), np.int32),
                                        sds((rb, chunks), np.int32),
                                        i32_rb, cache_s["k"],
                                        cache_s["v"], lt_s, llp_s,
                                        keys_rb, f32_rb, i32_rb, f32_rb)))
                        if self._warm_resume():
                            maxp = self.paged.allocator.maxp
                            plan.append((self._prefill_paged_resume_fused, (
                                params_s, tok, i32_Bp, i32_Bp, table,
                                sds((Bp, maxp), np.int32), i32_Bp,
                                cache_s["k"], cache_s["v"], lt_s, llp_s,
                                keys_Bp, f32_Bp, i32_Bp, f32_Bp)))
                    elif self._role_warms_prefill():
                        lane_pages = min(ppb + -(-bucket // self._prefix_ps),
                                         self.max_seq // self._prefix_ps)
                        reg = sds((Bp, lane_pages), np.int32)
                        plan.append((self._prefill_prefix_fused, (
                            params_s, tok, i32_Bp, i32_Bp, table, reg, reg,
                            i32_Bp, cache_s, lt_s, llp_s,
                            spec(self._prefix_pool[0]),
                            spec(self._prefix_pool[1]),
                            keys_Bp, f32_Bp, i32_Bp, f32_Bp)))
        return plan

    def precompile(self, parallel: int = 4) -> float:
        """AOT-compile every warmup variant with ``parallel`` threads and
        return seconds spent. Compilation releases the GIL (XLA C++ /
        the remote compile service), so independent variants overlap;
        with the persistent cache on (utils/xla_cache.py) each compiled
        executable is serialized to disk, and warmup()'s subsequent jit
        executions — and any serving-path call — deserialize it instead
        of recompiling. Pure compile: nothing executes on the device, so
        engine state (cache donation lifecycle included) is untouched."""
        t0 = time.time()
        plan = self.warmup_call_plan()

        def lower_one(item):
            fn, specs = item
            fn.lower(*specs).compile()

        if parallel > 1:
            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=parallel) as ex:
                # surface the first failure instead of swallowing it
                list(ex.map(lower_one, plan))
        else:
            for item in plan:
                lower_one(item)
        dt = time.time() - t0
        logger.info("precompiled %d variants with %d threads in %.1fs",
                    len(plan), parallel, dt)
        return dt

    # ------------------------------------------------------------ submission

    def submit(self, request: GenRequest) -> str:
        """Thread-safe enqueue; returns the request id."""
        if request.resume_len + len(request.prompt) >= self.max_seq:
            raise ValueError(
                f"prompt length {request.resume_len + len(request.prompt)} "
                f"(incl. resumed) >= max_seq {self.max_seq}"
            )
        if request.resume_pages is not None:
            if self._stateful:
                raise NotImplementedError(
                    "a configuration with conv state cannot resume from "
                    "kept pages: a rolling resume, a tier promotion and a "
                    "fleet handoff bring pages back without the conv state "
                    "at their end")
            if self._latent:
                raise NotImplementedError(
                    "a configuration with latent pages cannot resume from "
                    "kept pages: the rolling resume prefill, swarmtier's "
                    "host store and the fleet handoff move pages of keys "
                    "and values a head, not rows without a heads axis")
            if not self.supports_rolling():
                raise ValueError("resume_pages requires the rolling-KV "
                                 "machinery (paged+resume prefill, or a "
                                 "dense engine with the prefix cache)")
            if self._mh is not None:
                # pod mode mirrors the resume DISPATCH fine (CALL_PAGED_
                # RESUME_PREFILL), but page custody lives in the serving
                # layer's registry, and a pod failure recovers by process
                # restart — which silently orphans/aliases every resumed
                # page id. Refuse until registry state is pod-durable.
                raise ValueError("rolling-KV resume is not supported in "
                                 "multi-host (pod) mode")
            if not request.resume_pages or request.resume_len <= 0:
                raise ValueError("resume needs pages and resume_len > 0")
            ps = self.rolling_page_size()
            if len(request.resume_pages) > self._prefix_pp_buckets[-1]:
                raise ValueError(
                    f"{len(request.resume_pages)} resume pages exceed the "
                    f"widest prefix-gather bucket "
                    f"{self._prefix_pp_buckets[-1]}")
            if -(-request.resume_len // ps) != len(request.resume_pages):
                raise ValueError("resume_pages must exactly cover "
                                 "resume_len")
            if (request.resume_epoch is not None
                    and request.resume_epoch != self.pool_epoch()):
                raise ValueError(
                    "stale resume epoch: the page pool was rebuilt since "
                    "these pages were planned (engine restart); the "
                    "conversation must restart fresh"
                )
        if request.keep_pages and self._mh is not None:
            # the dense keep-retirement extraction (_extract_lane_fused)
            # is not a mirrored call, so it would silently desync worker
            # prefix pools; and kept custody is useless in a pod anyway
            # (resume is refused above). Refuse symmetrically (review r5).
            raise ValueError("rolling-KV keep_pages is not supported in "
                             "multi-host (pod) mode")
        if self.paged:
            need = self.paged.allocator.pages_needed(
                len(request.prompt), request.sampling.max_new_tokens,
                self.decode_chunk,
            )
            # per-SLOT capacity, not the global pool: a DP-sharded slot can
            # only draw from its own shard's sub-pool, and an uncoverable
            # request at the queue head wedges the no-skip-ahead admission
            # forever (review finding)
            cap = self.paged.allocator.slot_capacity()
            if need > cap:
                raise ValueError(
                    f"request needs {need} KV pages but a slot can hold at "
                    f"most {cap}; raise num_pages or shorten"
                )
        with self._cv:
            heapq.heappush(
                self._queue,
                (-request.priority, request.submitted_at,
                 next(self._tiebreak), request),
            )
            self._cv.notify_all()
            ses = self._resident
            if ses is not None and ses.consuming:
                # the engine thread waits on the FIFO between a session's
                # blocks: wake it to plan this admission while the chunk
                # runs (under _cv, as the session's end clears _resident:
                # no wake is left behind for the next session)
                self._resident_fifo.put(_PLAN_WAKE)
        return request.request_id

    def cancel(self, request_id: str) -> bool:
        """Stop a request early (client disconnect, stop-sequence match).

        Queued requests are removed immediately (their ``on_done`` fires
        with reason "cancelled"); an ACTIVE request's slot is flagged and
        retires when the engine processes its next token block — its lane
        computes at most one more chunk of garbage, exactly like a natural
        EOS mid-chunk. Returns False for unknown/finished ids (cancel of a
        completed request is a no-op, not an error — the races are
        inherent). Thread-safe."""
        with self._cv:
            for i, item in enumerate(self._queue):
                if item[3].request_id == request_id:
                    req = item[3]
                    del self._queue[i]
                    heapq.heapify(self._queue)
                    break
            else:
                req = None
            held = self._held_plan
            if req is None and held is not None:
                # planned ahead and still waiting for its round: it goes
                # as a queued request goes, and gives back what the plan
                # took for it
                for j, r in enumerate(held.popped):
                    if r.request_id == request_id:
                        req = self._drop_planned(held, j)
                        break
            if req is None:
                if request_id in self._admitting:
                    # popped but not yet activated (prefill in flight, can
                    # take seconds on a cold compile): flag for _activate
                    self._cancel_pending.add(request_id)
                    self.metrics.counters["engine_cancelled"].inc()
                    return True
                for slot in self.slots:
                    if (slot.active and slot.request is not None
                            and slot.request.request_id == request_id):
                        slot.cancelled = True
                        self.metrics.counters["engine_cancelled"].inc()
                        return True
                return False
        # queued removal: fire completion outside the lock (callbacks may
        # re-enter submit()/stats())
        self.metrics.counters["engine_cancelled"].inc()
        if req.on_done is not None:
            try:
                req.on_done(req.request_id, [], "cancelled")
            except Exception:
                logger.exception("on_done callback failed")
        return True

    def generate_sync(self, prompt: List[int], sampling: SamplingParams,
                      timeout: float = 120.0) -> Tuple[List[int], str]:
        """Blocking convenience API (tests, benches)."""
        done = threading.Event()
        result: Dict[str, Any] = {}

        def on_done(rid, toks, reason):
            result["tokens"] = toks
            result["reason"] = reason
            done.set()

        self.submit(GenRequest(prompt=prompt, sampling=sampling, on_done=on_done))
        if not done.wait(timeout):
            raise TimeoutError("generation timed out")
        return result["tokens"], result["reason"]

    # ------------------------------------------------------------- the loop

    def _run(self) -> None:  # swarmlint: hot
        # (token block, logprob block, snapshot, dispatch stamp, decode
        # variant[, routing block]) per chunk
        in_flight: List[Tuple[Any, ...]] = []
        tracer = self.tracer
        while True:
            self._in_step = False
            self._beat()
            self._loop_step += 1
            t_wait = 0
            with self._cv:
                while (not self._stop and not self._queue
                       and self._held_plan is None
                       and not self._any_active() and not in_flight
                       and not self._chaos_pending()):
                    # idle engines must still beat or the supervisor
                    # would read "no work" as "wedged"; the tick bounds
                    # idle beat staleness well under any sane suspect
                    # threshold. An armed chaos fault exits the wait so
                    # it lands at the seam below (outside the lock) even
                    # on an idle lane.
                    if not t_wait:
                        t_wait = tracer.phase_begin("engine.wait")
                    self._beat()
                    self._cv.wait(timeout=0.25)
                stopping = self._stop
            if t_wait:
                tracer.phase_end(t_wait, "engine.wait", cat="engine",
                                 args={"step": self._loop_step})
            cs = self.chaos_step
            if stopping or cs is not None:
                # a boundary block still to be delivered: before the loop
                # ends, and before a fault may end it
                self._deliver_pending()
            if stopping:
                # a plan made ahead goes back to the queue it came from
                self._release_held_plan(requeue=True)
                # drain dispatched chunks so their requests complete
                # instead of hanging to their callers' timeouts — OUTSIDE
                # the lock: processing blocks on the device and runs user
                # callbacks, either of which under _cv could deadlock a
                # thread re-entering submit()/stop()
                for entry in in_flight:
                    try:
                        self._process_block(*entry)
                    except Exception:
                        logger.exception("drain on stop failed")
                in_flight.clear()
                break
            if cs is not None:
                # fault-injection seam (backend/chaos.py): kill raises
                # LaneKilled (BaseException — deliberately NOT caught by
                # the recovery handler below, the thread dies); wedge
                # blocks here, starving the liveness beat
                cs(self)
            self._in_step = True
            try:
                self._admission_round()
                if self._role == "prefill":
                    # fleet prefill lanes retire admission-only requests
                    # straight off the prefill sample — decode never runs
                    # for them, so the lane's whole duty is prefill waves
                    self._drain_prefill_only()
                if self._use_resident():
                    # device-resident session: the while_loop runs chunks
                    # until all lanes finish or the host votes to stop
                    # (admissible work / cancel) through the emission
                    # ring's callback return; ONE host sync per session.
                    # The flight step is recorded POST-admission, PRE-
                    # session: a session boundary is the one moment
                    # occupancy is transiently low (retired slots not yet
                    # refilled), and sampling only there would read a
                    # fully-loaded engine as stalled-with-free-slots
                    # (admission_stall_frac would be garbage).
                    self._flight_step(0)
                    if self._any_active():
                        self._run_resident()
                    else:
                        # a boundary block that no session follows
                        self._deliver_pending()
                    continue
                if self._any_active():
                    in_flight.append(self._dispatch_decode())
                while in_flight and (len(in_flight) >= self.pipeline_depth
                                     or not self._any_active()):
                    self._process_block(*in_flight.pop(0))
                self._flight_step(len(in_flight))
            except Exception:
                in_flight.clear()
                logger.exception("engine step failed; failing active requests")
                self.flight.auto_dump("engine_error", self._flight_dir)
                self._fail_all("engine_error")
                if self._mh is not None:
                    # Pod mode: workers may have executed an op this
                    # coordinator failed mid-way, and a local state rebuild
                    # cannot be mirrored to them (their cache would silently
                    # diverge and corrupt every later TP/EP reduction).
                    # Fail the pod loudly; recovery is a process restart.
                    logger.error("multi-host engine failure is fatal; "
                                 "stopping the pod decode program")
                    with self._cv:
                        self._stop = True
                    try:
                        self._mh.publish_stop()
                    except Exception:
                        logger.exception("pod stop broadcast failed")
                    # workers have exited their loop: a second stop
                    # broadcast from Engine.stop() would be a collective
                    # with no peers and hang shutdown
                    self._mh = None
                    break
                # the decode step donates the cache buffer (and the fed-token
                # vector is donated through _set_last_token): if it raised
                # mid-step they may reference deleted buffers — rebuild both
                # so the engine survives the error
                try:
                    with self._device_ctx():
                        self._last_tokens = jnp.zeros((self.max_batch,),
                                                      jnp.int32)
                        self._last_lps = jnp.zeros((self.max_batch,),
                                                   jnp.float32)
                    self.cache = self._fresh_cache()
                    if self._prefix is not None:
                        # the rebuilt pool is zeroed and (paged) its pages
                        # are back on the free list: stale chain entries
                        # would hit zeroed or REUSED pages — forget all
                        # (mirrors restart())
                        if not self.paged:
                            self._prefix_pool = self._prefix_init_pool(
                                self._prefix.num_pages, self._prefix_ps)
                        self._prefix.reset()
                        self._slot_prefix_pins.clear()
                except Exception:
                    logger.exception("cache re-init failed; stopping engine")
                    with self._cv:
                        self._stop = True

    def _any_active(self) -> bool:
        return any(s.active for s in self.slots)

    def _compiled_count(self) -> int:
        """Total compiled-executable count across the engine's jit entry
        points (jax's per-wrapper cache sizes). A step-over-step increase
        in the flight record is a RECOMPILE landing mid-traffic — the
        exact stall class warmup exists to prevent."""
        fns: List[Any] = list(self._decode_variants)
        if self._resident_variants is not None:
            fns.extend(self._resident_variants)
        for name in ("_prefill_fused", "_prefill_paged_fused",
                     "_prefill_paged_packed", "_prefill_paged_prefix_fused",
                     "_prefill_paged_resume_fused", "_prefill_prefix_fused",
                     "_prefill_ragged_fused", "_extract_lane_fused"):
            fn = getattr(self, name, None)
            if fn is not None:
                fns.append(fn)
        n = 0
        for fn in fns:
            size = getattr(fn, "_cache_size", None)
            if callable(size):
                try:
                    n += int(size())
                except Exception:  # private API; absence is not an error
                    pass
        return n

    def _flight_step(self, in_flight_n: int) -> None:  # swarmlint: hot
        """One flight-recorder step record per engine-loop iteration that
        has work (idle iterations are skipped so the ring's last-N steps
        describe the crash window, not hours of quiet)."""
        if self.sentinel is not None:
            # window-close probe: one compare per engine step (the close
            # itself is rare and runs off the sentinel's own snapshot)
            self.sentinel.maybe_tick()
        with self._cv:
            queued = len(self._queue)
            by_prio: Dict[int, int] = {}
            for negp, _, _, _ in self._queue:
                by_prio[-negp] = by_prio.get(-negp, 0) + 1
        active = sum(1 for s in self.slots if s.active)
        has_work = bool(active or queued or in_flight_n)
        if not has_work and not self._flight_last_had_work:
            return
        # one trailing record after work drains: the ring's final step
        # then carries the SETTLED counters (a dump taken while idle
        # matches the metrics registry exactly)
        self._flight_last_had_work = has_work
        compiled = self._compiled_count()
        if compiled > self._compiled_seen:
            # a program compiled since the last step: the stall class
            # warm-up exists to prevent, marked where it landed
            self.tracer.instant(
                "engine.compile", cat="engine",
                args={"step": self._loop_step,
                      "delta": compiled - self._compiled_seen})
        self._compiled_seen = compiled
        c = self.metrics.counters
        rec: Dict[str, Any] = {
            "ts": time.time(),
            "active": active,
            "max_batch": self.max_batch,
            "queued": queued,
            "queued_by_priority": by_prio,
            "in_flight_chunks": in_flight_n,
            # cumulative counters: deltas between steps localize where
            # tokens/padding/syncs happened in time
            "tokens_generated": c["tokens_generated"].value,
            "prompt_tokens": c["prompt_tokens"].value,
            "prefill_padding_tokens": c["prefill_padding_tokens"].value,
            "prefill_packed_tokens": c["prefill_packed_tokens"].value,
            "host_syncs": c["engine_host_syncs"].value,
            "restarts": c["engine_restarts"].value,
            "compiled_variants": compiled,
        }
        if self._last_wave_kind is not None:
            # which prefill family served the most recent wave (ragged
            # packed stream vs bucketed dense batch)
            rec["wave_kind"] = self._last_wave_kind
        if self._decode_kernel is not None:
            # which decode-attention path serves this engine (pallas
            # kernel vs XLA page gather) — the analyzer needs it to
            # attribute kernel-vs-gather regressions across records
            rec["decode_kernel"] = self._decode_kernel
        if self._use_resident():
            # evidence-quality marker for the analyzer's stall split:
            # resident-path steps sample occupancy right AFTER admission
            # (the loop records pre-session), so active-vs-queued is a
            # trustworthy admission-stall signal; scan-path steps sample
            # mid-pipeline and stay unmarked (analyze._queue_split only
            # trusts marked dumps)
            rec["occ_at_admit"] = True
        if self.flight_shard is not None:
            rec["shard"] = self.flight_shard
        if self._prefix is not None:
            ps = self._prefix.stats()
            rec["prefix_hit_tokens"] = ps["hit_tokens"]
            rec["prefix_miss_tokens"] = ps["miss_tokens"]
        if (self.paged is not None
                and getattr(self.paged.allocator, "n_shards", 1) > 1):
            # DP-sharded pool: per-shard occupancy — the dpx=0.22 class
            # of mystery is usually one starved/overloaded shard
            shard_of = self.paged.allocator.shard_of
            by_shard: Dict[int, int] = {}
            for i, s in enumerate(self.slots):
                if s.active:
                    sh = shard_of(i)
                    by_shard[sh] = by_shard.get(sh, 0) + 1
            rec["active_by_shard"] = by_shard
        self.flight.record_step(rec)

    def _age_queue(self) -> None:  # swarmlint: hot
        """Bounded anti-starvation for priority admission (BENCH_r05
        diagnosis): the heap ORDERING — (-priority, submitted_at,
        tiebreak) — is correct, but under a saturating arrival stream
        strict priority leaves LOW waiting unboundedly (p50 TTFT 13.55 s
        vs 2.62 s for CRITICAL on the swarm100 closed loop; the request
        timelines show the whole gap is queue wait). Every ``aging_s``
        seconds a request waits, it COMPETES one priority class higher —
        the effective class is recomputed from wait time (idempotent
        across passes; ``req.priority`` itself is never mutated) and ties
        within a class still break on ``submitted_at``, so an aged LOW
        outranks younger requests of its effective class. Wait is thus
        bounded by ~(3 - priority) * aging_s + the class-3 backlog."""
        if self._aging_s <= 0:
            return
        now = time.time()
        with self._cv:
            if not self._queue:
                return
            changed = False
            for i, (negp, sub, tb, req) in enumerate(self._queue):
                boost = int((now - sub) / self._aging_s)
                if boost <= 0:
                    continue
                eff = min(3, req.priority + boost)
                if eff > -negp:
                    self._queue[i] = (-eff, sub, tb, req)
                    changed = True
            if changed:
                heapq.heapify(self._queue)
                self.metrics.counters["engine_priority_aged"].inc()

    def _free_slot_ids(self) -> List[int]:  # swarmlint: hot
        free = [i for i, s in enumerate(self.slots) if not s.active]
        if (free and self.paged is not None
                and getattr(self.paged.allocator, "n_shards", 1) > 1):
            # DP-sharded pool: id-order admission would pile every light-
            # load request onto shard 0 (slot->shard affinity binds a
            # slot's pages to its shard's SUB-pool), exhausting one
            # sub-pool while the others sit empty. Interleave the free
            # list across shards — rotated by an admission counter so a
            # strictly SERIAL stream (slot 0 always free again by the
            # next admission) also spreads, instead of re-landing every
            # request and its prefix-cache registrations on shard 0.
            alloc = self.paged.allocator
            by_shard: Dict[int, List[int]] = {}
            for i in free:
                by_shard.setdefault(alloc.shard_of(i), []).append(i)
            lanes = list(by_shard.values())
            rot = self._admit_rr % len(lanes)
            self._admit_rr += 1
            lanes = lanes[rot:] + lanes[:rot]
            free = [lane[k] for k in range(max(map(len, lanes)))
                    for lane in lanes if k < len(lane)]
        return free

    # ------------------------------------------------------------- admission

    def _expire_deadlines(self) -> None:  # swarmlint: hot
        """Fail QUEUED requests whose deadline already passed with reason
        "deadline" (final, not retryable): serving them would stream into
        a client that stopped waiting, and admitting them burns pool
        pages higher-priority live requests need. In-flight requests are
        never cut mid-stream — the supervisor's deadline watch cancels
        those at chunk granularity."""
        now = time.time()
        expired: List[GenRequest] = []
        with self._cv:
            held = self._held_plan
            if held is not None:
                # planned ahead, not admitted yet: still a queued request
                for j in reversed(range(len(held.popped))):
                    req = held.popped[j]
                    if req.deadline is not None and now > req.deadline:
                        expired.append(self._drop_planned(held, j))
            if self._queue:
                keep = []
                for item in self._queue:
                    req = item[3]
                    if req.deadline is not None and now > req.deadline:
                        expired.append(req)
                    else:
                        keep.append(item)
                if len(keep) < len(self._queue):
                    self._queue[:] = keep
                    heapq.heapify(self._queue)
        for req in expired:
            self.metrics.counters["requests_deadline_expired"].inc()
            if req.on_done is not None:
                try:
                    req.on_done(req.request_id, [], "deadline")
                except Exception:
                    logger.exception("on_done callback failed")

    def _pool_headroom(self) -> float:
        """Fraction of the page pool still claimable by admission: free
        pages plus UNPINNED prefix-cache pages (the cache fills the pool
        by design — counting cached-but-evictable pages as used would
        read a healthy warm cache as pressure)."""
        free = self.paged.allocator.free_count()
        if self._prefix is not None:
            free += self._prefix.evictable_count()
        cap = max(1, self.paged.num_pages - 1)  # page 0 is trash
        return min(1.0, free / cap)

    def _backpressure_gate(self) -> bool:  # swarmlint: hot
        """Watermark hysteresis over pool utilization; returns True when
        admission may proceed. Paused admission still reclaims retired
        pages (the caller runs the pending-free flush first) and still
        fires the pool-pressure hook, so parked rolling conversations
        get evicted instead of deadlocking the pause."""
        if self.paged is None or self._bp_high >= 1.0:
            return True
        util = 1.0 - self._pool_headroom()
        # tiered-KV demote band (ISSUE 19): same hysteresis shape as the
        # pause band but one rung lower — start spilling cold
        # conversations to the warm tier BEFORE admission pauses, stop
        # once utilization falls back under the low watermark. The hook
        # only signals the tier worker (no device work in the gate).
        if self.on_tier_pressure is not None and self._bp_demote < 1.0:
            if self._tier_demoting:
                if util <= self._bp_low:
                    self._tier_demoting = False
            elif util >= self._bp_demote:
                self._tier_demoting = True
                self.tracer.instant("tier.pressure", cat="engine",
                                    args={"util": round(util, 3)})
            if self._tier_demoting:
                cap = max(1, self.paged.num_pages - 1)
                need = max(1, int((util - self._bp_low) * cap))
                try:
                    self.on_tier_pressure(need)
                except Exception:
                    logger.exception("tier-pressure callback failed")
        if self._bp_paused:
            if util <= self._bp_low:
                self._bp_paused = False
                self.metrics.counters["engine_admission_resumed"].inc()
                self.flight.record_event(
                    {"kind": "pool.backpressure_resumed",
                     "util": round(util, 3), "shard": self.flight_shard})
                return True
        elif util >= self._bp_high:
            self._bp_paused = True
            self.metrics.counters["engine_admission_paused"].inc()
            self.flight.record_event(
                {"kind": "pool.backpressure_paused",
                 "util": round(util, 3), "shard": self.flight_shard})
            self.tracer.instant("pool.backpressure", cat="engine",
                                args={"util": round(util, 3)})
        if not self._bp_paused:
            return True
        # paused: free what can be freed, shed what must be shed
        if self.on_pool_pressure is not None:
            cap = max(1, self.paged.num_pages - 1)
            need = max(1, int((util - self._bp_low) * cap))
            try:
                self.on_pool_pressure(need)
            except Exception:
                logger.exception("pool-pressure callback failed")
        if util >= self._bp_shed:
            self._shed_lowest()
        return False

    def _shed_lowest(self) -> None:  # swarmlint: hot
        """Past the hard watermark: return the lowest-priority queued
        class with retryable reason "shed" so the higher classes drain
        the remaining pool first. Priority-aware by construction — a
        homogeneous queue sheds nothing (there is no lower-priority work
        to sacrifice; deadlines bound those waits instead)."""
        shed: List[GenRequest] = []
        with self._cv:
            if len(self._queue) < 2:
                return
            prios = {-negp for negp, _, _, _ in self._queue}
            if len(prios) < 2:
                return
            lowest = min(prios)
            keep = []
            for item in self._queue:
                if -item[0] == lowest:
                    shed.append(item[3])
                else:
                    keep.append(item)
            self._queue[:] = keep
            heapq.heapify(self._queue)
        for req in shed:
            self.metrics.counters["requests_shed"].inc()
            self.flight.record_event(
                {"kind": "pool.request_shed", "rid": req.request_id,
                 "priority": req.priority, "shard": self.flight_shard})
            if req.on_done is not None:
                try:
                    req.on_done(req.request_id, [], "shed")
                except Exception:
                    logger.exception("on_done callback failed")

    # swarmlint: holds[self._cv]
    def _waiting(self) -> int:
        """Requests not admitted yet: the queue's, and those a plan made
        ahead took off it, which still wait for their session to end."""
        held = self._held_plan
        return len(self._queue) + (len(held.popped) if held else 0)

    # swarmlint: holds[self._cv]
    def _drop_planned(self, plan: _AdmissionPlan, j: int) -> GenRequest:
        """Take request ``j`` out of a held plan, under ``_cv``, and give
        back what the plan took for it: its pinned hits, and its slot's
        pages by way of the reclaim (as a retired slot's). Returns it;
        what becomes of it (requeued, failed, cancelled) is the
        caller's."""
        req = plan.popped.pop(j)
        plan.entries.pop(j)
        slot_id, _row = plan.rows.pop(j)
        hits, _chains = plan.plans.pop(slot_id, ((), None))
        plan.hit_routing.pop(slot_id, None)
        self._admitting.discard(req.request_id)
        self._cancel_pending.discard(req.request_id)
        self.paged.allocator.mark_retired(slot_id)
        if hits:
            self._prefix.unpin(hits)
        return req

    def _release_held_plan(self, requeue: bool) -> List[GenRequest]:
        """Undo the plan made ahead, if one is held: every request gives
        back its slot, pages and pins and, with ``requeue``, goes back on
        the queue under the entry it had. Returns the requests."""
        with self._cv:
            plan, self._held_plan = self._held_plan, None
            if plan is None:
                return []
            entries = list(plan.entries)
            for j in reversed(range(len(plan.popped))):
                self._drop_planned(plan, j)
            if requeue:
                for entry in entries:
                    heapq.heappush(self._queue, entry)
        return [entry[3] for entry in entries]

    # swarmlint: hot
    def _plan_ahead(self) -> None:
        """Between two blocks of a resident session (``_PLAN_WAKE``): make
        admission's plan for what is queued now, while the device runs a
        chunk, so that the boundary's round finds it made. Only the
        host's part: pops, slot choice, ``_prefix_plan`` with its pins,
        page allocation. The table rows go with the boundary's reclaim,
        the stamps (``admitted_at``, ``queue_wait_s``, ``engine.admit``,
        ``engine_admitted``) where the wave is dispatched.

        Only where the boundary's round would admit the same requests:
        the gate is open and no tier drain or demotion is under way,
        every queued request is a plain one (no kept pages to resume,
        none past its deadline), the round still holds them all
        (``prefill_batch``), each gets a slot that is free now and owns
        no pages (a slot retired in this session waits for the reclaim),
        and the pool covers all their worst-case pages. Else the queue
        is left alone and the boundary decides, with the freed slots and
        in priority order. A vote counts the held plan's requests as
        queued (``_resident_vote``)."""
        if (not self._queue  # swarmlint: disable=SWL301 -- a peek; the plan locks
                or self.on_tier_drain is not None or self.paged is None
                or not self._ragged_active()):
            return
        alloc = self.paged.allocator
        now = time.time()
        plan = None
        n0 = 0
        t_plan = self.tracer.phase_begin("engine.admission.plan")
        try:
            with self._cv:
                plan = self._held_plan or _AdmissionPlan()
                n0 = len(plan.popped)
                queued = [item[3] for item in self._queue]
                taken = {r[0] for r in plan.rows}
                free = [i for i in self._free_slot_ids()
                        if i not in taken and not alloc.owns(i)]
                room = alloc.free_count() + (
                    self._prefix.evictable_count()
                    if self._prefix is not None else 0)
                if (not queued or not self._gate_open_now()
                        or len(queued) > len(free)
                        or n0 + len(queued) > self.prefill_batch
                        or any(r.resume_pages is not None
                               or (r.deadline is not None
                                   and now > r.deadline) for r in queued)
                        or sum(alloc.pages_needed(
                            len(r.prompt), r.sampling.max_new_tokens,
                            self.decode_chunk) for r in queued) > room):
                    return
                self._plan_pass(plan, free, len(queued))
                self._held_plan = plan
        finally:
            self.tracer.phase_end(
                t_plan, "engine.admission.plan", cat="engine",
                args=self._plan_phase_args(
                    plan, early=True,
                    planned=len(plan.popped) - n0 if plan is not None else 0))

    def _gate_open_now(self) -> bool:
        """Whether ``_backpressure_gate`` would let a round admit as the
        pool stands, read without moving its hysteresis, firing a hook or
        shedding: a plan made ahead may look, the boundary's round
        decides."""
        if self._bp_high >= 1.0:
            return True
        if self._bp_paused or self._tier_demoting:
            return False
        util = 1.0 - self._pool_headroom()
        return util < self._bp_high and (
            self.on_tier_pressure is None or util < self._bp_demote)

    def _admission_round(self) -> None:  # swarmlint: hot
        """One loop step's admission, as the phase ``engine.admission``
        (not ``engine.admit``, which is one request's wait in the queue).
        Its sub-phases ``.reclaim``, ``.plan``, ``.pack`` and ``.dispatch``
        are opened where that work happens."""
        tracer = self.tracer
        t0 = tracer.phase_begin("engine.admission")
        n0 = self.total_requests
        try:
            self._admit()
        finally:
            with self._cv:
                queued = len(self._queue)
            tracer.phase_end(
                t0, "engine.admission", cat="engine",
                args={"step": self._loop_step,
                      "admitted": self.total_requests - n0,
                      "queued_after": queued})

    def _admit(self) -> None:  # swarmlint: hot
        """Move queued requests into free slots (highest priority first) and
        run their prefill in groups of up to ``prefill_batch``.

        Groups are split by bucket so a short prompt co-admitted with a
        long one never pays the long bucket's O(T^2) attention (review
        finding); every popped request is still admitted this round.

        A round that follows a resident session may find part of its plan
        made (``_plan_ahead``, ``_held_plan``): it starts from that, plans
        what arrived since into the same plan, and packs and dispatches
        the whole as one round, so the waves are what one plan at the
        boundary would have made.
        """
        self._age_queue()
        self._expire_deadlines()
        tracer = self.tracer
        gate = True
        if self.paged:
            # reclaim retired slots' pages first: zero their table rows on
            # device (mirrored to pod workers), THEN return pages to the
            # pool (stale-table/reuse race)
            pending = self.paged.allocator.take_pending_frees()
            with self._cv:
                held = self._held_plan
            t_reclaim = (tracer.phase_begin("engine.admission.reclaim")
                         if pending or self.on_tier_drain is not None else 0)
            freed_pages: List[int] = []
            if self._pagecheck is not None:
                for sid in pending:
                    freed_pages.extend(self.paged.allocator.pages_for(sid))
            try:
                # one dispatch, if there is anything to write: a plan made
                # ahead took slots that were free then, none of these, and
                # its rows ride with the zeroing
                self._send_table_rows(held, reclaim=pending)
            except Exception:
                # dispatch failed before the rows were zeroed: freeing
                # would reopen the stale-table race, dropping the drained
                # batch would leak its pages forever (swarmlint SWL801) —
                # requeue and let the engine's error recovery run, the
                # next admission round retries the reclaim
                self.paged.allocator.requeue_pending(pending)
                raise
            self.paged.allocator.release_taken(pending)
            if freed_pages:
                self._pagecheck_poison(freed_pages)
            if self.on_tier_drain is not None:
                # tiered KV (ISSUE 19): execute the tier worker's planned
                # demotions here — the D2H gathers ride the flush wave
                # this round already syncs on, never the decode hot path
                try:
                    self.on_tier_drain()
                except Exception:
                    logger.exception("tier drain failed")
            tracer.phase_end(t_reclaim, "engine.admission.reclaim",
                             cat="engine",
                             args={"step": self._loop_step,
                                   "slots": len(pending)})
            # a held plan passed the gate when it was made: a gate that
            # closes now holds back what arrived since, not the plan
            gate = self._backpressure_gate()
            if not gate and held is None:
                return
        pressure_called = False
        while True:
            stale_resumes: List[GenRequest] = []
            pressure_need = ahead = 0
            plan = None
            # the phase opens before the lock is taken: a wait for _cv is
            # part of what a plan costs
            t_plan = tracer.phase_begin("engine.admission.plan")
            try:
                with self._cv:
                    plan, self._held_plan = (
                        self._held_plan or _AdmissionPlan(), None)
                    ahead = len(plan.popped)
                    if gate:
                        taken = {r[0] for r in plan.rows}
                        free = [i for i in self._free_slot_ids()
                                if i not in taken]
                        take = min(len(free), len(self._queue),
                                   self.prefill_batch - ahead)
                        if take > 0:
                            stale_resumes, pressure_need = (
                                self._plan_pass(plan, free, take))
                        elif not ahead:
                            return
            finally:
                if ahead:
                    self.metrics.counters["admission_planned_ahead"
                                          ].inc(ahead)
                tracer.phase_end(
                    t_plan, "engine.admission.plan", cat="engine",
                    args=self._plan_phase_args(plan, ahead=ahead))
            popped = plan.popped
            # outside the lock: fire callbacks / the pressure hook (either
            # may re-enter submit() or take the serving layer's locks)
            for req in stale_resumes:
                self.metrics.counters["engine_stale_resumes"].inc()
                if req.on_done is not None:
                    try:
                        req.on_done(req.request_id, [], "stale_resume")
                    except Exception:
                        logger.exception("on_done callback failed")
            if not popped:
                if (pressure_need > 0 and not pressure_called
                        and self.on_pool_pressure is not None):
                    # ONE eviction attempt per admission round: the hook
                    # frees idle rolling conversations' pages; if even
                    # that can't cover the head request, fall back to
                    # waiting for retirements as before
                    pressure_called = True
                    try:
                        self.on_pool_pressure(pressure_need)
                    except Exception:
                        logger.exception("pool-pressure callback failed")
                    continue
                if stale_resumes:
                    continue  # stale pops may have unblocked the queue head
                return
            self._dispatch_plan(plan)
            if not gate:
                return

    # swarmlint: hot
    def _plan_phase_args(self, plan: Optional[_AdmissionPlan],
                         **more: Any) -> Dict[str, Any]:
        """The arguments of an ``engine.admission.plan`` phase over
        ``plan`` as it stands (the boundary's: the whole round's plan,
        ``ahead`` of its rows taken from the held one; ``early``: the
        held plan so far)."""
        popped = plan.popped if plan is not None else []
        plans = plan.plans if plan is not None else {}
        # cached: prefix-cache hits (whole pages) and the kept tokens of
        # rolling continuations, whose prompt is the new part only
        hit = (self._prefix_ps * sum(len(p[0]) for p in plans.values())
               if plans else 0)
        return {"step": self._loop_step, "rows": len(popped),
                "cached_tokens": hit + sum(r.resume_len for r in popped),
                "new_tokens": sum(len(r.prompt) for r in popped) - hit,
                # rows seeded from a cached page's state
                "state_rows": sum(bool(p[0]) for p in plans.values())
                if self._stateful else 0, **more}

    # swarmlint: hot
    # swarmlint: holds[self._cv]
    def _plan_pass(self, plan: _AdmissionPlan, free: List[int],
                   take: int) -> Tuple[List[GenRequest], int]:
        """Admission's plan, under ``_cv``: pop up to ``take`` requests in
        priority order into ``plan``, each with a slot of ``free`` and
        (paged) its prefix hits pinned and its pages allocated. Returns
        the stale resumes it popped without a slot and the pages the head
        request lacked where the pool stopped it."""
        entries, popped, rows = plan.entries, plan.popped, plan.rows
        plans, hit_routing = plan.plans, plan.hit_routing
        resume_rows = plan.resume_rows
        stale_resumes: List[GenRequest] = []
        pressure_need = forgone = 0
        # routed configurations: slot -> the routing rows its hit pages
        # were registered with (the head of its record)
        routed = self._routed is not None
        stateful = self._stateful
        if self.paged:
            # admit in priority order while the pool covers each
            # request's worst-case page footprint; stop at the first
            # that doesn't fit (no skip-ahead: prevents starvation
            # of long prompts behind a stream of short ones). With
            # the prefix cache, hit pages are pinned and referenced
            # in place; only the remainder needs fresh pages, and
            # LRU cache pages are evicted into the free list when
            # the pool runs short.
            use_pp = self._prefix is not None
            # candidates = ALL free slots (the wave-size cap
            # bounds how many ADMIT, not which slots are
            # eligible — free[:take] would pre-pick slots
            # positionally and defeat the shard-hint search)
            remaining = list(free)
            admitted = 0
            n_sh = getattr(self.paged.allocator, "n_shards", 1)
            while remaining and self._queue and admitted < take:
                req = self._queue[0][3]
                if (req.resume_pages is not None
                        and req.resume_epoch is not None
                        and req.resume_epoch
                        != self.paged.allocator.generation):
                    # re-validate the resume epoch at ADMISSION,
                    # not just submit (ADVICE r4 #2): a pool
                    # reset while the request sat queued makes
                    # its page ids dangling aliases. No slot is
                    # consumed by a stale pop.
                    heapq.heappop(self._queue)
                    stale_resumes.append(req)
                    continue
                # slot choice: honor the request's shard hint
                # when its shard still has a free slot, so a
                # conversation's turns land where its cached
                # prefix pages live (same-shard-only reuse).
                # Unhinted prefix-eligible requests get a
                # CONTENT-affine default — a stable hash of the
                # first page of tokens — so identical prefixes
                # collide on one shard (cross-request reuse)
                # while distinct prompts still spread.
                slot_id = None
                hint = req.shard_hint
                if (hint is None and n_sh > 1 and use_pp
                        and len(req.prompt) >= self._prefix_ps
                        and not req.keep_pages):
                    hint = zlib.crc32(np.asarray(
                        req.prompt[:self._prefix_ps],
                        np.int32).tobytes())
                if hint is not None and n_sh > 1:
                    h = hint % n_sh
                    for j, sid in enumerate(remaining):
                        if self.paged.allocator.shard_of(sid) == h:
                            slot_id = remaining.pop(j)
                            break
                if slot_id is None:
                    slot_id = remaining.pop(0)
                if req.resume_pages is not None:
                    # rolling-KV continuation: the kept pages are
                    # referenced (caller custody); only the part
                    # past resume_len needs fresh pages
                    ps_ = self.paged.page_size
                    worst = min(
                        self.paged.allocator.max_seq,
                        req.resume_len + len(req.prompt)
                        + req.sampling.max_new_tokens
                        + self.decode_chunk,
                    )
                    total = -(-worst // ps_)
                    n_fresh = max(0,
                                  total - len(req.resume_pages))
                    row = self.paged.allocator.allocate_with_prefix(
                        slot_id, req.resume_pages, n_fresh)
                    if row is None:
                        pressure_need = n_fresh
                        break  # pool exhausted; retry later
                    entries.append(heapq.heappop(self._queue))
                    self._admitting.add(req.request_id)
                    popped.append(req)
                    rows.append((slot_id, row))
                    resume_rows[slot_id] = row
                    admitted += 1
                    continue
                need = self.paged.allocator.pages_needed(
                    len(req.prompt), req.sampling.max_new_tokens,
                    self.decode_chunk,
                )
                row = None
                hits: List[int] = []
                chains: List[bytes] = []
                for attempt in range(2):
                    hits, chains = [], []
                    hit_rows = [] if routed else None
                    hit_states = [] if stateful else None
                    # keep_pages (rolling) requests bypass the
                    # hash prefix cache both ways: a hit would
                    # reference cache-custody pages that
                    # retirement cannot hand to the caller, and
                    # registration would steal the slot's own
                    # pages INTO cache custody
                    if (use_pp and len(req.prompt) >= self._prefix_ps
                            and not req.keep_pages):
                        hits, chains = self._prefix_plan(
                            req.prompt, pin=True,
                            routing=hit_rows, states=hit_states)
                        if stateful:
                            # a row can start only where the
                            # state it resumes from was kept:
                            # the match is cut back to the
                            # deepest page that has its state
                            # and the rest is computed again
                            keep = max((i + 1 for i, st in
                                        enumerate(hit_states)
                                        if st is not None),
                                       default=0)
                            if keep < len(hits):
                                forgone += len(hits) - keep
                                self._prefix.unpin(hits[keep:])
                                hits = hits[:keep]
                            if self._snapshots:
                                # the snapshot slot the row resumes from
                                self._snap_src[slot_id] = (
                                    hit_states[keep - 1] if keep else 0)
                        # DP-sharded pool: a slot can only
                        # reference pages of its own shard (the
                        # shard_map'd decode addresses its local
                        # sub-pool); truncate foreign-shard hits
                        keep = self.paged.allocator.usable_prefix(
                            slot_id, hits)
                        if keep < len(hits):
                            self._prefix.unpin(hits[keep:])
                            hits = hits[:keep]
                    row = self._paged_allocate(
                        slot_id, hits, max(0, need - len(hits)))
                    if row is not None:
                        break
                    if hits:
                        self._prefix.unpin(hits)
                    # the hint is ADVISORY (review r5): a hinted
                    # shard whose sub-pool cannot cover the
                    # request must not head-of-line-block the 7
                    # healthy shards — retry once on the
                    # freest-pooled other free slot
                    if (attempt == 0 and hint is not None
                            and n_sh > 1 and remaining):
                        remaining.append(slot_id)  # still free
                        alt = max(remaining,
                                  key=self.paged.allocator.free_count)
                        remaining.remove(alt)
                        slot_id = alt
                        continue
                    break
                if row is None:
                    pressure_need = max(0, need - len(hits))
                    break  # pool exhausted; retry after retirements
                entries.append(heapq.heappop(self._queue))
                self._admitting.add(req.request_id)
                popped.append(req)
                rows.append((slot_id, row))
                admitted += 1
                if (use_pp and len(req.prompt) >= self._prefix_ps
                        and not req.keep_pages):
                    plans[slot_id] = (hits, chains)
                    if routed:
                        hit_routing[slot_id] = \
                            hit_rows[:len(hits)]
        else:
            for _ in range(take):
                if not self._queue:
                    break
                req = self._queue[0][3]
                if (req.resume_pages is not None
                        and req.resume_epoch is not None
                        and req.resume_epoch != self.pool_epoch()):
                    # dense rolling resume planned against a pool
                    # that has since been rebuilt (same race as
                    # the paged branch above)
                    heapq.heappop(self._queue)
                    stale_resumes.append(req)
                    continue
                entries.append(heapq.heappop(self._queue))
                popped.append(req)
            self._admitting.update(r.request_id for r in popped)
            plan.free = free
        if forgone:
            self.metrics.counters["prefix_state_forgone_tokens"
                                  ].inc(forgone * self._prefix_ps)
        return stale_resumes, pressure_need

    # swarmlint: hot
    def _send_table_rows(self, plan: Optional[_AdmissionPlan],
                         reclaim: Sequence[int] = ()) -> None:
        """One dispatch that writes to the device's page table the rows of
        ``plan`` it does not hold yet and zeroes the rows of the retired
        slots in ``reclaim`` (never a planned slot: a plan takes slots
        that own no pages)."""
        with self._cv:
            new = ([(r, q) for r, q in zip(plan.rows, plan.popped)
                    if r[0] not in plan.sent] if plan is not None else [])
        if self._pagecheck is not None:
            # sanitizer: stamp owners, then verify the canary of every
            # re-allocated page is still intact — an overwritten canary
            # is a write-after-free landing between free and re-allocation
            for (sid, _row), req in new:
                self._pagecheck_admit(sid, req)
        if not new and not len(reclaim):
            return
        vals = np.zeros((len(reclaim) + len(new),
                         self.paged.allocator.maxp), np.int32)
        for j, ((_sid, row), _req) in enumerate(new, len(reclaim)):
            vals[j] = row
        self._mirrored(
            self.CALL_SET_PT_ROWS,
            np.asarray(list(reclaim) + [r[0] for r, _q in new], np.int32),
            vals)
        if new:
            plan.sent.update(r[0] for r, _q in new)

    # swarmlint: hot
    def _dispatch_plan(self, plan: _AdmissionPlan) -> None:
        """Hand a round's plan to the prefill paths: the table rows the
        device lacks, then the groups' packs and dispatches."""
        popped, rows, plans = plan.popped, plan.rows, plan.plans
        hit_routing, resume_rows = plan.hit_routing, plan.resume_rows
        routed = self._routed is not None
        if self.paged:
            self._send_table_rows(plan)
            # warm-tier promotions (ISSUE 19): bulk-insert the host
            # payload into the freshly reserved resume pages BEFORE
            # the resume prefill reads them. Engine thread only —
            # the pools are donated by the prefill jits below.
            for req in popped:
                if req.promote_payload is not None:
                    self._promote_insert(req)
        use_prefix = self._prefix is not None
        ragged = self.paged is not None and self._ragged_active()
        row_by_slot = dict(rows) if self.paged else {}
        groups: Dict[Tuple[Any, int], List[Tuple]] = {}
        ragged_batch: List[Tuple] = []
        prefix_batch: List[Tuple] = []
        resume_batch: List[Tuple] = []
        max_suffix = max_hits = 0
        # paged pops can SKIP a slot (stale resume popped without
        # consuming it), so pair each request with the slot recorded
        # at its allocation, not positionally with `free`
        slot_ids = ([r[0] for r in rows] if self.paged
                    else plan.free[:len(popped)])
        for slot_id, req in zip(slot_ids, popped):
            slot = self.slots[slot_id]
            slot.cached_tokens = req.resume_len
            slot.new_tokens = len(req.prompt)
            if routed:
                # the record starts with what the pool really holds
                # for this row: its hit pages' rows. Kept pages of a
                # rolling resume come without theirs (their custody is
                # the caller's: service registry, tiers, fleet transit)
                slot.routing = hit_routing.get(slot_id, [])
                slot.cached_parts = len(slot.routing)
                slot.routing_complete = req.resume_pages is None
            slot.table_row = row_by_slot.get(slot_id)
            slot.row_pages = (
                int(np.count_nonzero(slot.table_row))
                if self.paged else 0)
            if slot_id in resume_rows:
                resume_batch.append((slot_id, req, resume_rows[slot_id]))
                continue
            if ragged:
                # packed ragged waves absorb BOTH the plain and the
                # prefix-planned rows (a cache hit is just a nonzero
                # prefix_len descriptor); resume rows keep the
                # bucketed path (mid-page custody bookkeeping)
                if use_prefix and slot_id in plans:
                    hits, chains = plans[slot_id]
                else:
                    hits, chains = [], None
                slot.cached_tokens = len(hits) * self.paged.page_size
                slot.new_tokens -= slot.cached_tokens
                ragged_batch.append((slot_id, req, hits, chains,
                                     row_by_slot[slot_id]))
                continue
            if not self.paged and req.resume_pages is not None:
                # dense rolling resume: kept prefix-pool pages compose
                # into the lane (no row-table — the lane IS the slot)
                resume_batch.append((slot_id, req, None))
                continue
            # sub-page prompts (no hit possible, nothing to register)
            # stay on the plain path; everything else goes through the
            # prefix path even on a full miss so its pages get
            # REGISTERED for the next turn. Paged requests were
            # matched (and pinned) during the pop loop above —
            # matching again would double-pin — so route on the plan's
            # existence there.
            if self.paged and self._prefix is not None:
                planned = slot_id in plans
            else:
                planned = (use_prefix
                           and len(req.prompt) >= self._prefix_ps)
            if planned:
                if self.paged:
                    hits, chains = plans[slot_id]
                else:
                    hits, chains = self._prefix_plan(
                        req.prompt, routing=slot.routing)
                    slot.cached_parts = len(hits)
                suffix_len = len(req.prompt) - len(hits) * self._prefix_ps
                slot.cached_tokens = len(hits) * self._prefix_ps
                slot.new_tokens = suffix_len
                prefix_batch.append((slot_id, req, hits, chains))
                max_suffix = max(max_suffix, suffix_len)
                max_hits = max(max_hits, len(hits))
            else:
                key = (self._bucket_for(len(req.prompt)), 0)
                groups.setdefault(key, []).append((slot_id, req))
        if prefix_batch:
            # ONE group per admission wave, padded to the wave's max
            # (suffix bucket, prefix width): prefill cost is dominated
            # by the weight read, so co-dispatching short-suffix rows
            # with long ones is nearly free while per-(bucket, width)
            # splitting multiplies whole-model HBM passes (measured:
            # fragmentation cost more than prefix reuse saved)
            key = (self._bucket_for(max(1, max_suffix)),
                   self._pp_bucket_for(max(1, max_hits)))
            groups[key] = prefix_batch
        if resume_batch:
            # rolling-KV continuations, grouped PER suffix bucket
            # (sentinel -ppb keys route to the resume prefill). The
            # prefix wave's one-group rule does not transfer here:
            # resume deltas are bimodal — a one-turn continuation is
            # a few tokens while a conversation that chatted plain
            # during an in-flight stretch returns with hundreds — and
            # padding the short rows to the deep straggler's bucket
            # multiplies their whole-model pass (measured 290ms vs
            # 10ms at S=512), landing squarely on resume TTFT. The
            # warmup grid already covers every (bucket, width) pair.
            per_bucket: Dict[int, List[Tuple]] = {}
            for item in resume_batch:
                b = self._bucket_for(max(1, len(item[1].prompt)))
                per_bucket.setdefault(b, []).append(item)
            for b, items in per_bucket.items():
                maxp = max(
                    max(1, len(it[1].resume_pages)) for it in items)
                key = (b, -self._pp_bucket_for(maxp))
                groups.setdefault(key, []).extend(items)
        if ragged_batch:
            groups[("ragged", 0)] = ragged_batch
        for (bucket, ppb), batch in groups.items():
            try:
                if bucket == "ragged":
                    self._prefill_ragged_waves(batch)
                elif ppb < 0 and not self.paged:
                    self._prefill_dense_resume_batch(batch, bucket, -ppb)
                elif ppb < 0:
                    self._prefill_paged_resume_batch(batch, bucket, -ppb)
                elif ppb > 0 and self.paged:
                    self._prefill_paged_prefix_batch(batch, bucket, ppb)
                elif ppb > 0:
                    self._prefill_prefix_batch(batch, bucket, ppb)
                else:
                    self._prefill_batch(batch)
            except Exception:
                # the requests are already off the queue and not yet in
                # slots: fail them here or their on_done would never fire
                # (generate_sync / SSE streams would hang to the timeout)
                logger.exception("prefill failed for %s",
                                 [item[1].request_id for item in batch])
                if self._mh is not None:
                    # pod mode: the op may already be published (workers
                    # applied a prefill this coordinator didn't) —
                    # swallowing here would silently desynchronize the
                    # SPMD state; escalate to _run's pod-fatal handler
                    for item in batch:
                        req = item[1]
                        if req.on_done is not None:
                            try:
                                req.on_done(req.request_id, [],
                                            "engine_error")
                            except Exception:
                                pass
                    raise
                for item in batch:
                    slot_id, req = item[0], item[1]
                    with self._cv:
                        self._admitting.discard(req.request_id)
                        self._cancel_pending.discard(req.request_id)
                    if self.paged:
                        # release the slot's pages or the next occupant's
                        # allocate() raises "already holds pages" and the
                        # whole engine fails over (review finding)
                        self.paged.allocator.mark_retired(slot_id)
                        # prefix items carry (slot, req, hits, chains);
                        # resume items carry (slot, req, row ndarray) —
                        # only matched-hit LISTS are pinned
                        if (len(item) > 2 and isinstance(item[2], list)
                                and item[2]):
                            self._prefix.unpin(item[2])  # matched hits
                    if req.on_done is not None:
                        try:
                            req.on_done(req.request_id, [], "engine_error")
                        except Exception:
                            pass


    def _pp_widths(self, maxp: int) -> List[int]:
        """Prefix-PP gather-width buckets (both prefix engines): each
        width multiplies warmup's compile count by |prefill buckets|, so
        long context drops the quarter width — its high-hit-rate regime
        matches near-full prefixes anyway (see the prefill-bucket ladder
        comment in __init__ for the per-compile cost)."""
        widths = ({maxp // 2, maxp - 1} if self._long_context
                  else {maxp // 4, maxp // 2, maxp - 1})
        return sorted({max(1, w) for w in widths})

    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def _rows_for(self, n: int) -> int:
        """Smallest row bucket covering an ``n``-admission wave (the wave
        arrays' leading dimension; [prefill_batch] unless the engine is
        row-bucketed — see __init__)."""
        for rb in self._row_buckets:
            if n <= rb:
                return rb
        return self._row_buckets[-1]

    def _set_slot_key(self, slot_id: int, seed) -> None:
        """Per-request PRNG seed: rewrite the slot's key row (host array;
        the keys ride every dispatch as a numpy argument, so this costs
        nothing on device). None restores the engine-default slot key."""
        if seed is None:
            self._base_keys_np[slot_id] = self._default_keys_np[slot_id]
        else:
            s = int(seed) & 0xFFFFFFFFFFFFFFFF
            self._base_keys_np[slot_id] = (s >> 32, s & 0xFFFFFFFF)

    # ------------------------------------------------------- prefix caching

    def _pp_bucket_for(self, n: int) -> int:
        """Smallest prefix-gather width bucket covering ``n`` hit pages."""
        for b in self._prefix_pp_buckets:
            if n <= b:
                return b
        return self._prefix_pp_buckets[-1]

    def _prefix_plan(self, prompt: List[int], pin: bool = False,
                     routing: Optional[List[Any]] = None,
                     states: Optional[List[Any]] = None):
        """Longest cached prefix for ``prompt`` -> (hit page ids, chain
        hashes for every full prompt page). Hits are capped one page short
        of the prompt so at least one suffix token remains to prefill
        (the sampled first token needs logits). ``pin=True`` (paged mode)
        pins the hits so a later admission in the same round cannot evict
        pages this request's table row is about to reference. ``routing``
        (a routed configuration) receives each hit page's routing rows,
        ``states`` (one with conv state) each hit page's state handle."""
        from ..ops.prefix_cache import page_chains

        ps = self._prefix_ps
        n_full = len(prompt) // ps
        chains = page_chains(prompt, ps, max_pages=n_full)
        cap = n_full - 1 if n_full * ps == len(prompt) else n_full
        cap = min(cap, self._prefix_pp_buckets[-1])
        if cap <= 0:
            return [], chains
        if pin:
            hits = self._prefix.match_and_pin(chains[:cap], prompt, routing,
                                              states)
        else:
            hits = self._prefix.match(chains[:cap], prompt, routing, states)
        return hits, chains

    def _paged_allocate(self, slot_id: int, hits: List[int],
                        n_fresh: int) -> Optional[np.ndarray]:
        """Allocate a paged slot row (= pinned hit pages + fresh pages),
        evicting LRU prefix-cache pages into the allocator's free list
        when the pool runs short. None if still uncoverable."""
        alloc = self.paged.allocator
        if self._prefix is not None:
            # sharded pool: only this slot's shard's free pages count, and
            # only same-shard cache pages are worth evicting (a foreign-
            # shard eviction frees pages this slot can never use — review
            # finding: unfiltered rounds drained the whole cache)
            shortfall = n_fresh - alloc.free_count(slot_id)
            if shortfall > 0:
                evicted = self._prefix.evict_lru(
                    shortfall, want=alloc.evictable(slot_id))
                if evicted:
                    alloc.add_free(evicted)
            return alloc.allocate_with_prefix(slot_id, hits, n_fresh)
        return alloc.allocate(slot_id, n_fresh)

    # ------------------------------------------------- page sanitizer
    # Both helpers run ONLY under SWARMDB_PAGECHECK=1 (self._pagecheck
    # set by the checked-allocator factory) — the flag-off path never
    # reaches them. They are deliberately NOT marked hot: the canary
    # verify is a sanctioned per-admission device sync the sanitizer
    # pays for detection.

    def _pagecheck_poison(self, pages: List[int]) -> None:
        """Stamp freed pages' device K/V with the canary pattern (one
        eager scatter per reclaim batch). Skipped in pod mode — a
        local-only device write would desynchronize the SPMD mirrors."""
        if self._mh is not None or not pages:
            return
        from ..ops.paged_kv import canary_fill

        self.cache["k"], self.cache["v"] = canary_fill(
            self.cache["k"], self.cache["v"], pages)
        self._pagecheck.mark_poisoned(pages)

    def _pagecheck_admit(self, slot_id: int, req: "GenRequest") -> None:
        """Admission-side sanitizer bookkeeping: stamp the slot's owner
        (request id — the aliasing reports name both conversations),
        then verify the canary of every poisoned page this slot was
        just handed is intact. A mismatch means something WROTE to the
        page while it was free — the write-after-free no host-side
        bookkeeping can see."""
        pc = self._pagecheck
        pc.set_owner(slot_id, req.request_id)
        if self._mh is not None:
            return
        fresh = self.paged.allocator.pages_for(slot_id)
        poisoned = pc.poisoned_pages(fresh)
        if not poisoned:
            return
        from ..ops.paged_kv import canary_check

        bad = canary_check(self.cache["k"], self.cache["v"], poisoned)
        if bad:
            pc.canary_violation(
                bad, detail=f"at admission of {req.request_id}")
        pc.clear_poison(poisoned)

    def _promote_insert(self, req: "GenRequest") -> None:
        """Warm-tier promotion (ISSUE 19): bulk-device_put the host-RAM
        payload into the request's freshly reserved resume pages — the
        EXACT storage-width bytes that left the pool at demotion come
        back (``pool_insert_raw``: no requantization), so a resumed
        greedy decode is bit-identical to never having spilled.

        Engine thread only (the pools are donated by engine jits). The
        insert loops a ONE-page jitted scatter over the payload rather
        than batching: a batched insert's shape varies with the
        conversation's page count, and every new count would compile a
        fresh variant — a multi-hundred-ms stall landing exactly on the
        warm-hit TTFT this tier exists to shrink. One fixed-shape
        variant compiles once; per-page dispatches are off the decode
        hot path and cheap."""
        payload, req.promote_payload = req.promote_payload, None
        if payload is None or not req.resume_pages:
            return
        from ..ops.paged_kv import pool_insert_raw

        t0 = time.time()

        def _page(pay, i):
            if isinstance(pay, tuple):
                return tuple(a[:, i:i + 1] for a in pay)
            return pay[:, i:i + 1]

        fn = getattr(self, "_promote_jit", None)
        if fn is None:
            fn = jax.jit(
                pool_insert_raw,
                donate_argnums=(0,) if self._donate_cache else ())
            self._promote_jit = fn
        with self._device_ctx():
            new_k, new_v = self.cache["k"], self.cache["v"]
            for i, pid in enumerate(req.resume_pages):
                ids_arr = jnp.asarray([pid], jnp.int32)
                new_k = fn(new_k, ids_arr, _page(payload[0], i))
                new_v = fn(new_v, ids_arr, _page(payload[1], i))
        self.cache = self._paged_cache_with(new_k, new_v)
        self.metrics.counters["engine_tier_promote_inserts"].inc()
        self.metrics.latencies["tier_promote_s"].observe(
            time.time() - t0)

    # swarmlint: hot
    def _prefill_paged_prefix_batch(self, batch: List[Tuple], bucket: int,
                                    ppb: int) -> None:
        """Paged-pool prefix prefill: gather reused pages in place, forward
        only the suffix, scatter its KV into the slot's fresh pages (the
        reuse boundary is page-aligned, so suffix chunk c maps to fresh
        page c), then REGISTER the prompt's fresh full pages — custody
        moves to the cache with no copy. One fused pool-donating dispatch
        per admission wave (see ``_prefill_paged_prefix_insert``)."""
        t0 = time.time()
        t_pack = self.tracer.phase_begin("engine.admission.pack")
        ps = self._prefix_ps
        Bp = self._rows_for(len(batch))  # row-bucketed wave (lanes)
        chunks = -(-bucket // ps)
        padded = np.full((Bp, bucket), self.pad_id, np.int32)
        lengths = np.ones(Bp, np.int32)
        plens = np.zeros(Bp, np.int32)
        table = np.zeros((Bp, ppb), np.int32)
        target = np.zeros((Bp, chunks), np.int32)
        gather = np.zeros(Bp, np.int64)
        scatter = np.full(Bp, self.max_batch, np.int32)
        reg_records = []
        for row, (slot_id, req, hits, chains) in enumerate(batch):
            prompt = req.prompt
            p0 = len(hits) * ps
            suffix = prompt[p0:]
            padded[row, : len(suffix)] = suffix
            lengths[row] = len(suffix)
            plens[row] = p0
            table[row, : len(hits)] = hits
            gather[row] = slot_id
            scatter[row] = slot_id
            fresh = self.paged.allocator.pages_for(slot_id)
            m = min(len(fresh), chunks)
            target[row, :m] = fresh[:m]
            s = req.sampling
            self._temp[slot_id] = s.temperature
            self._topk[slot_id] = s.top_k
            self._topp[slot_id] = s.top_p
            self._set_slot_key(slot_id, s.seed)
            n_full = len(prompt) // ps
            for page_idx in range(len(hits), n_full):
                f = page_idx - len(hits)
                if f >= len(fresh):
                    break
                reg_records.append(
                    (slot_id, chains[page_idx],
                     tuple(prompt[page_idx * ps:(page_idx + 1) * ps]),
                     fresh[f], (row, slice(f * ps, (f + 1) * ps))))
        self.tracer.phase_end(
            t_pack, "engine.admission.pack", cat="engine",
            args=self._pack_args(padded.size, lengths[:len(batch)].sum()))
        self._mirrored(
            self.CALL_PAGED_PREFIX_PREFILL, padded, lengths, plens, table,
            target, scatter, self._base_keys_np[gather],
            self._temp[gather], self._topk[gather], self._topp[gather],
        )
        wave = self._record_wave_rows(batch, range(len(batch)), lengths)
        self.metrics.counters["prefill_padding_tokens"].inc(
            int(padded.size) - int(lengths[:len(batch)].sum()))
        self.metrics.counters["prefill_packed_tokens"].inc(
            int(lengths[:len(batch)].sum()))
        self._last_wave_kind = "bucketed"
        self._prof.wave("bucketed", bucket,
                        int(lengths[:len(batch)].sum()),
                        int(padded.size) - int(lengths[:len(batch)].sum()),
                        prof_key("prefill.paged_prefix", padded.shape, ppb))
        pins: Dict[int, List[int]] = {}
        for slot_id, chain, toks, page_id, where in reg_records:
            if self._prefix.register(
                    chain, toks, page_id,
                    routing=wave.part(where) if wave is not None else None):
                # custody -> cache; pin while this slot still reads it
                self.paged.allocator.transfer_to_cache(slot_id, [page_id])
                self._prefix.pin([page_id])
                pins.setdefault(slot_id, []).append(page_id)
        for slot_id, req, hits, _chains in batch:
            # unpinned at retirement (together with the matched hits)
            self._slot_prefix_pins[slot_id] = hits + pins.get(slot_id, [])
        self.metrics.counters["prefix_reused_tokens"].inc(int(plens.sum()))
        self._activate([(s, r) for s, r, _, _ in batch], t0)

    # swarmlint: hot
    def _prefill_paged_resume_batch(self, batch: List[Tuple], bucket: int,
                                    ppb: int) -> None:
        """One fused suffix prefill CONTINUING kept conversations
        (rolling KV, GenRequest.resume_pages): attend the kept pages +
        the new tokens, write the new K/V positionally from resume_len
        (mid-page), sample. No hash registration — custody of the kept
        pages stays with the caller's registry."""
        t0 = time.time()
        t_pack = self.tracer.phase_begin("engine.admission.pack")
        Bp = self.prefill_batch
        maxp = self.paged.allocator.maxp
        padded = np.full((Bp, bucket), self.pad_id, np.int32)
        lengths = np.ones(Bp, np.int32)
        rlens = np.zeros(Bp, np.int32)
        table = np.zeros((Bp, ppb), np.int32)
        row_tables = np.zeros((Bp, maxp), np.int32)
        gather = np.zeros(Bp, np.int64)
        scatter = np.full(Bp, self.max_batch, np.int32)
        for r, (slot_id, req, row) in enumerate(batch):
            suffix = req.prompt
            padded[r, : len(suffix)] = suffix
            lengths[r] = len(suffix)
            rlens[r] = req.resume_len
            table[r, : len(req.resume_pages)] = req.resume_pages
            row_tables[r] = row
            gather[r] = slot_id
            scatter[r] = slot_id
            s = req.sampling
            self._temp[slot_id] = s.temperature
            self._topk[slot_id] = s.top_k
            self._topp[slot_id] = s.top_p
            self._set_slot_key(slot_id, s.seed)
        self.tracer.phase_end(
            t_pack, "engine.admission.pack", cat="engine",
            args=self._pack_args(padded.size, lengths[:len(batch)].sum()))
        self._mirrored(
            self.CALL_PAGED_RESUME_PREFILL, padded, lengths, rlens, table,
            row_tables, scatter, self._base_keys_np[gather],
            self._temp[gather], self._topk[gather], self._topp[gather],
        )
        self._record_wave_rows(batch, range(len(batch)), lengths)
        self.metrics.counters["prefill_padding_tokens"].inc(
            int(padded.size) - int(lengths[:len(batch)].sum()))
        self.metrics.counters["prefill_packed_tokens"].inc(
            int(lengths[:len(batch)].sum()))
        self._last_wave_kind = "bucketed"
        self._prof.wave("bucketed", bucket,
                        int(lengths[:len(batch)].sum()),
                        int(padded.size) - int(lengths[:len(batch)].sum()),
                        prof_key("prefill.resume", padded.shape, ppb))
        self.metrics.counters["prefix_reused_tokens"].inc(int(rlens.sum()))
        self._activate([(s, r) for s, r, _ in batch], t0)

    # swarmlint: hot
    def _prefix_fused_dispatch(self, rows, bucket: int, ppb: int,
                               t0: float) -> Optional[WaveRouting]:
        """Shared array build + dispatch for the dense prefix-path
        prefills (_prefill_prefix_batch and _prefill_dense_resume_batch —
        the resume path is the registration-free special case: same
        shapes, same executable, no new compile variants).

        ``rows``: (slot_id, req, suffix_tokens, prefix_len, table_pages,
        reg_pairs) per admission; ``reg_pairs`` = [(lane_col, pool_page)]
        to register (empty for resume). Returns the wave's routing (None
        where the configuration is dense) for the caller's registrations."""
        t_pack = self.tracer.phase_begin("engine.admission.pack")
        ps = self._prefix_ps
        Bp = self.prefill_batch
        lane_pages = min(ppb + -(-bucket // ps), self.max_seq // ps)
        RC = lane_pages
        padded = np.full((Bp, bucket), self.pad_id, np.int32)
        lengths = np.ones(Bp, np.int32)
        plens = np.zeros(Bp, np.int32)
        table = np.zeros((Bp, ppb), np.int32)
        reg_cols = np.full((Bp, RC), -1, np.int32)
        reg_pages = np.zeros((Bp, RC), np.int32)
        gather = np.zeros(Bp, np.int64)
        scatter = np.full(Bp, self.max_batch, np.int32)
        for row, (slot_id, req, suffix, plen, tpages, reg_pairs) in \
                enumerate(rows):
            padded[row, : len(suffix)] = suffix
            lengths[row] = len(suffix)
            plens[row] = plen
            table[row, : len(tpages)] = tpages
            gather[row] = slot_id
            scatter[row] = slot_id
            s = req.sampling
            self._temp[slot_id] = s.temperature
            self._topk[slot_id] = s.top_k
            self._topp[slot_id] = s.top_p
            self._set_slot_key(slot_id, s.seed)
            for r, (page_idx, pid) in enumerate(reg_pairs):
                reg_cols[row, r] = page_idx
                reg_pages[row, r] = pid
        self.tracer.phase_end(
            t_pack, "engine.admission.pack", cat="engine",
            args=self._pack_args(padded.size, lengths[:len(rows)].sum()))
        self._mirrored(
            self.CALL_DENSE_PREFIX_PREFILL, padded, lengths, plens, table,
            reg_cols, reg_pages, scatter, self._base_keys_np[gather],
            self._temp[gather], self._topk[gather], self._topp[gather],
        )
        wave = self._record_wave_rows(rows, range(len(rows)), lengths)
        self.metrics.counters["prefix_reused_tokens"].inc(int(plens.sum()))
        self.metrics.counters["prefill_padding_tokens"].inc(
            int(padded.size) - int(lengths[:len(rows)].sum()))
        self.metrics.counters["prefill_packed_tokens"].inc(
            int(lengths[:len(rows)].sum()))
        self._last_wave_kind = "bucketed"
        self._prof.wave("bucketed", bucket,
                        int(lengths[:len(rows)].sum()),
                        int(padded.size) - int(lengths[:len(rows)].sum()),
                        prof_key("prefill.dense_prefix", padded.shape, ppb))
        self._activate([(r[0], r[1]) for r in rows], t0)
        return wave

    # swarmlint: hot
    def _prefill_dense_resume_batch(self, batch, bucket: int,
                                    ppb: int) -> None:
        """Dense rolling resume: gather each row's KEPT prefix-pool pages,
        compose them into the slot lane with a MID-PAGE boundary
        (compose_prefix_lane / gqa_attention_prefix are token-granular in
        prefix_lens — no page alignment needed), forward only the suffix,
        and register NOTHING (reg_cols = -1 routes the registration
        einsum's writes to the trash page; page custody stays with the
        caller's registry)."""
        self._prefix_fused_dispatch(
            [(slot_id, req, req.prompt, req.resume_len,
              req.resume_pages, [])
             for slot_id, req, _none in batch],
            bucket, ppb, time.time(),
        )

    # swarmlint: hot
    def _prefill_prefix_batch(self, batch, bucket: int,
                              ppb: int) -> None:
        """One fused suffix prefill for a group of admissions sharing a
        (suffix bucket, prefix width) shape: gather reused prefix pages +
        forward ONLY the suffix + compose/insert each row's KV lane +
        register the prompt's fresh full pages — one dispatch, pool- and
        cache-donating. Mirrors ``_prefill_batch``; see
        ``_prefill_prefix_insert`` in ``__init__``."""
        t0 = time.time()
        ps = self._prefix_ps
        rows = []
        reg_records = []
        acquired = []
        for row, (slot_id, req, hits, chains) in enumerate(batch):
            prompt = req.prompt
            p0 = len(hits) * ps
            # register the prompt's fresh FULL pages (their lane content
            # is final — decode writes start at len(prompt), past them)
            n_full = len(prompt) // ps
            new_idx = list(range(len(hits), n_full))
            ids = self._prefix.acquire(len(new_idx)) if new_idx else []
            acquired.extend(ids)
            reg_pairs = list(zip(new_idx, ids))
            for page_idx, pid in reg_pairs:
                reg_records.append(
                    (chains[page_idx],
                     tuple(prompt[page_idx * ps:(page_idx + 1) * ps]), pid,
                     (row, slice(page_idx * ps - p0,
                                 (page_idx + 1) * ps - p0))))
            rows.append((slot_id, req, prompt[p0:], p0, hits, reg_pairs))
        try:
            wave = self._prefix_fused_dispatch(rows, bucket, ppb, t0)
        except Exception:
            for pid in acquired:
                self._prefix.release(pid)
            raise
        for chain, toks, pid, where in reg_records:
            self._prefix.register(
                chain, toks, pid,
                routing=wave.part(where) if wave is not None else None)

    # swarmlint: hot
    def _take_snapshots(self, batch: List[Tuple]
                        ) -> Optional[Dict[int, Tuple[int, int]]]:
        """With snapshots: slot -> ``(snapshot slot, end)`` for each row
        of ``batch`` whose prompt has a whole page behind its hits: the
        snapshot slot taken for the state at the prompt's last page end,
        ``end`` tokens in. Taken before the round's first wave, by
        ``PrefixLRU.take_state_slot``'s rule, never one a row of this
        round resumes from; a row without one writes that state to the
        bin. A row's older snapshot is superseded by its newer one, and
        only a snapshot that was still its sequence's deepest counts as
        evicted. None for an engine without snapshots."""
        if not self._snapshots:
            return None
        ps = self.paged.page_size
        busy = {self._snap_src.get(b[0], 0) for b in batch if b[2]}
        out: Dict[int, Tuple[int, int]] = {}
        c = self.metrics.counters
        for slot_id, req, hits, chains, _row in batch:
            src = self._snap_src.get(slot_id, 0) if hits else 0
            if src:
                c["ssm_state_tokens_resumed"].inc(len(hits) * ps)
            n_full = len(req.prompt) // ps
            if chains is None or n_full <= len(hits):
                continue
            dst, lost = self._prefix.take_state_slot(
                chains[n_full - 1], n_full, busy)
            c["ssm_snapshots_evicted"].inc(int(lost))
            if dst:
                c["ssm_snapshots_taken"].inc()
                busy.add(dst)
                out[slot_id] = (dst, n_full * ps)
                if src:
                    self._prefix.supersede(src)
        if self._prefix is not None:
            c["ssm_snapshot_slots"].inc(self._snapshots)
            c["ssm_snapshot_slots_live"].inc(self._prefix.state_slots_live())
        return out

    # swarmlint: hot
    def _prefill_ragged_waves(self, batch: List[Tuple]) -> None:
        """Packed ragged admission waves (ISSUE 11 tentpole): the wave's
        rows concatenate into ONE token stream — no row buckets, no
        length buckets — described by per-row (start, len, prefix_len)
        descriptors, and every wave's width is the next rung of the
        round's least-cost cover (``_ragged_width_for``): full waves
        while the tokens outweigh a pass over the weights, then one
        wave rounded up, its padding dead (``tok_row = R``,
        ``tok_pos = cap``) and counted in ``prefill_padding_tokens``.
        A row longer than a wave's remaining budget SPLITS: its head's
        K/V lands in its pages this wave, and the tail rides the next
        wave with prefix_len advanced — the ragged kernel reads the
        already-written pages back in place, exactly like a prefix-cache
        hit. Sampling fires only on a row's FINAL chunk (scatter id
        max_batch drops the rest), with the same absolute-position PRNG
        fold as the bucketed paths.

        The round's last wave also carries the running rows as RIDERS
        (ISSUE 43): a slot whose state the host has confirmed
        (``_wave_riders``) is, to the ragged forward, a row with a cached
        prefix of ``position`` tokens and a suffix of one, the token it
        was fed last. It sits in a seat the plan already pays for
        (``_riders_that_fit``), samples into its own ``_last_tokens``
        lane with the key of its position, advances by one and has that
        token surfaced as row 0 of its next block (``pending_token``):
        the pass over the weights that admits a request is a decode step
        for the rows that wait for it. Riders are not in ``batch``: they
        count into ``wave_rider_tokens`` and into nothing of admission's,
        and the running rows the plan had no seat for into
        ``wave_riders_unseated``. Under snapshots a rider's state goes
        from its slot back to its slot, a token on; it takes no snapshot.

        ``batch`` rows: (slot_id, req, hits, chains, table_row) — hits/
        chains from the admission-time prefix plan (chains None = row not
        prefix-planned: sub-page prompt, keep_pages, or prefix off)."""
        t0 = time.time()
        R = self.max_batch
        ps = self.paged.page_size
        maxp = self.paged.allocator.maxp
        cap = maxp * ps
        pend: List[List[Any]] = []
        for slot_id, req, hits, chains, row in batch:
            p0 = len(hits) * ps
            pend.append([slot_id, req.prompt[p0:], p0, 0, row])
            s = req.sampling
            self._temp[slot_id] = s.temperature
            self._topk[slot_id] = s.top_k
            self._topp[slot_id] = s.top_p
            self._set_slot_key(slot_id, s.seed)
        snap_dst = self._take_snapshots(batch)
        packed_n = padding_n = scan_segments = unseated = 0
        # routed: slot -> the parts of its suffix, in stream order
        stream_parts: Dict[int, List[RoutingRows]] = {}
        riding: List[int] = []     # the slots that ride the last wave
        tracer = self.tracer
        while pend:
            t_pack = tracer.phase_begin("engine.admission.pack")
            total = 0
            for it in pend:
                total += len(it[1]) - it[3]
            wd = self._ragged_width_for(total)
            if wd >= total:
                # the round's last wave: every pending row ends in it,
                # and the seats and rows it has left take riders
                running = self._wave_riders()
                riding = running[:self._riders_that_fit(
                    total, wd, min(len(running), R - len(pend)))]
                unseated = len(running) - len(riding)
                for sid in riding:
                    s = self.slots[sid]
                    pend.append([sid, s.generated[-1:], s.position, 0,
                                 s.table_row])
                wd = self._ragged_width_for(total + len(riding))
            tokens = np.full(wd, self.pad_id, np.int32)
            tok_row = np.full(wd, R, np.int32)   # R = dead row sentinel
            tok_pos = np.full(wd, cap, np.int32)  # >= coverage -> trash
            starts = np.zeros(R, np.int32)
            lens = np.zeros(R, np.int32)
            plens = np.zeros(R, np.int32)
            tables = np.zeros((R, maxp), np.int32)
            scatter = np.full(R, self.max_batch, np.int32)
            gather = np.zeros(R, np.int64)
            # conv state: where each row's seed comes from (a page id, 0
            # for zeros, -1 for its own slot) and the slot its state
            # after this wave goes to (max_batch: a padding row, dropped)
            state_src = np.zeros(R, np.int32)
            state_slot = np.full(R, self.max_batch, np.int32)
            state_dst = np.zeros(R, np.int32)
            filled = 0
            r = 0
            for it in pend:
                if filled >= wd or r >= R:
                    break
                slot_id, suffix, p0, consumed, row = (it[0], it[1], it[2],
                                                      it[3], it[4])
                take = min(len(suffix) - consumed, wd - filled)
                if take <= 0:
                    continue
                abs0 = p0 + consumed
                tokens[filled:filled + take] = suffix[consumed:
                                                      consumed + take]
                tok_row[filled:filled + take] = r
                tok_pos[filled:filled + take] = np.arange(
                    abs0, abs0 + take, dtype=np.int32)
                starts[r] = filled
                lens[r] = take
                plens[r] = abs0
                tables[r] = row
                gather[r] = slot_id
                state_slot[r] = slot_id
                # a first chunk behind a prefix hit resumes from its last
                # hit page (the table row starts with the hit pages; with
                # snapshots: from the snapshot its hits led to); a rider,
                # like a later chunk, from its own slot's state
                rides = slot_id in riding
                if consumed or rides:
                    state_src[r] = -1
                elif p0:
                    state_src[r] = (row[p0 // ps - 1] if snap_dst is None
                                    else self._snap_src.get(slot_id, 0))
                if snap_dst is not None:
                    # the chunk that holds the prompt's last page end
                    # writes the snapshot; an earlier chunk's goes to the
                    # bin, and so does a rider's whose token ends a page
                    dst, end = (0, 0) if rides else snap_dst.get(
                        slot_id, (0, 0))
                    if abs0 < end <= abs0 + take:
                        state_dst[r] = dst
                    scan_segments += wave_segments(abs0, take, ps)
                if consumed + take == len(suffix):
                    scatter[r] = slot_id     # final chunk: sample here
                it[3] = consumed + take
                filled += take
                r += 1
            if self._kerncheck:
                # descriptor audit BEFORE the wave ships: a bad page id /
                # trash-page target / duplicate (page, offset) cell is an
                # engine bug the kernel would silently scatter into the
                # pool (runtime face of SWL901/902)
                from ..obs.kerncheck import check_wave_descriptors

                check_wave_descriptors(
                    tok_row, tok_pos, tables,
                    self.paged.allocator.num_pages, ps)
            # admission's accounts hold the admitted tokens alone, as
            # before: a rider's seat is padding to them
            admitted = filled - len(riding)
            tracer.phase_end(t_pack, "engine.admission.pack", cat="engine",
                             args=self._pack_args(wd, admitted, len(riding)))
            self._mirrored(
                self.CALL_PAGED_PREFILL_RAGGED, tokens, tok_row, tok_pos,
                starts, lens, plens, tables, scatter,
                self._base_keys_np[gather], self._temp[gather],
                self._topk[gather], self._topp[gather],
                *((state_src, state_slot) if self._stateful else ()),
                *((state_dst,) if self._snapshots else ()),
            )
            # dispatch-shape profile: the tiny flush waves ROADMAP item 2
            # wants sized show up here as named (ragged, small-width) rows
            self._prof.wave("ragged", wd, filled, wd - filled,
                            prof_key("prefill.ragged", tokens.shape))
            wave = self._take_wave()
            if wave is not None:
                # a row's record takes its chunk of this wave's stream (a
                # rider's one row lies in position order: every chunk
                # before this round has been processed); ``stream_parts``
                # keeps, an admitted slot, where each suffix position's
                # row lies, for the pages registered below
                for j in range(r):
                    sid = int(gather[j])
                    part = wave.part(slice(int(starts[j]),
                                           int(starts[j] + lens[j])))
                    self.slots[sid].routing.append(part)
                    if sid not in riding:
                        stream_parts.setdefault(sid, []).append(part)
            for sid in riding:
                # the slot is a token further and that token is pending,
                # as after a prefill
                s = self.slots[sid]
                s.position += 1
                s.dispatched_position = s.position
                s.pending_token = True
            packed_n += admitted
            padding_n += wd - admitted
            pend = [it for it in pend if it[3] < len(it[1])]
        self.metrics.counters["prefill_packed_tokens"].inc(packed_n)
        self.metrics.counters["prefill_padding_tokens"].inc(padding_n)
        self.metrics.counters["wave_rider_tokens"].inc(len(riding))
        self.metrics.counters["wave_riders_unseated"].inc(unseated)
        if snap_dst is not None:
            # what the scan walks: a rider's one-token segment is one too
            self.metrics.counters["ssm_wave_segments"].inc(scan_segments)
            self.metrics.counters["ssm_wave_segment_tokens"].inc(
                packed_n + len(riding))
        self._last_wave_kind = "ragged"
        if self._prefix is not None:
            # registration mirrors _prefill_paged_prefix_batch: custody
            # of the prompt's fresh FULL pages moves to the cache with no
            # copy; matched hits stay pinned until retirement
            reused = 0
            for slot_id, req, hits, chains, _row in batch:
                if chains is None:
                    continue
                reused += len(hits) * ps
                prompt = req.prompt
                fresh = self.paged.allocator.pages_for(slot_id)
                pins: List[int] = []
                n_full = len(prompt) // ps
                for page_idx in range(len(hits), n_full):
                    f = page_idx - len(hits)
                    if f >= len(fresh):
                        break
                    toks = tuple(prompt[page_idx * ps:(page_idx + 1) * ps])
                    if self._prefix.register(
                            chains[page_idx], toks, fresh[f],
                            routing=_SuffixRows(stream_parts[slot_id],
                                                f * ps, (f + 1) * ps)
                            if slot_id in stream_parts else None,
                            # the wave wrote the state at this page's end
                            # into page_state under the page's id (with
                            # snapshots: the state at the prompt's last
                            # page end into the slot ``_take_snapshots``
                            # bound to that page's chain)
                            state=True if self._stateful
                            and snap_dst is None else None):
                        self.paged.allocator.transfer_to_cache(
                            slot_id, [fresh[f]])
                        self._prefix.pin([fresh[f]])
                        pins.append(fresh[f])
                self._slot_prefix_pins[slot_id] = hits + pins
            if reused:
                self.metrics.counters["prefix_reused_tokens"].inc(reused)
                if self._latent:
                    # cached rows this wave's attention read in place
                    self.metrics.counters[
                        "latent_prefix_tokens_reused"].inc(reused)
        self._activate([(b[0], b[1]) for b in batch], t0)

    def _prefill_batch(self, batch: List[Tuple[int, GenRequest]]) -> None:  # swarmlint: hot
        """One compiled prefill for up to ``prefill_batch`` admissions.

        The call is padded to the fixed [Bp, bucket] shape (one compiled
        variant per bucket); padding rows are discarded. NO host sync
        happens here — sampled first tokens land in the device fed-token
        vector and surface as row 0 of the next chunk's block.
        """
        t0 = time.time()
        t_pack = self.tracer.phase_begin("engine.admission.pack")
        n = len(batch)
        # row-bucketed wave (lane engines): pay for the admissions the
        # wave actually has, not prefill_batch unconditionally
        Bp = self._rows_for(n)
        longest = max(len(req.prompt) for _, req in batch)
        bucket = self._bucket_for(longest)
        padded = np.full((Bp, bucket), self.pad_id, np.int32)
        lengths = np.ones(Bp, np.int32)
        # row -> slot gather index, padded to Bp (padding rows borrow slot 0's
        # params/keys; their outputs are discarded)
        gather = np.zeros(Bp, np.int64)
        # row -> slot scatter index for the fused insert; padding rows point
        # one past the last slot so mode="drop" discards their writes
        scatter = np.full(Bp, self.max_batch, np.int32)
        for row, (slot_id, req) in enumerate(batch):
            prompt = req.prompt  # submit() enforces len < max_seq
            padded[row, : len(prompt)] = prompt
            lengths[row] = len(prompt)
            gather[row] = slot_id
            scatter[row] = slot_id
            # slot sampling params must be set BEFORE prefill samples the
            # first token, or the request inherits the previous occupant's
            s = req.sampling
            self._temp[slot_id] = s.temperature
            self._topk[slot_id] = s.top_k
            self._topp[slot_id] = s.top_p
            self._set_slot_key(slot_id, s.seed)
        # padding waste: grid tokens dispatched minus real prompt tokens
        # (bucket rounding + padding rows) — flight-recorder occupancy
        packed_n = int(lengths[:n].sum())
        padding_n = int(padded.size) - packed_n
        self.metrics.counters["prefill_padding_tokens"].inc(padding_n)
        self.metrics.counters["prefill_packed_tokens"].inc(packed_n)
        self._last_wave_kind = "bucketed"
        # the paged branches below lay the same rows out once more for
        # their call: microseconds, which show under engine.admission only
        self.tracer.phase_end(
            t_pack, "engine.admission.pack", cat="engine",
            args=self._pack_args(padded.size, packed_n))

        if not self.paged:
            # ONE dispatch: forward + sample + slot insert + token scatter.
            # Stale entries a previous occupant left at positions >= bucket
            # are never read: decode writes position p in the same step
            # that first attends to it (write-before-read invariant).
            if self._mh is not None:
                self._mh.publish_prefill(
                    padded, lengths, scatter, self._base_keys_np[gather],
                    self._temp[gather], self._topk[gather],
                    self._topp[gather])
            prof = self._prof
            t_wave = self.tracer.phase_begin("engine.admission.dispatch")
            t0_ns = time.monotonic_ns() if prof.enabled else 0
            (self.cache, self._last_tokens, self._last_lps,
             *self._wave_routing) = self._prefill_fused(
                    self.params,
                    padded,              # raw np: transfer rides the dispatch
                    lengths,
                    scatter,
                    self.cache,
                    self._last_tokens,
                    self._last_lps,
                    self._base_keys_np[gather],
                    self._temp[gather],
                    self._topk[gather],
                    self._topp[gather],
                )
            self.tracer.phase_end(
                t_wave, "engine.admission.dispatch", cat="engine",
                args=self._count_wave("dense"))
            if t0_ns:
                key = prof_key("prefill.dense", padded.shape)
                prof.dispatch(key, t0_ns, time.monotonic_ns() - t0_ns)
                prof.wave("bucketed", bucket, packed_n, padding_n, key)
            self._record_wave_rows(batch, range(n), lengths)
            self._activate(batch, t0)
            return

        # slot rows allocated fewer pages than the bucket (short prompt
        # in a big bucket) route the all-padding chunks to trash page 0;
        # padding rows (beyond n) scatter entirely to trash
        chunks = -(-bucket // self.paged.page_size)
        if self._packed_active():
            # shard-packed collective-free prefill: re-lay the wave as
            # per-shard row blocks (block d = shard d's rows; slot→shard
            # affinity makes every row's pages and fed-token slot local
            # to its block's device; padding rows are dropped/trashed)
            n_sh, rows_per, R = self._packed_geometry()
            p_tokens = np.full((R, bucket), self.pad_id, np.int32)
            p_lengths = np.ones(R, np.int32)
            p_target = np.zeros((R, chunks), np.int32)
            p_scatter = np.full(R, self.max_batch, np.int32)
            p_gather = np.zeros(R, np.int64)
            fill = [0] * n_sh  # next free row within each shard block
            packed_rows: List[int] = []  # batch order -> row of the wave
            for row, (slot_id, req) in enumerate(batch):
                sh = self.paged.allocator.shard_of(slot_id)
                r = sh * rows_per + fill[sh]
                fill[sh] += 1
                packed_rows.append(r)
                p_tokens[r] = padded[row]
                p_lengths[r] = lengths[row]
                p_scatter[r] = slot_id
                p_gather[r] = slot_id
                pages = self.paged.allocator.pages_for(slot_id)
                m = min(len(pages), chunks)
                p_target[r, :m] = pages[:m]
            self._mirrored(
                self.CALL_PAGED_PREFILL_PACKED, p_tokens, p_lengths,
                p_target, p_scatter, self._base_keys_np[p_gather],
                self._temp[p_gather], self._topk[p_gather],
                self._topp[p_gather],
            )
            self._prof.wave("packed", bucket, packed_n,
                            int(p_tokens.size) - packed_n,
                            prof_key("prefill.packed", p_tokens.shape))
            self._record_wave_rows(batch, packed_rows, p_lengths)
            self._activate(batch, t0)
            return
        target = np.zeros((Bp, chunks), np.int32)
        for row in range(n):
            pages = self.paged.allocator.pages_for(int(gather[row]))
            m = min(len(pages), chunks)
            target[row, :m] = pages[:m]
        # padding rows -> max_batch, dropped; raw np args: the transfer
        # rides the dispatch (and, pod mode, the publish to workers)
        self._mirrored(
            self.CALL_PAGED_PREFILL, padded, lengths, target, scatter,
            self._base_keys_np[gather], self._temp[gather],
            self._topk[gather], self._topp[gather],
        )
        self._prof.wave("bucketed", bucket, packed_n, padding_n,
                        prof_key("prefill.paged", padded.shape))
        self._record_wave_rows(batch, range(n), lengths)
        self._activate(batch, t0)

    # swarmlint: hot
    def _record_wave_rows(self, batch, wave_rows,
                          lengths) -> Optional[WaveRouting]:
        """A row-bucketed prefill's routing into its rows' records:
        ``batch[j]`` (a tuple that starts with its slot id) was row
        ``wave_rows[j]`` of the wave just dispatched and computed
        ``lengths[row]`` positions. Returns the wave for the caller's page
        registrations; None, and nothing done, where the configuration is
        dense."""
        wave = self._take_wave()
        if wave is not None:
            for item, row in zip(batch, wave_rows):
                self.slots[item[0]].routing.append(
                    wave.part((row, slice(0, int(lengths[row])))))
        return wave

    def _activate(self, batch: List[Tuple[int, GenRequest]], t0: float) -> None:  # swarmlint: hot
        if self._pagecheck is not None:
            # dispatch-time page validation: every page the slot's row
            # was stamped with at allocation is still live at the same
            # alloc epoch (a page freed+reallocated in between is the
            # stale-table race; a foreign page is cross-lane aliasing)
            for slot_id, _req in batch:
                self._pagecheck.validate_row(slot_id)
        for slot_id, req in batch:
            slot = self.slots[slot_id]
            slot.active = True
            slot.request = req
            slot.admitted_at = t0
            # next write position; rolling-KV continuations resume past
            # the tokens already in their kept pages
            slot.position = req.resume_len + len(req.prompt)
            slot.dispatched_position = slot.position
            slot.generated = []
            slot.logprobs = []
            slot.pending_token = True
            slot.admit_syncs = self._host_sync_n
            with self._cv:
                self._admitting.discard(req.request_id)
                # cancelled while the prefill was in flight: retire at the
                # next processed block
                slot.cancelled = req.request_id in self._cancel_pending
                self._cancel_pending.discard(req.request_id)
            slot.first_token_at = None
            self.total_requests += 1
            # prefill work accounting (bench MFU: prompt tokens cost the
            # same per-token FLOPs as decode tokens but 10-20x the volume
            # under chat-history prompts). The LOGICAL prompt includes a
            # rolling continuation's kept tokens; reuse is counted
            # separately in prefix_reused_tokens, so computed = total -
            # reused stays consistent across the prefix and resume paths
            self.metrics.counters["prompt_tokens"].inc(
                len(req.prompt) + req.resume_len)
            # admission accounting for the SLO sentinel's window
            # summaries: requests admitted + one wave per _activate call
            # (the offline analyzer derives the same two numbers from
            # prefill-span clustering; online they are two counter incs)
            self.metrics.counters["engine_admitted"].inc()
            self._lat_queue_wait.observe(t0 - req.submitted_at)
            HIST_QUEUE_WAIT.observe(t0 - req.submitted_at,
                                    req.request_id)
            self.metrics.counters["phase_us_queue_wait"].inc(
                max(0, int((t0 - req.submitted_at) * 1e6)))
            # retro-span: the wait was over before any tracer call site
            # could run, so it is recorded from its wall-clock endpoints
            self.tracer.span_at("engine.admit", req.submitted_at, t0,
                                cat="engine", rid=req.request_id,
                                args={"step": self._loop_step})
        prefill_dt = time.time() - t0
        self._lat_prefill.observe(prefill_dt)
        self.metrics.counters["engine_admission_waves"].inc()
        if self.overlap_probe is not None:
            # per-shard lanes: count waves whose prefill dispatch ran
            # while a SIBLING lane's decode session was in flight — the
            # overlap that a single global admission wave can never have
            try:
                if self.overlap_probe():
                    self.metrics.counters[
                        "engine_admission_overlap_steps"].inc()
            except Exception:  # probe is advisory telemetry only
                pass
        self.metrics.counters["phase_us_prefill"].inc(
            max(0, int(prefill_dt * 1e6)))
        for slot_id, req in batch:
            slot = self.slots[slot_id]
            self.tracer.span_at(
                "engine.prefill", t0, t0 + prefill_dt, cat="engine",
                rid=req.request_id,
                args={"slot": slot_id,
                      "mid": req.metadata.get("message_id"),
                      "step": self._loop_step,
                      "cached_tokens": slot.cached_tokens,
                      "new_tokens": slot.new_tokens,
                      # the last prefill dispatch that served this batch
                      "wave": self._wave_n})

    # --------------------------------------------------------------- decode

    def _use_resident(self) -> bool:
        """Whether the loop runs device-resident decode sessions instead
        of per-chunk scan dispatches. ONE gate shared by the loop,
        warmup() and warmup_call_plan() (same drift contract as
        _packed_active): built only for single-shard paged engines, and
        pod mode falls back — worker hosts replay per-call, and a
        host-steered while_loop cannot be mirrored."""
        return self._resident_variants is not None and self._mh is None

    # swarmlint: hot
    def _resident_emit(self, packed) -> np.bool_:
        """Ordered io_callback target: one call per device chunk, on the
        runtime's callback thread, with the chunk's one packed operand
        (``_pack_resident_block``). The device loop stands still until
        this returns, so it does only what the answer needs: stamp the
        time, copy the operand (the one copy: the buffer is the runtime's,
        the block is the engine's to keep) and take views of it, vote
        (``_resident_vote``), put the block on the FIFO for the engine
        thread and return the vote. No token is emitted, no slot retired
        and no span written here: ``_resident_consume`` does all of that
        on the engine thread while the device runs the next chunk. (Where
        the dispatch does not return before the program ends, the engine
        thread is not there to take the block and it is processed here,
        after the vote: the CPU backend runs a program that has a host
        callback on the calling thread.)
        ``last`` mirrors the device loop's ``cond`` (the chunk bound, the
        vote, the loop's ``done`` row), so the consumer knows which block
        ends the session without asking the device. Before it votes it
        takes the session's credit, which the engine thread returns with
        each processed block: the emission is one chunk behind the device
        and no more, so what the vote reads of the slots, of a cancel and
        of an armed chaos fault is at most one block old (a host slower
        than the device holds the device back, as it always did). Never
        raises: an exception here would poison the device program
        mid-flight, so a failure votes to stop the loop and says so to
        the consumer."""
        stamp_ns = time.monotonic_ns()
        try:
            ses = self._resident
            if ses is None:
                return np.bool_(False)
            ses.credit.acquire(timeout=_RESIDENT_POLL_S)
            block, lps, n, done, routing = _unpack_resident_block(
                np.array(packed), self.decode_chunk + 1, self.max_batch,
                self._routed)
            vote, queued = self._resident_vote(ses, block, n)
            last = not (vote and n + 1 < ses.max_chunks
                        and not done.all())
            blk = _ResidentBlock(block, lps, routing, n, stamp_ns, vote,
                                 queued, last)
            if ses.consuming:
                self._resident_fifo.put(blk)
            else:
                self._resident_block(ses, blk)
            return np.bool_(vote)
        except Exception:
            logger.exception("emission-ring callback failed; "
                             "stopping the resident session")
            self._resident_fifo.put(None)
            return np.bool_(False)

    # swarmlint: hot
    def _resident_vote(self, ses: _ResidentSession, block: np.ndarray,
                       n: int) -> Tuple[bool, int]:
        """The host's continue vote on chunk ``n``, taken BEFORE the
        engine thread processes the block, so it reckons what that
        processing will find, vectorised over the lanes ``[B]``. Returns
        the vote and the queue's length it saw (0 where it never looked).

        Stop when the engine is stopping, a chaos fault is armed, no lane
        will be active once this block is processed, or queued work (a
        request on the queue, or one whose plan ``_plan_ahead`` holds)
        could be admitted into a slot that is free now or freed by this
        block (exit -> admit -> new session). A lane the votes so far expect
        live retires in this block by EOS (an ``eos_id`` anywhere in its
        column: row 0 is a pending sample, a prefill's or a wave rider's,
        or a fed token that an earlier block already showed not to be
        one), by length (the tokens its request had left when the snapshot
        was built, against the ``(n + 1) * K`` steps through this block
        and the pending token) or at ``max_seq`` (the block's last step
        would write at or past it): exactly ``_process_host_block``'s
        three retirements.
        The loop's own ``done`` row is not used for this: its ``stop_pos``
        carries a ``+ 1`` for the pending token whether one is
        pending or not, so by length it is up to a chunk generous; it
        serves the mirror of ``cond`` in ``_resident_emit``, where it is
        the device's word. What processing has already found is read from
        the slots (a lane retired by an earlier block, a cancel flagged by
        now); only a cancel that another thread flags between this vote
        and the block's processing is seen a chunk late, and counted
        (``resident_votes_stale``). Reads of _stop and the slots are
        lock-light by design: a stale verdict costs ONE extra chunk, while
        taking _cv for them would put the lock on every chunk's path."""
        if self._stop:  # swarmlint: disable=SWL301 -- chunk-granular race is benign
            return False, 0
        if ses.failed or self._chaos_pending():
            # an armed chaos fault must land at the loop-top seam: exit
            # the session so the next iteration runs chaos_step (a kill
            # raised inside this ordered callback would be swallowed)
            return False, 0
        through = (n + 1) * self.decode_chunk
        alive = ses.alive
        alive &= ~((block == self.eos_id).any(axis=0)
                   | (ses.left <= through + ses.first)
                   | (ses.pos0 + through > self.max_seq))
        for i, req, _pos0 in ses.snap:
            if alive[i]:
                s = self.slots[i]
                if not s.active or s.request is not req or s.cancelled:
                    alive[i] = False
        with self._cv:
            queued = self._waiting()
        if not alive.any() or (queued and not alive.all()):
            return False, queued
        return True, queued

    # swarmlint: hot
    def _resident_consume(self, ses: _ResidentSession, n_dev) -> None:
        """The engine thread's half of a resident session: take the
        session's blocks off the FIFO in order and process each
        (``_resident_block``) while the device runs the chunk after it,
        until the block the callback marked last. Whenever the FIFO is
        empty it asks the device whether the program still runs, and
        again a timeout later, so a program that failed, a ``last`` that
        mirrored ``cond`` wrongly or a dispatch that returned only when
        all was over cannot hang the loop: the caller's drain read raises
        or returns, and ``_resident_flush`` takes what was left."""
        fifo = self._resident_fifo
        ses.consuming = True
        # what was queued between the dispatch and here woke nobody
        self._plan_wake(ses)
        while True:
            if fifo.empty() and n_dev.is_ready():
                return
            try:
                blk = fifo.get(timeout=_RESIDENT_POLL_S)
            except queue.Empty:
                continue
            if blk is _PLAN_WAKE:
                self._plan_wake(ses)
            elif self._resident_block(ses, blk):
                return

    # swarmlint: hot
    def _plan_wake(self, ses: _ResidentSession) -> None:
        """A request was queued while the session runs: plan its admission
        now, while the device runs the chunk, not at the boundary."""
        try:
            self._plan_ahead()
        except Exception:
            ses.failed = True    # the boundary's round decides
            logger.exception("planning ahead failed; stopping the "
                             "resident session")

    # swarmlint: hot
    def _resident_flush(self, ses: Optional[_ResidentSession]) -> None:
        """Empty the FIFO: after the drain read every callback has run,
        so what is here is the session's whole remainder (nothing, unless
        ``_resident_consume`` left early). ``ses`` None discards it."""
        fifo = self._resident_fifo
        while not fifo.empty():
            blk = fifo.get_nowait()
            if ses is not None and blk is not _PLAN_WAKE:
                self._resident_block(ses, blk)

    # swarmlint: hot
    def _resident_block(self, ses: _ResidentSession,
                        blk: Optional[_ResidentBlock]) -> bool:
        """Process one block of a resident session: the chunk's device
        time (boundary to boundary, by the callback's stamps: no sync, no
        block_until_ready), ``_process_host_block``, then whether the
        vote held. Returns whether the session ends here. An exception
        stops the session at the next vote and is logged; later blocks
        are still processed, so the lanes it did not touch stay in step
        with the device."""
        if blk is None:      # the callback itself failed, and voted stop
            return True
        try:
            K = self.decode_chunk
            snapshot = [(i, req, pos0 + blk.n * K)
                        for i, req, pos0 in ses.snap]
            self._prof.dispatch(self._prof_resident_key, ses.prev_ns,
                                blk.stamp_ns - ses.prev_ns)
            # the block that ends a session for work to admit (the vote
            # saw it queued or planned) is settled here and delivered
            # behind the round's wave (``_deliver_pending``): the device
            # waits for the wave's dispatch, not for the callbacks
            self._process_host_block(
                blk.block, blk.lps, snapshot, ses.prev_ns, blk.n,
                blk.routing, stamp_ns=blk.stamp_ns,
                defer=bool(blk.last and blk.queued and ses.consuming))
            ses.prev_ns = blk.stamp_ns
            if blk.vote:
                # what the old rule, taken after processing, finds on the
                # inputs the vote had: stop where the vote said continue
                # is a vote that went stale (a cancel flagged after it)
                active = [s.active for s in self.slots]
                if not any(active) or (blk.queued and not all(active)):
                    self.metrics.counters["resident_votes_stale"].inc()
        except Exception:
            ses.failed = True
            logger.exception("emission-ring block processing failed; "
                             "stopping the resident session")
        finally:
            ses.credit.release()
        return blk.last

    # swarmlint: hot
    def _run_resident(self) -> None:
        """Dispatch one device-resident decode session, emit its chunks
        as they come and drain it.

        The session covers every currently-active slot; admission happens
        only between sessions (the continue vote exits the loop when
        queued work meets a free slot). Host<->device traffic for the
        whole session: the dispatch (no sync), one packed block and one
        vote a chunk through the ordered callback, and ONE drain read of
        the chunk counter — a request admitted and retired within a
        session therefore spans a single sanctioned sync, vs one per
        chunk on the scan path."""
        t_session = self.tracer.phase_begin("engine.session")
        # the last session's last block, settled before the round's wave
        # went: delivered now, behind that wave and before this session's
        # inputs are read off the slots
        self._deliver_pending()
        n_chunks = 0
        variant = -1
        carried = 0  # slots already decoding before this session
        B = self.max_batch
        K = self.decode_chunk
        positions = np.zeros((B,), np.int32)
        stop_pos = np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        # what the votes reckon with (_resident_vote): tokens each lane's
        # request may still emit, and whether one is pending (a prefill's
        # sample or a wave rider's)
        left = np.zeros((B,), np.int32)
        first = np.zeros((B,), np.int32)
        snap: List[Tuple[int, GenRequest, int]] = []
        needs_filters = False
        needs_sampling = False
        max_rem = 0
        for i, s in enumerate(self.slots):
            if not s.active:
                continue
            pos0 = s.dispatched_position
            positions[i] = pos0
            live[i] = True
            left[i] = s.request.sampling.max_new_tokens - len(s.generated)
            first[i] = s.pending_token
            # +1 covers a pending token (row 0 of the first
            # block); the device stops the LOOP here, the host still
            # owns exact retirement semantics
            stop_pos[i] = min(self.max_seq, pos0 + max(1, int(left[i]) + 1))
            snap.append((i, s.request, pos0))
            if s.first_token_at is not None:
                # decoding before this session: a rider too, whose
                # pending token is not its first
                carried += 1
            max_rem = max(max_rem, int(stop_pos[i]) - pos0)
            if self._topk[i] > 0 or self._topp[i] < 1.0:
                needs_filters = True
            if self._temp[i] > 0:
                needs_sampling = True
        try:
            if not snap:
                return
            max_chunks = np.int32(-(-max(1, max_rem) // K) + 1)
            variant = (0 if needs_filters else 1 if needs_sampling else 2)
            n_chunks = self._resident_session(
                variant, positions, stop_pos, live, max_chunks,
                _ResidentSession(snap, positions, left, first, live.copy(),
                                 max_chunks))
        finally:
            # build inputs -> dispatch -> every block emitted -> the
            # drain read returned
            self.tracer.phase_end(
                t_session, "engine.session", cat="engine",
                args={"step": self._loop_step,
                      "variant": (RESIDENT_PROGRAM_NAMES[variant]
                                  if variant >= 0 else None),
                      "slots": len(snap), "carried": carried,
                      "chunks": n_chunks})
        for i, req, _pos0 in snap:
            s = self.slots[i]
            if s.active and s.request is req:
                # every emitted block advanced s.position; the device's
                # next fed token corresponds to exactly that extent
                s.dispatched_position = s.position

    # swarmlint: hot
    def _resident_session(self, variant: int, positions, stop_pos, live,
                          max_chunks, ses: _ResidentSession) -> int:
        """Dispatch one resident program, consume its blocks and read its
        chunk count: the device half of ``_run_resident``. Returns the
        chunks run."""
        fn = self._resident_variants[variant]
        self._prof_resident_key = PROF_RESIDENT_KEYS[variant]
        self._resident = ses
        self._lane_busy = True
        try:
            n_dev, lt, llp, cache = fn(
                self.params, self._last_tokens, self._last_lps, positions,
                self.cache, self._base_keys_np, self._temp, self._topk,
                self._topp, stop_pos, live, max_chunks,
            )
            self._last_tokens, self._last_lps, self.cache = lt, llp, cache
            # the drain read's transfer, queued behind the program: it is
            # on the host by the time the last block has been emitted
            n_dev.copy_to_host_async()
            self._resident_consume(ses, n_dev)
            t_sync0 = time.monotonic_ns()
            # swarmlint: sanctioned-drain -- THE one sync per session:
            # after the last block it returns at once, and its resolution
            # guarantees every ordered emission callback has run, so the
            # flush below leaves slot state host-confirmed
            n_chunks = int(jax.device_get(n_dev))
            t_sync1 = time.monotonic_ns()
            self._resident_flush(ses)
            self.tracer.span_end(t_sync0, "engine.host_sync", cat="engine")
            self.metrics.counters["engine_host_syncs"].inc()
            self._host_sync_n += 1
            self.metrics.counters["phase_us_host_sync"].inc(
                (t_sync1 - t_sync0) // 1000)
            self.metrics.counters["engine_resident_sessions"].inc()
            self.metrics.counters["engine_resident_chunks"].inc(n_chunks)
            return n_chunks
        finally:
            with self._cv:     # submit() wakes a live session under it
                self._resident = None
            self._resident_flush(None)   # a failed program's leftovers
            self._lane_busy = False

    def _dispatch_decode(self):  # swarmlint: hot
        """Issue one K-step decode chunk (NO host sync) and return
        (device token block, snapshot) for later processing.

        The snapshot pins (slot, request, start position) at dispatch
        time: with pipelining, a slot can retire and be re-admitted while
        this chunk is still in flight — its lane then holds the OLD
        occupant's garbage, which processing must discard (the request
        identity check does exactly that).
        """
        positions = np.zeros((self.max_batch,), np.int32)
        snapshot: List[Tuple[int, GenRequest, int]] = []
        needs_filters = False
        needs_sampling = False
        for i, s in enumerate(self.slots):
            if s.active:
                positions[i] = s.dispatched_position
                snapshot.append((i, s.request, s.dispatched_position))
                s.dispatched_position += self.decode_chunk
                if self._topk[i] > 0 or self._topp[i] < 1.0:
                    needs_filters = True
                if self._temp[i] > 0:
                    needs_sampling = True
        variant = (0 if needs_filters else 1 if needs_sampling else 2)
        decode = self._decode_variants[variant]
        if self._mh is not None:
            self._mh.publish_decode(variant, positions, self._base_keys_np,
                                    self._temp, self._topk, self._topp)
        # keys ride as a raw [B, 2] numpy argument (like temp/topk/topp):
        # per-REQUEST seeds just rewrite a host row at admission, with no
        # graph change and no eager transfer
        (all_toks, all_lps, self._last_tokens, self._last_lps, self.cache,
         *routing) = decode(
                self.params, self._last_tokens, self._last_lps, positions,
                self.cache, self._base_keys_np,
                self._temp, self._topk, self._topp,
            )
        # dispatch stamp: _process_block closes each snapshot slot's
        # "engine.decode_chunk" span against it (monotonic, so a wall
        # clock step can't produce a negative chunk); the variant index
        # rides along so the chunk's device time lands on the right
        # swarmprof key; a routed configuration's chunk routing rides last
        return (all_toks, all_lps, snapshot, time.monotonic_ns(), variant,
                *routing)

    # swarmlint: hot
    def _drain_prefill_only(self) -> None:
        """Fleet PREFILL lanes (ISSUE 20): retire admission-only
        (max_new_tokens <= 1) requests straight off the prefill sample.
        ``_last_tokens[i]`` IS the fed token the colocated decode path
        reads as ``block[0, i]``, so emitting it here keeps the
        prefill→decode handoff bit-identical to colocated serving. One
        host sync per admission round, accounted like _process_block's.
        Off-role slots (max_new > 1, e.g. colocated fallback under a
        quarantined decode pool) are left for the regular decode loop."""
        self._deliver_pending()    # a request's callbacks keep their order
        rows = [i for i, s in enumerate(self.slots)
                if s.active and s.pending_token and s.request is not None
                and s.request.sampling.max_new_tokens <= 1]
        if not rows:
            return
        t_sync0 = time.monotonic_ns()
        # swarmlint: sanctioned-drain
        toks, lps = jax.device_get((self._last_tokens, self._last_lps))
        t_sync1 = time.monotonic_ns()
        self.tracer.span_end(t_sync0, "engine.host_sync", cat="engine")
        self.metrics.counters["engine_host_syncs"].inc()
        self._host_sync_n += 1
        self.metrics.counters["phase_us_host_sync"].inc(
            (t_sync1 - t_sync0) // 1000)
        now = time.time()
        for i in rows:
            s = self.slots[i]
            if not s.active:
                continue
            if s.cancelled:
                self._retire(i, "cancelled")
                continue
            s.pending_token = False
            self._emit_token(i, int(toks[i]), now, logprob=float(lps[i]))
            if s.active:
                # emit retires max_new<=1 on "length"/"eos"; this only
                # fires for a degenerate max_new=0 request
                self._retire(i, "length")

    def _process_block(self, all_toks, all_lps, snapshot,
                       t_dispatch_ns: int = 0, variant: int = -1,
                       routing=None) -> None:
        """Fetch one dispatched chunk's [K+1, B] token block (+ matching
        raw-model logprobs, + a routed configuration's [K, B, L_routed, k]
        routing) with the one host sync and emit its tokens.

        Token (s+1, i) was sampled at write position ``pos0_i + s`` —
        emission stops at a slot's EOS / max_new_tokens / max_seq and the
        remainder of its lane is discarded garbage.
        """
        t_sync0 = time.monotonic_ns()
        # everything else in the hot path rides jit dispatches; this is
        # the scan path's per-chunk drain (the resident emission ring
        # replaces it with one drain per SESSION — _run_resident)
        # swarmlint: sanctioned-drain
        block, lps, routing = jax.device_get((all_toks, all_lps, routing))
        t_sync1 = time.monotonic_ns()
        # the sanctioned sync is itself a span + counter: the flight
        # recorder and bench phase breakdown both need "how much wall
        # time went to host<->device" to be a first-class number
        self.tracer.span_end(t_sync0, "engine.host_sync", cat="engine")
        self.metrics.counters["engine_host_syncs"].inc()
        self._host_sync_n += 1
        self.metrics.counters["phase_us_host_sync"].inc(
            (t_sync1 - t_sync0) // 1000)
        if t_dispatch_ns and variant >= 0:
            # scan-path device time: dispatch -> drained (pipelined
            # chunks overlap, so per-variant sums can exceed wall clock
            # — same stance as phase_us_decode)
            self._prof.dispatch(PROF_DECODE_KEYS[variant], t_dispatch_ns,
                                t_sync1 - t_dispatch_ns)
        self._process_host_block(np.asarray(block), np.asarray(lps),
                                 snapshot, t_dispatch_ns, routing=routing)

    # swarmlint: hot
    def _process_host_block(self, block, lps, snapshot,
                            t_dispatch_ns: int = 0, chunk: int = 0,
                            routing=None, stamp_ns: int = 0,
                            defer: bool = False) -> None:
        """Pure host-side half of block processing, in two passes over
        the block: ``_settle_block`` (the slots' state: tokens taken,
        positions, retirements, pages marked for the reclaim) and
        ``_deliver_block`` (everything anybody is told: ``on_token`` and
        ``on_done``, the per-chunk spans, counters, the flight record,
        the ``engine.emit`` phase). Fed numpy blocks by BOTH paths, on
        the engine thread in both: the scan path after its per-chunk
        drain, and the resident session's consumer (``_resident_block``),
        which runs a chunk behind the device and is not waited for by it.
        The passes run back to back but for one block: the one that ends
        a resident session for work to admit (``defer``) is settled here,
        where the device stands still, and delivered behind the
        admission round's wave (``_deliver_pending``).

        ``chunk`` is the block's index in its resident session (a scan
        dispatch is one chunk a loop step: 0). ``routing`` is the chunk's
        [K, B, L_routed, k] where the configuration routes: row
        ``[s, i]`` is the routing of the token slot ``i`` was FED at step
        ``s``, so it joins the slot's record when the token that step
        sampled is read. ``stamp_ns`` is when the resident callback took
        the block (0 on the scan path): how far behind it the delivery
        begins is ``engine.emit``'s ``behind_us``."""
        # blocks are delivered in order, whatever was deferred
        self._deliver_pending()
        # a resident session keeps the engine thread here or on the FIFO
        # for as long as it lasts, so a processed block is how a live
        # lane proves progress — beat HERE, not just in the loop
        self._beat()
        if defer:
            self._undelivered = self._settle_block(
                block, lps, snapshot, t_dispatch_ns, chunk, routing,
                stamp_ns)
            return
        t_emit = self.tracer.phase_begin("engine.emit")
        done = self._settle_block(block, lps, snapshot, t_dispatch_ns,
                                  chunk, routing, stamp_ns)
        self.tracer.phase_end(t_emit, "engine.emit", cat="engine",
                              args=self._deliver_block(done, False))

    # swarmlint: hot
    def _deliver_pending(self) -> None:
        """Deliver the block a session's end left settled, if any: behind
        the admission round's wave, as its ``engine.emit`` says."""
        done, self._undelivered = self._undelivered, None
        if done is None:
            return
        t_emit = self.tracer.phase_begin("engine.emit")
        self.tracer.phase_end(t_emit, "engine.emit", cat="engine",
                              args=self._deliver_block(done, True))

    # swarmlint: hot
    def _settle_block(self, block, lps, snapshot, t_dispatch_ns: int = 0,
                      chunk: int = 0, routing=None,
                      stamp_ns: int = 0) -> _SettledBlock:
        """First pass over a block: bring every live slot of ``snapshot``
        to the state the block leaves it in, and tell nobody. A slot takes
        its tokens (``generated``, ``logprobs``, ``pending_token``,
        ``position``, its routing rows) up to where it retires, by EOS,
        length, ``max_seq`` or a flagged cancel: the three retirements a
        vote reckons (``_resident_vote``) and the cancel it reads, decided
        here and nowhere else; a retired slot's pages are marked for the
        reclaim. What ``_deliver_block`` owes each request is returned,
        with nothing in it that reads a slot again: admission may fill a
        retired slot before the delivery runs."""
        done = _SettledBlock()
        done.t_begin_ns = time.monotonic_ns()
        done.snapshot = snapshot
        done.t_dispatch_ns, done.chunk = t_dispatch_ns, chunk
        done.stamp_ns = stamp_ns
        done.emits = emits = []
        # routed: the rows live slots read
        done.live_rows = live_rows = []
        # live slots of this chunk, and over them the pages each owns
        # and how many of those its written extent covers
        n_live = pages_reserved = pages_written = 0
        alloc = self.paged.allocator if self.paged else None
        K = self.decode_chunk
        for i, req, pos0 in snapshot:
            s = self.slots[i]
            if not s.active or s.request is not req:
                continue  # retired mid-flight (possibly re-admitted)
            n_live += 1
            if alloc is not None:
                # pages the row references but the slot does not own
                # (cache hits, pages registered to the cache) hold whole
                # prompt pages: written, and nobody's reservation
                owned = len(alloc.pages_for(i))
                shared = max(0, s.row_pages - owned)
                pages_reserved += owned
                pages_written += min(owned, max(
                    0, -(-s.position // alloc.page_size) - shared))
            em = _SlotEmit(i, req)
            emits.append(em)
            if s.cancelled:
                em.retired = self._settle_retire(i, "cancelled")
                continue
            if s.pending_token:
                # row 0 is the fed token == what this slot sampled in a
                # prefill wave (its first token, or the one it rode for),
                # which the host deliberately never fetched there
                s.pending_token = False
                self._settle_token(em, int(block[0, i]), float(lps[0, i]))
            taken = 0      # steps of this chunk whose output the slot read
            if routing is not None and s.active:
                # the slot's K rows of the chunk, copied once (a view
                # would keep every lane's rows alive with the slot's): a
                # slot that retires inside the loop below has its record
                # cut to the outputs it read (_finish_routing)
                col = np.array(routing[:, i])
                s.routing.append(col)
            for step in range(K):
                if not s.active:
                    break
                if pos0 + step >= self.max_seq:
                    # the cache lane is full; later writes were dropped
                    em.retired = self._settle_retire(i, "max_seq")
                    break
                taken += 1
                self._settle_token(em, int(block[step + 1, i]),
                                   float(lps[step + 1, i]))
            if s.active:
                s.position = pos0 + K
            if routing is not None and taken:
                live_rows.append(col[:taken])
        done.n_live = n_live
        done.pages_reserved, done.pages_written = (pages_reserved,
                                                   pages_written)
        done.settle_us = (time.monotonic_ns() - done.t_begin_ns) // 1000
        return done

    # swarmlint: hot
    def _deliver_block(self, done: _SettledBlock,
                       behind_wave: bool) -> Dict[str, Any]:
        """Second pass: tell everybody what ``_settle_block`` decided. A
        request's ``on_token`` calls in order and its ``on_done`` after
        its last token, the slots in the snapshot's order; the per-chunk
        spans and counters; the expert load. Returns the arguments of the
        pass's ``engine.emit`` phase, which its caller opened: over both
        passes where they run back to back, over this one alone where an
        admission round's dispatch went between them (``behind_wave``;
        the settling then stood in ``engine.session``, ``settle_us``
        long)."""
        t_begin_ns = (time.monotonic_ns() if behind_wave
                      else done.t_begin_ns)
        t_dispatch_ns, snapshot = done.t_dispatch_ns, done.snapshot
        if t_dispatch_ns:
            # per-chunk latency, dispatch -> processed (pipelined chunks
            # overlap, so sums can exceed wall clock — documented); on
            # the resident path the stamp is the previous emission, so
            # this is the chunk's device wall time
            self.metrics.counters["phase_us_decode"].inc(
                (done.t_begin_ns - t_dispatch_ns) // 1000)
            # exemplar rid: the chunk covers every snapshot slot; tag it
            # with the first one so a tail decode-chunk bucket opens a
            # representative trace (tuple indexing, no allocation)
            HIST_DECODE_CHUNK.observe(
                (done.t_begin_ns - t_dispatch_ns) / 1e9,
                snapshot[0][1].request_id if snapshot else None)
            for _i, req, _pos0 in snapshot:
                # one decode-chunk span per live snapshot slot: these are
                # the leaves of a request's exported timeline
                self.tracer.span_end(t_dispatch_ns, "engine.decode_chunk",
                                     cat="engine", rid=req.request_id)
        now = time.time()
        for em in done.emits:
            self._deliver_emit(em, now)
        c = self.metrics.counters
        c["decode_slot_chunks"].inc(done.n_live)
        if self._snapshots:
            c["ssm_state_rows_walked"].inc(done.n_live)
            c["ssm_state_rows_held"].inc(self.max_batch)
        c["kv_page_chunks_reserved"].inc(done.pages_reserved)
        c["kv_page_chunks_written"].inc(done.pages_written)
        args = {"step": self._loop_step, "chunk": done.chunk,
                "live": done.n_live}
        if done.stamp_ns:
            args["behind_us"] = (t_begin_ns - done.stamp_ns) // 1000
            args["behind_wave"] = behind_wave
            args["settle_us"] = done.settle_us
        if done.live_rows:
            # what the reservoir observed of this chunk, a routed layer
            # a ratio: a reader that cannot reach the registry reads it
            # here (benchmark/layer_metrics/moe_load_max_over_mean_p50.py)
            args["moe_load"] = self._observe_load(done.live_rows)
        return args

    def _observe_load(self, live_rows: List[np.ndarray]) -> List[float]:
        """Expert load of one decode chunk, from the routing of the rows
        live slots read, a slot ``[steps it read, L_routed, k]`` (dead
        lanes and padding, which the device's own mean load counts, are
        left out). Balance, a layer an observation: the busiest expert's
        assignments over the mean; 1.0 is even, ``E / k`` every row on
        the same experts. Reach, a decode step and a routed layer:
        ``moe_expert_hits`` the distinct experts the step's live rows
        chose, ``moe_expert_step_slots`` the ``E`` it could have: the
        share of the expert weights a step has to read. Returns the
        chunk's balance ratios, a routed layer each."""
        l_routed, k, n_experts = self._routed
        rows = np.concatenate(live_rows)
        # one count over (layer, expert), whatever the depth
        layer = np.arange(l_routed, dtype=np.int32)[None, :, None] * n_experts
        counts = np.bincount((layer + routing_experts(rows)).ravel(),
                             minlength=l_routed * n_experts)
        busiest = counts.reshape(l_routed, n_experts).max(axis=1)
        reservoir = self.metrics.latencies["moe_load_max_over_mean"]
        ratios = (busiest * (n_experts / (len(rows) * k))).tolist()
        for ratio in ratios:
            reservoir.observe(ratio)
        steps = max(len(r) for r in live_rows)
        held = getattr(self, "_held_experts", None)
        # one column more: where the choices that are not held land
        hit = np.zeros((steps, l_routed * n_experts + 1), bool)
        for r in live_rows:
            at = layer + routing_experts(r)
            if held is not None:
                at = np.where(routing_dropped(r), l_routed * n_experts, at)
            np.put_along_axis(hit[:len(r)], at.reshape(len(r), -1), True, 1)
        c = self.metrics.counters
        c["moe_expert_hits"].inc(int(hit[:, :-1].sum()))
        c["moe_expert_step_slots"].inc(
            steps * l_routed * (held[1] if held else n_experts))
        return ratios

    def _finish_routing(self, ret: _Retired) -> None:
        """Hand a retired occupant its routing record (GenRequest.routing,
        before on_done fires) and count it. The record is what the slot
        gathered, cut to the positions whose output was read: the prompt,
        and a row a sampled token but the last (the slot's last chunk
        brought all its K steps). One that holds fewer rows than that is
        incomplete whatever its path said: forwards that report nothing,
        a wave that never landed. ``moe_assignments`` /
        ``moe_dropped_assignments`` are the token-choices of the rows THIS
        request computed (its prefill and its decode steps, not the rows
        its cached pages came with) and those of them that fell over the
        capacity; ``routing_incomplete_requests`` the records that lack
        positions."""
        req, parts = ret.req, ret.routing
        c = self.metrics.counters
        l_routed, k, _e = self._routed
        try:
            landed = [p if isinstance(p, np.ndarray) else p.get()
                      for p in parts]
        except Exception:
            # the wave never landed (the dispatch that computed it failed:
            # this retirement is the recovery's): no rows, and counted
            logger.exception("routing of %s did not land", req.request_id)
            landed = []
        sampled = len(ret.generated) + (ret.reason == "eos")
        need = len(req.prompt) + max(sampled - 1, 0)
        rows = (np.concatenate(landed) if landed
                else np.zeros((0, l_routed, k), np.int16))[:need]
        req.routing = rows
        req.routing_complete = ret.routing_complete and len(rows) == need
        mine = rows[sum(len(p) for p in landed[:ret.cached_parts]):]
        c["moe_assignments"].inc(int(mine.size))
        left_out = int(routing_dropped(mine).sum())
        if self._held_experts is None:
            c["moe_dropped_assignments"].inc(left_out)
        else:
            # nothing falls over a capacity: what is left out here is
            # computed where it is held
            c["moe_dropped_assignments"].inc(0)
            c["moe_held_assignments"].inc(int(mine.size) - left_out)
        if not req.routing_complete:
            c["routing_incomplete_requests"].inc()

    # swarmlint: hot
    def _emit_token(self, slot_id: int, token: int,
                    now: Optional[float] = None,
                    logprob: Optional[float] = None) -> None:
        """Record a sampled token for a slot, stream it, retire if
        finished: the two passes of ``_process_host_block`` on one token,
        for a caller that has a token and no block
        (``_drain_prefill_only``)."""
        em = _SlotEmit(slot_id, self.slots[slot_id].request)
        self._settle_token(em, token, logprob)
        self._deliver_emit(em, now or time.time())

    # swarmlint: hot
    def _settle_token(self, em: _SlotEmit, token: int,
                      logprob: Optional[float] = None) -> None:
        """A slot takes a sampled token: its state moves (``generated``,
        ``logprobs``; the retirement where the token is EOS or the
        request's last) and ``em`` notes what is owed for it."""
        slot = self.slots[em.slot_id]
        if slot.first_token_at is None and em.first is None:
            # its request's first, EOS or not: ``_deliver_emit`` stamps it
            em.first = (slot.admitted_at, slot.cached_tokens,
                        slot.new_tokens)
        if token == self.eos_id:
            em.retired = self._settle_retire(em.slot_id, "eos")
            return
        slot.generated.append(token)
        if logprob is not None:
            slot.logprobs.append(logprob)
        em.tokens.append(token)
        if len(slot.generated) >= em.req.sampling.max_new_tokens:
            em.retired = self._settle_retire(em.slot_id, "length")

    # swarmlint: hot
    def _deliver_emit(self, em: _SlotEmit, now: float) -> None:
        """Tell a request what ``_settle_token`` noted for it: its first
        token's stamps, its tokens to ``on_token`` in order, and its
        retirement after them."""
        req, ret = em.req, em.retired
        if em.first is not None:
            admitted_at, cached_tokens, new_tokens = em.first
            slot = self.slots[em.slot_id]
            if ret is not None:
                ret.first_token_at = now
            elif slot.request is req:
                slot.first_token_at = now
            self._lat_first_token.observe(now - req.submitted_at)
            HIST_TTFT.observe(now - req.submitted_at, req.request_id)
            # admission (prefill start) -> first token out: the prefill
            # waves and the decode chunk the token rode out with
            self.tracer.span_at(
                "engine.first_token", admitted_at or now, now,
                cat="engine", rid=req.request_id,
                args={"step": self._loop_step,
                      "mid": req.metadata.get("message_id"),
                      "cached_tokens": cached_tokens,
                      "new_tokens": new_tokens})
        for token in em.tokens:
            self.total_generated += 1
            self.metrics.rates["tokens_generated"].mark(now)
            self.metrics.counters["tokens_generated"].inc()
            if req.on_token is not None:
                try:
                    req.on_token(req.request_id, token)
                except Exception:
                    logger.exception("on_token callback failed")
        if ret is not None:
            self._deliver_retired(ret)

    def _retire(self, slot_id: int, reason: str) -> None:  # swarmlint: hot
        """Retire a slot's occupant and tell it so at once (a failure, a
        cancel, a prefill lane's drain): both passes, as a block's
        retirements go through."""
        self._deliver_retired(self._settle_retire(slot_id, reason))

    # swarmlint: hot
    def _settle_retire(self, slot_id: int, reason: str) -> _Retired:
        """The slot's half of a retirement: it is free from here on (the
        votes and the next round read that), its pages go to their next
        owner (the caller of a rolling conversation, else the reclaim),
        its pins are dropped. Returns what ``_deliver_retired`` needs of
        the occupant, which the slot forgets."""
        slot = self.slots[slot_id]
        req = slot.request
        slot.active = False
        slot.request = None
        ret = _Retired()
        ret.req, ret.reason = req, reason
        ret.generated, ret.logprobs = slot.generated, slot.logprobs
        ret.admitted_at = slot.admitted_at
        ret.first_token_at = slot.first_token_at
        # sanctioned host syncs this request's lifetime spanned, +1 for
        # the drain its retirement rides in (the resident session's
        # drain lands AFTER its last block). Scan path: ~one per chunk;
        # resident path: admit + drain (+ final)
        ret.host_syncs = self._host_sync_n - slot.admit_syncs + 1
        ret.routing, slot.routing = slot.routing, None
        ret.routing_complete = slot.routing_complete
        ret.cached_parts = slot.cached_parts
        if self.paged:
            if req is not None and req.keep_pages:
                # rolling KV: hand the conversation's pages to the caller
                # instead of freeing. written_len = host-confirmed written
                # extent (chunk-granular); emitted tokens past it have no
                # K/V yet and ride back as tail_tokens for the caller to
                # prepend to the next turn's suffix (re-feeding rewrites
                # their K/V identically — same context).
                fresh = self.paged.allocator.pages_for(slot_id)
                self.paged.allocator.transfer_to_cache(slot_id, fresh)
                all_pages = list(req.resume_pages or []) + fresh
                written = slot.position
                start = req.resume_len + len(req.prompt)
                tail = list(slot.generated[max(0, written - start):])
                ps = self.paged.page_size
                covering = -(-written // ps) if written > 0 else 0
                kept, extras = all_pages[:covering], all_pages[covering:]
                if extras:
                    self.paged.allocator.add_free(extras)
                if req.on_pages is not None:
                    try:
                        req.on_pages(req.request_id, kept, written, tail)
                    except Exception:
                        logger.exception("on_pages callback failed")
            # pages stay owned (absorbing end-of-chunk garbage writes) until
            # the next admission round zeroes the table row and frees them
            self.paged.allocator.mark_retired(slot_id)
            pins = self._slot_prefix_pins.pop(slot_id, None)
            if pins:
                # eviction/rewrite of these pages can only be DISPATCHED
                # after this point, so any in-flight chunk's reads (issued
                # earlier) complete first — device program order
                self._prefix.unpin(pins)
        elif (req is not None and req.keep_pages
              and reason in ("length", "eos")
              and getattr(self, "_extract_lane_fused", None) is not None):
            # clean finishes only: failure retirements (_fail_all during
            # error recovery) run BEFORE the donated cache/pool buffers
            # are rebuilt, and a device dispatch here would raise on the
            # deleted arrays and kill the recovery itself
            try:
                self._dense_keep_extract(slot_id, slot, req)
            except Exception:
                logger.exception("dense keep extraction failed")
        return ret

    # swarmlint: hot
    def _deliver_retired(self, ret: _Retired) -> None:
        """The occupant's half of a retirement: the completion counter,
        the flight record, its log-probs and routing record, ``on_done``."""
        req = ret.req
        self.metrics.counters["engine_completed"].inc()
        if req is None:
            return
        # flight-recorder request timeline (ring write, engine thread)
        self.flight.record_request({
            "rid": req.request_id,
            "priority": req.priority,
            "prompt_len": len(req.prompt) + req.resume_len,
            "generated": len(ret.generated),
            "reason": ret.reason,
            "submitted_at": req.submitted_at,
            "admitted_at": ret.admitted_at,
            "first_token_at": ret.first_token_at,
            "retired_at": time.time(),
            "host_syncs": ret.host_syncs,
        })
        # raw-model logprobs of the generated tokens (parallel list);
        # delivered via request metadata so on_done's signature stays
        req.metadata["logprobs"] = list(ret.logprobs)
        if ret.routing is not None:
            self._finish_routing(ret)
        if req.on_done is not None:
            try:
                req.on_done(req.request_id, list(ret.generated), ret.reason)
            except Exception:
                logger.exception("on_done callback failed")

    # swarmlint: hot
    def _dense_keep_extract(self, slot_id: int, slot: _Slot,
                            req: GenRequest) -> None:
        """Dense rolling-KV retirement (see _extract_lane in __init__):
        copy the lane's written KV into acquired prefix-pool pages and
        hand custody to on_pages. The last page may be PARTIAL (written
        is mid-page); its tail bytes are stale lane garbage, masked at
        resume by prefix_lens=written. On pool shortage the turn simply
        doesn't roll: no on_pages, the caller's registry keeps its
        previous state (whose pages we then must NOT release)."""
        ps = self._prefix_ps
        written = slot.position
        start = req.resume_len + len(req.prompt)
        tail = list(slot.generated[max(0, written - start):])
        n = -(-written // ps) if written > 0 else 0
        if not (0 < n <= self._prefix_pp_buckets[-1]):
            return
        # escalation ladder for the page budget: plain acquire ->
        # self-reuse (release the superseded SOURCE pages first: their
        # last reads — the resume prefill; this extraction gathers the
        # LANE, not them — were dispatched earlier, so any re-acquirer's
        # writes land after those reads in device program order; without
        # this a resumed conversation needs 2x its footprint live during
        # extraction and rolls starve at half-pool occupancy) ->
        # pressure hook (LRU-evict parked conversations)
        released_source = False
        pages: List[int] = self._prefix.acquire(n)
        if len(pages) != n and req.resume_pages:
            for p in pages:
                self._prefix.release(p)
            for p in req.resume_pages:
                self._prefix.release(p)
            released_source = True
            pages = self._prefix.acquire(n)
        if len(pages) != n and self.on_pool_pressure is not None:
            for p in pages:
                self._prefix.release(p)
            try:
                self.on_pool_pressure(n)
            except Exception:
                logger.exception("pool-pressure callback failed")
            pages = self._prefix.acquire(n)
        if len(pages) != n:
            for p in pages:
                self._prefix.release(p)
            if released_source and req.on_pages is not None:
                # the registry's kept state now references freed pages —
                # hand it an EMPTY state (the serving layer treats
                # pages=[] as restart-next-turn) instead of leaving
                # dangling ids behind
                try:
                    req.on_pages(req.request_id, [], 0, [])
                except Exception:
                    logger.exception("on_pages callback failed")
            return
        target = np.zeros(self.max_seq // ps, np.int32)
        target[: n] = pages
        pk, pv = self._prefix_pool
        t0_ns = time.monotonic_ns() if self._prof.enabled else 0
        try:
            pk, pv = self._extract_lane_fused(
                self.cache, pk, pv, np.int32(slot_id), target)
            if t0_ns:
                self._prof.dispatch("extract.lane", t0_ns,
                                    time.monotonic_ns() - t0_ns)
        except Exception:
            # dispatch failed: nothing read `pages` on device — return
            # them. If the source pages were already self-reuse-released
            # above, the registry still references freed ids: hand it an
            # empty state (review r5 #2: letting _rolling_finalize free
            # st["pages"] AGAIN would put duplicates on the free list —
            # two conversations acquiring the same page)
            for p in pages:
                self._prefix.release(p)
            if released_source and req.on_pages is not None:
                try:
                    req.on_pages(req.request_id, [], 0, [])
                except Exception:
                    logger.exception("on_pages callback failed")
            raise
        self._prefix_pool = (pk, pv)
        if req.resume_pages and not released_source:
            # superseded SOURCE pages (safe for the same program-order
            # reason as the early release above)
            for p in req.resume_pages:
                self._prefix.release(p)
        if req.on_pages is not None:
            try:
                req.on_pages(req.request_id, pages, written, tail)
            except Exception:
                logger.exception("on_pages callback failed")

    def _fail_all(self, reason: str) -> None:
        # what a boundary block still owes its requests comes first
        self._deliver_pending()
        for i, s in enumerate(self.slots):
            if s.active:
                self._retire(i, reason)
        planned = self._release_held_plan(requeue=False)
        with self._cv:
            pending = planned + [item[3] for item in self._queue]
            self._queue.clear()
            self._admitting.clear()
            self._cancel_pending.clear()
        for req in pending:
            if req.on_done is not None:
                try:
                    req.on_done(req.request_id, [], reason)
                except Exception:
                    pass

    # ------------------------------------------------------------------ info

    def stats(self) -> Dict[str, Any]:
        # caught by swarmlint SWL301 on landing the guard declarations:
        # len() of a mutating heap from outside the engine lock
        with self._cv:
            queued = self._waiting()
        out = {
            "active_slots": sum(1 for s in self.slots if s.active),
            "max_batch": self.max_batch,
            "queued": queued,
            "total_requests": self.total_requests,
            "total_generated": self.total_generated,
            "tokens_per_sec_60s": self.metrics.rates["tokens_generated"].rate(),
            "latencies": {
                k: self.metrics.latencies[k].summary()
                for k in ("queue_wait_s", "prefill_s", "first_token_s")
                if k in self.metrics.latencies
            },
        }
        if self._prefix is not None:
            out["prefix_cache"] = self._prefix.stats()
        if self.paged is not None:
            out["pool_headroom"] = round(self._pool_headroom(), 4)
            out["admission_paused"] = self._bp_paused
        return out
