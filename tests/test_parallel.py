"""Parallelism layer tests on the 8-virtual-device CPU mesh (conftest.py).

Validates the strategies SURVEY §2.4 requires (the reference has none):
mesh factorization, TP param sharding, DP cache sharding, EP expert
sharding, and numerical equivalence of the sharded forward against the
single-device forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from swarmdb_tpu.models import llama, mixtral
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.parallel import (
    build_serving_engine,
    build_sharded_model,
    make_mesh,
    plan_mesh_shape,
    shard_pytree,
)


def test_plan_mesh_shape_factorizes():
    assert plan_mesh_shape(8, want_model=2, want_expert=2) == {
        "data": 2, "model": 2, "expert": 2, "pipe": 1}
    shape = plan_mesh_shape(8, want_model=2, want_expert=1)
    assert shape == {"data": 4, "model": 2, "expert": 1, "pipe": 1}
    with pytest.raises(ValueError):
        plan_mesh_shape(8, want_model=3)


def test_make_mesh_axes():
    mesh = make_mesh(8, data=2, model=2, expert=2)
    assert dict(mesh.shape) == {"data": 2, "model": 2, "expert": 2, "pipe": 1}
    assert mesh.devices.size == 8


def test_shard_pytree_places_leaves():
    mesh = make_mesh(8, data=4, model=2, expert=1)
    tree = {"w": jnp.zeros((8, 6)), "b": jnp.zeros((6,))}
    specs = {"w": P("data", "model"), "b": P(None)}
    out = shard_pytree(tree, specs, mesh)
    # each data x model shard of w is (2, 3)
    shard_shapes = {s.data.shape for s in out["w"].addressable_shards}
    assert shard_shapes == {(2, 3)}
    assert out["b"].sharding.is_fully_replicated


def test_sharded_llama_matches_single_device():
    """TP x DP sharded forward == unsharded forward (same params)."""
    cfg = get_config("tiny-debug")
    mesh = make_mesh(8, data=4, model=2, expert=1)
    sm = build_sharded_model(cfg, mesh, seed=0)

    batch, seq = 4, 16
    tokens = jnp.asarray(np.arange(batch * 4).reshape(batch, 4) % 100 + 3)
    positions = jnp.tile(jnp.arange(4)[None], (batch, 1))
    cache = sm.init_cache_fn(batch, seq)

    logits_sharded, _ = jax.jit(sm.forward_fn)(sm.params, tokens, positions, cache)

    host_params = jax.device_get(sm.params)
    host_cache = llama.init_kv_cache(cfg, batch, seq)
    logits_ref, _ = llama.forward(host_params, cfg, tokens, positions, host_cache)

    np.testing.assert_allclose(
        np.asarray(logits_sharded), np.asarray(logits_ref), rtol=0.1, atol=0.1
    )


def test_sharded_mixtral_ep_matches_single_device():
    """EP-sharded MoE forward == unsharded forward."""
    cfg = get_config("tiny-moe")
    mesh = make_mesh(8, data=2, model=1, expert=4)
    sm = build_sharded_model(cfg, mesh, seed=0)

    batch, seq = 2, 16
    tokens = jnp.asarray(np.arange(batch * 4).reshape(batch, 4) % 100 + 3)
    positions = jnp.tile(jnp.arange(4)[None], (batch, 1))
    cache = sm.init_cache_fn(batch, seq)

    # a routed configuration's serving forward reports its routing last
    logits_sharded, _, routing = jax.jit(sm.forward_fn)(
        sm.params, tokens, positions, cache)

    host_params = jax.device_get(sm.params)
    host_cache = mixtral.init_kv_cache(cfg, batch, seq)
    logits_ref, _, routing_ref = llama.forward(
        host_params, cfg, tokens, positions, host_cache)

    np.testing.assert_allclose(
        np.asarray(logits_sharded), np.asarray(logits_ref), rtol=0.1, atol=0.1
    )
    # einsum dispatch over the expert axis chooses what scatter chooses on
    # one device (bf16: a near tie may fall the other way)
    assert routing.shape == routing_ref.shape == (batch, 4, cfg.n_layers,
                                                  cfg.experts_per_token)
    same = (np.sort(np.asarray(routing), -1)
            == np.sort(np.asarray(routing_ref), -1)).all(-1)
    assert same.mean() >= 0.9


def test_param_shards_are_actually_distributed():
    """TP must shard the big matmuls — each device holds 1/TP of wq."""
    cfg = get_config("tiny-debug")
    mesh = make_mesh(8, data=4, model=2, expert=1)
    sm = build_sharded_model(cfg, mesh, seed=0)
    wq = sm.params["layers"]["wq"]  # [L, D, Hq*hd] sharded (None, None, model)
    full = wq.shape
    for shard in wq.addressable_shards:
        assert shard.data.shape == (full[0], full[1], full[2] // 2)


def test_sharded_engine_generates():
    """The continuous-batching engine runs unmodified over a sharded model."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    mesh = make_mesh(8, data=2, model=2, expert=2)
    engine, sm = build_serving_engine(
        get_config("tiny-debug"), mesh, max_batch=4, max_seq=64
    )
    engine.start()
    try:
        toks, reason = engine.generate_sync(
            [1, 5, 9], SamplingParams(max_new_tokens=6), timeout=300
        )
        assert reason in ("length", "eos")
        assert len(toks) <= 6
    finally:
        engine.stop()


def test_sharded_paged_spec_carries_its_chunk_triple():
    """``build_sharded_paged`` returns the spec whole: the shard-mapped
    chunk triple rides ``PagedKV.chunked_fns`` (no third return value),
    the engine built from it decodes with that triple, and a caller's
    ``chunked_fns`` beside a page pool is refused by name."""
    from swarmdb_tpu.parallel.serving import (build_sharded_model,
                                              build_sharded_paged)

    mesh = make_mesh(8, data=8, model=1, expert=1)
    sm = build_sharded_model(get_config("tiny-debug"), mesh, seed=0)
    spec, prefix_fns = build_sharded_paged(sm, max_batch=8, max_seq=64,
                                           page_size=8)
    assert len(spec.chunked_fns) == 3 and all(map(callable,
                                                  spec.chunked_fns))
    assert prefix_fns is not None and spec.prefill_packed is not None
    engine, _ = build_serving_engine(
        get_config("tiny-debug"), mesh, max_batch=8, max_seq=64, seed=0,
        paged=True, page_size=8, admit_overlap=False)
    assert engine._chunked_fns is engine.paged.chunked_fns
    with pytest.raises(ValueError, match=r"PagedKV\.chunked_fns"):
        build_serving_engine(
            get_config("tiny-debug"), mesh, max_batch=8, max_seq=64,
            seed=0, paged=True, page_size=8, admit_overlap=False,
            chunked_fns=sm.chunked_fns)


def test_sharded_paged_engine_matches_dense_sharded():
    """The DP-sharded PAGED fast path (VERDICT r4 #2): pool/table sharded
    over an 8-way data axis, slot→shard-affine allocator, shard_map'd
    collective-free decode — and greedy tokens must match the dense
    sharded engine exactly (same model, same prompts)."""
    from swarmdb_tpu.backend.sampling import SamplingParams

    prompts = [[1, 5, 9, 13, 2], list(range(3, 40)), [7, 7, 7]]

    def run(paged):
        mesh = make_mesh(8, data=8, model=1, expert=1)
        engine, sm = build_serving_engine(
            get_config("tiny-debug"), mesh, max_batch=8, max_seq=64,
            seed=0, paged=paged, page_size=8, admit_overlap=False,
        )
        if paged:
            alloc = engine.paged.allocator
            assert alloc.n_shards == 8
            assert engine.paged.num_pages == alloc.pages_per_shard * 8
        engine.start()
        try:
            return [
                engine.generate_sync(
                    p, SamplingParams(max_new_tokens=6, temperature=0.0),
                    timeout=600)[0]
                for p in prompts
            ]
        finally:
            engine.stop()

    dense = run(False)
    paged = run(True)
    assert dense == paged, (dense, paged)


def test_sharded_paged_chunked_matches_unsharded_engine():
    """Chunked decode under ``shard_map`` reads the SHARD's pool through
    its flat view, so the per-layer table offset is the shard's local
    page count: three layers over a 4-way data axis, concurrent requests
    on every shard, greedy tokens equal to the single-device paged
    engine's (same weights, same prompts)."""
    import threading

    from swarmdb_tpu.backend.engine import GenRequest
    from swarmdb_tpu.backend.sampling import SamplingParams
    from swarmdb_tpu.backend.service import build_backend_engine

    cfg = get_config("tiny-debug", n_layers=3)
    prompts = [[1, 5, 9, 13, 2], list(range(3, 40)), [7, 7, 7], [4, 4],
               [9, 8, 7, 6, 5, 4], [11], [2, 3, 5, 7, 11, 13, 17], [6] * 12]
    kw = dict(max_batch=8, max_seq=64, seed=0, paged=True, page_size=8,
              decode_chunk=4)

    def run(engine):
        out, done = {}, threading.Event()

        def on_done(i):
            def cb(rid, toks, reason):
                out[i] = list(toks)
                if len(out) == len(prompts):
                    done.set()
            return cb

        engine.start()
        try:
            for i, p in enumerate(prompts):
                engine.submit(GenRequest(
                    prompt=p, on_done=on_done(i),
                    sampling=SamplingParams(max_new_tokens=10,
                                            temperature=0.0)))
            assert done.wait(600), f"{len(out)} of {len(prompts)} finished"
        finally:
            engine.stop()
        return [out[i] for i in range(len(prompts))]

    sharded, _sm = build_serving_engine(
        cfg, make_mesh(4, data=4, model=1, expert=1), admit_overlap=False,
        **kw)
    assert sharded.paged.allocator.n_shards == 4
    single, _tok = build_backend_engine(cfg, **kw)
    assert run(sharded) == run(single)


def test_sharded_paged_requires_pure_dp_mesh():
    mesh = make_mesh(8, data=4, model=2, expert=1)
    with pytest.raises(ValueError, match="pure-DP"):
        build_serving_engine(get_config("tiny-debug"), mesh, max_batch=4,
                             max_seq=64, paged=True, page_size=8)


def test_sharded_allocator_slot_affinity():
    from swarmdb_tpu.ops.paged_kv import ShardedPageAllocator

    a = ShardedPageAllocator(8, 4, 8, 64, 8)  # 8 pages/shard, 4 shards
    # slot 5 -> shard 2 -> ids in [16, 24), never 16 (shard trash)
    row = a.allocate(5, 3)
    assert a.shard_of(5) == 2
    assert all(16 < p < 24 for p in row[:3]), row
    # prefix usability truncates at the first foreign-shard page
    assert a.usable_prefix(5, [17, 18, 19]) == 3
    assert a.usable_prefix(5, [17, 9, 19]) == 1
    assert a.usable_prefix(0, [17, 18]) == 0
    # shard exhaustion is per-shard: draining shard 2 leaves others alone
    assert a.allocate(4, 4) is not None  # slot 4 also shard 2 -> 0 left
    with pytest.raises(RuntimeError, match="already holds"):
        a.allocate(5, 1)  # double-allocation is a bug, not a shortage
    assert a.free_count(1) == 7  # slot 1 -> shard 0 untouched
    assert a.free_count(5) == 0
    # frees route back to the owning shard
    a.add_free([23])
    assert a.free_count(5) == 1


def test_graft_entry_single_chip():
    """entry() must return a jittable fn + args (driver contract)."""
    import __graft_entry__ as ge
    import os

    os.environ["SWARMDB_ENTRY_MODEL"] = "tiny-debug"
    try:
        fn, args = ge.entry()
        logits, cache = jax.jit(fn)(*args)
        assert logits.shape[0] == args[1].shape[0]
    finally:
        del os.environ["SWARMDB_ENTRY_MODEL"]


def test_dp_paged_admission_spreads_shards():
    """Light load on a DP-sharded paged engine must spread across the
    shards' sub-pools (id-order admission would exhaust shard 0's pool
    while the others idle — review r5)."""

    from swarmdb_tpu.backend.engine import GenRequest
    from swarmdb_tpu.backend.sampling import SamplingParams
    from swarmdb_tpu.parallel.mesh import make_mesh
    from swarmdb_tpu.parallel.serving import build_serving_engine

    engine, _sm = build_serving_engine(
        "tiny-debug", make_mesh(8, data=8, model=1, expert=1),
        max_batch=16, max_seq=64, decode_chunk=4, prefill_buckets=[16],
        paged=True, page_size=8, admit_overlap=False,
    )
    alloc = engine.paged.allocator
    assert alloc.n_shards == 8
    engine.start()
    results = []
    try:
        for i in range(4):
            engine.submit(GenRequest(
                prompt=[1 + i, 2, 3],
                sampling=SamplingParams(max_new_tokens=24),
                on_done=lambda rid, toks, reason: results.append(reason),
            ))
        deadline = 90
        import time as _t
        t0 = _t.time()
        shards_seen = set()
        while _t.time() - t0 < deadline and len(results) < 4:
            with alloc._lock:
                held = list(alloc._by_slot.keys())
            shards_seen |= {alloc.shard_of(s) for s in held}
            if len(shards_seen) >= 4:
                break
            _t.sleep(0.02)
        assert len(shards_seen) >= 4, (
            f"4 concurrent requests used only shards {shards_seen}")
    finally:
        engine.stop()


def test_dp_paged_shard_hint_preserves_prefix_affinity():
    """A conversation's turns carry a shard hint: turn 2 must land on the
    same shard as turn 1's prefix-cache registrations and HIT them —
    without the hint, the load-spreading rotation scatters turns across
    shards where the cached pages are unusable (same-shard-only reuse)."""
    from swarmdb_tpu.backend.sampling import SamplingParams
    from swarmdb_tpu.parallel.mesh import make_mesh
    from swarmdb_tpu.parallel.serving import build_serving_engine

    engine, _sm = build_serving_engine(
        "tiny-debug", make_mesh(8, data=8, model=1, expert=1),
        max_batch=16, max_seq=64, decode_chunk=4, prefill_buckets=[32],
        paged=True, page_size=8, admit_overlap=False,
    )
    engine.start()
    try:
        prompt = list(range(1, 21))  # 2 full pages -> registers on hit path
        for turn in range(3):
            from swarmdb_tpu.backend.engine import GenRequest
            import threading as _th

            done = _th.Event()
            engine.submit(GenRequest(
                prompt=prompt, sampling=SamplingParams(max_new_tokens=3),
                shard_hint=5,
                on_done=lambda rid, toks, reason: done.set(),
            ))
            assert done.wait(120)
        hits = engine.metrics.counters["prefix_reused_tokens"].value
        assert hits >= 32, (  # turns 2+3 each reuse 2 pages = 16 tokens
            f"shard-hinted turns never hit the prefix cache (hits={hits})")
    finally:
        engine.stop()


def test_dp_paged_hint_falls_back_when_shard_exhausted():
    """The shard hint is advisory: a request hinted at a shard whose
    sub-pool cannot cover it must admit on another shard instead of
    head-of-line blocking the queue (review r5)."""
    import threading as _th
    import time as _t

    from swarmdb_tpu.backend.engine import GenRequest
    from swarmdb_tpu.backend.sampling import SamplingParams
    from swarmdb_tpu.parallel.mesh import make_mesh
    from swarmdb_tpu.parallel.serving import build_serving_engine

    # tiny pool: ~9 pages/shard; each request's worst case is 7 pages,
    # so a shard can hold ONE request at a time
    engine, _sm = build_serving_engine(
        "tiny-debug", make_mesh(8, data=8, model=1, expert=1),
        max_batch=16, max_seq=64, decode_chunk=4, prefill_buckets=[32],
        paged=True, page_size=8, kv_pool_tokens=512, admit_overlap=False,
    )
    alloc = engine.paged.allocator
    engine.start()
    done = [_th.Event(), _th.Event()]
    try:
        for i in range(2):
            engine.submit(GenRequest(
                prompt=list(range(1 + i, 21 + i)),
                sampling=SamplingParams(max_new_tokens=30),
                shard_hint=5,
                on_done=lambda rid, toks, reason, e=done[i]: e.set(),
            ))
        # while the first still decodes, the second must already hold
        # pages on a DIFFERENT shard (fallback admitted it)
        deadline = _t.time() + 60
        shards = set()
        while _t.time() < deadline:
            with alloc._lock:
                held = list(alloc._by_slot.keys())
            shards = {alloc.shard_of(s) for s in held}
            if len(shards) == 2:
                break
            if done[0].is_set() and done[1].is_set():
                break
            _t.sleep(0.02)
        assert len(shards) == 2, (
            f"hinted request head-of-line blocked instead of falling "
            f"back (shards seen concurrently: {shards})")
        assert done[0].wait(120) and done[1].wait(120)
    finally:
        engine.stop()


def test_sharded_warmup_plan_covers_packed_variant(tmp_path):
    """Drift guard for the SHARDED paged engine's warmup_call_plan (review
    r5: the single-chip drift test never builds an n_shards > 1 engine,
    so packed-variant drift would ship silently). The plan must contain
    the packed prefill with spec args that LOWER against the real jitted
    fn — catching the shape/dtype/arg-order/donation drift class.
    (The stronger zero-new-cache-entries property — PROFILE r5's KNOWN
    GAP, closed by Engine._pin_slot_state — is asserted end-to-end by
    test_sharded_precompile_cache_covers_warmup below.)"""
    engine, _sm = build_serving_engine(
        get_config("tiny-debug"),
        make_mesh(8, data=8, model=1, expert=1),
        max_batch=16, max_seq=64, decode_chunk=4,
        prefill_buckets=[16], paged=True, page_size=8,
        admit_overlap=False,
    )
    assert engine._packed_active()
    plan = engine.warmup_call_plan()
    packed = [(fn, specs) for fn, specs in plan
              if fn is engine._prefill_paged_packed]
    n_buckets = len(engine.prefill_buckets)
    assert len(packed) == n_buckets, (
        f"plan holds {len(packed)} packed variants for {n_buckets} "
        "buckets")
    # the GSPMD plain variant must NOT be planned (dead on sharded
    # engines — warming it would waste one big compile per bucket)
    assert not any(fn is engine._prefill_paged_fused for fn, _ in plan)
    for fn, specs in plan:
        fn.lower(*specs)  # type-checks shapes/dtypes/order for each


def test_sharded_precompile_cache_covers_warmup(compile_cache_dir):
    """Sharded warm start: parallel AOT precompile writes EXACTLY one
    persistent-cache program per warmup variant (compile-count ==
    variant-count), and the subsequent warmup() adds ZERO new entries —
    i.e. mesh-placed engines now REUSE the precompiled executables
    instead of compiling every variant twice (VERDICT r5 #6 / PROFILE r5
    finding d). The old failure mode: warmup's own decode call handed
    the fed-token vectors back in a GSPMD-chosen P('data') sharding
    where the plan's specs said replicated, so every later variant's
    eager call was a different HLO; Engine._pin_slot_state +
    place_state's canonical _state_sharding close it."""
    import swarmdb_tpu.utils.xla_cache as xla_cache

    engine, _sm = build_serving_engine(
        get_config("tiny-debug"),
        make_mesh(8, data=8, model=1, expert=1),
        max_batch=16, max_seq=64, decode_chunk=4,
        prefill_buckets=[16], paged=True, page_size=8,
        admit_overlap=False,
    )
    assert engine._packed_active()

    def programs():
        return xla_cache.persistent_cache_programs(compile_cache_dir)

    built = programs()      # what building the engine itself compiled
    engine.precompile(parallel=2)
    before = programs()
    plan = engine.warmup_call_plan()
    assert len(before - built) == len(plan), (
        f"precompile wrote {len(before - built)} programs for {len(plan)} "
        "plan variants")
    engine.warmup()
    after = programs()
    assert after == before, (
        f"sharded warmup compiled {len(after - before)} programs "
        "precompile missed — state sharding drifted between variants")
