"""AOT compiles for a described TPU v5e: what interpret mode cannot see.

The TPU's compiler is installed beside the CPU backend and compiles for
a chip that is described, not attached. Every Pallas kernel the default
TPU serving path reaches is compiled here at Llama-3-8B geometry
(32 query heads, 8 KV heads, head_dim 128, page 16, batch 8, table span
1024) — the shapes at which the chip's compiler refused the ragged
prefill kernel from W=256 up and the dense decode kernel at S=1024 while
every interpret-mode test passed. Nothing runs: a compile that passes
says nothing about results or times.

The topology is described inside a module-scoped fixture (only the
worker that runs this file loads the TPU library) and the persistent
compile cache is off around the compiles (an entry written for a
described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from swarmdb_tpu.ops import attention_pallas as ap
from swarmdb_tpu.ops import moe_pallas, ssm_pallas

HQ, HKV, D, PS, B, SPAN = 32, 8, 128, 16, 8, 1024
MAXP = SPAN // PS
PAGES = 1 + B * MAXP + B * SPAN // 2 // PS   # slots + prefix budget + trash
KC = 8                                       # decode chunk (server default)
RUNGS = (8, 16, 32, 64, 128, 256, 512, 1024)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(chip, fn, *shapes, **static):
    """Compile ``fn`` for the described chip; the kernel must be in the
    program as a Mosaic custom call, not interpreted away."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32
Q = ((B, HQ, D), BF)
QPOOL = ((PAGES, PS, HKV, D), I8)
SCALE = ((PAGES, HKV), F32)
TABLE = ((B, MAXP), I32)
ROW = ((B,), I32)
CHUNK = ((B, KC, HKV, D), BF)
STEP = ((), I32)


@pytest.mark.parametrize("b,hkv,maxp,pages,window", [
    pytest.param(B, HKV, MAXP, PAGES, None, id="llama3-8b-span1024"),
    # mistral7b.chat: 16 rows, a 256-page table, the flat 16-layer pool
    pytest.param(16, 8, 256, 73728, None, id="chat-cell-flat-pool"),
    # lfm2-8b-a1b.chat: 32 rows, 8 KV heads of 64 in 128 lanes, the flat
    # pool of its 4 attention layers (11,265 pages each)
    pytest.param(32, 8, 256, 45060, None, id="chat-lfm2-cell"),
    pytest.param(40, 8, 256, 4096, None, id="two-row-groups"),
    pytest.param(16, 4, 256, 4096, None, id="yi-G8"),
    pytest.param(16, 8, 256, 4096, 1024, id="sliding-window"),
])
def test_paged_chunked_decode_compiles(one_chip, b, hkv, maxp, pages,
                                       window):
    """The in-kernel walk (whole-batch blocks indexed by a row read from
    SMEM, pools left in HBM, pages copied by the kernel into a double
    buffer, loops over the live rows and their blocks whose trip counts
    are read from SMEM) is what the chip's compiler has to take; and the
    custom call keeps the name the benchmark's trace reader looks for."""
    pool = ((pages, PS, hkv, D), BF)
    chunk = ((b, KC, hkv, D), BF)
    compiled = _compile(
        one_chip, ap.paged_decode_gqa_attention_chunked,
        ((b, HQ, D), BF), pool, pool, ((b, maxp), I32), chunk, chunk,
        ((b,), I32), STEP, ((b,), I32), STEP, window=window)
    assert "%paged_decode_gqa_attention_chunked" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("width,hkv,window", [
    # mistral7b.chat: every rung of the ragged ladder up to max_seq 4096,
    # 16 rows, a 256-page table, the flat 16-layer pool of 73,728 pages
    *[pytest.param(w, 8, None, id=f"chat-cell-w{w}")
      for w in RUNGS + (2048, 4096)],
    pytest.param(128, 4, None, id="yi-G8-w128"),
    pytest.param(1024, 4, None, id="yi-G8-w1024"),
    pytest.param(128, 8, 1024, id="sliding-window-w128"),
])
def test_ragged_prefill_rung_compiles(one_chip, width, hkv, window):
    """The ragged prefill kernel's in-step walk (pools and suffix stream
    left in HBM, pages and tiles copied by the kernel into a double
    buffer, loops whose trip counts are read from SMEM) is what the
    chip's compiler has to take, at every width: it refused whole-stream
    residency from W=256 up while every interpret-mode test passed. The
    custom call keeps the name the benchmark's trace reader looks for,
    and nothing pool-shaped (nothing at all) is planned beside it."""
    rows, maxp, pages = 16, 256, 73728
    qs, kv = ((width, HQ, D), BF), ((width, hkv, D), BF)
    pool = ((pages, PS, hkv, D), BF)
    row = ((rows,), I32)
    compiled = _compile(
        one_chip, ap.ragged_paged_prefill_attention,
        qs, kv, kv, pool, pool, ((rows, maxp), I32), row, row, row,
        window=window)
    assert "%ragged_paged_prefill_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("rows", [
    pytest.param(32, id="lfm2-cell-decode-step"),
    pytest.param(16, id="decode-step-16"),
    pytest.param(8, id="smallest-rung"),
    pytest.param(64, id="rung-64"),
    pytest.param(256, id="rung-256"),
    pytest.param(512, id="widest-rung-that-takes-it"),
])
def test_expert_stream_kernel_compiles(one_chip, rows):
    """``lfm2-8b-a1b.chat``'s routed layer at the published widths
    (hidden 2048, 32 experts of 1792), the scanned segment's flat stack of
    3 x 32 experts left in HBM as it is stored: the kernel's double buffer
    of half experts (22 MB) is over the 16 MiB a Pallas call is given by
    default, which only the chip's compiler counts. The custom call keeps the name
    the benchmark's ``breakdown.device_ops`` shows, and nothing
    weight-shaped is planned beside it."""
    d, f, e = 2048, 1792, 32
    wide, tall = ((3 * e, d, f), BF), ((3 * e, f, d), BF)
    compiled = _compile(
        one_chip, moe_pallas.stream_experts,
        ((rows, d), BF), ((rows, e), F32), ((e,), jnp.bool_),
        wide, wide, tall, ((), I32))
    assert "%moe_stream_experts" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < d * f * 2


@pytest.mark.parametrize("b,maxp,pages", [
    pytest.param(B, MAXP, PAGES, id="llama3-8b-span1024"),
    # the chat cells' geometry under SWARMDB_KV_DTYPE=int8: 16 rows and a
    # 256-page table, which this twin still walks as a grid (16, 257)
    pytest.param(16, 256, 4096, id="chat-cell-table"),
])
def test_paged_chunked_decode_quant_compiles(one_chip, b, maxp, pages):
    qpool, scale = ((pages, PS, HKV, D), I8), ((pages, HKV), F32)
    chunk = ((b, KC, HKV, D), BF)
    _compile(one_chip, ap.paged_decode_gqa_attention_chunked_quant,
             ((b, HQ, D), BF), qpool, scale, qpool, scale,
             ((b, maxp), I32), chunk, chunk, ((b,), I32), STEP)


@pytest.mark.parametrize("width", (128, 1024))
def test_ragged_prefill_quant_compiles(one_chip, width):
    qs, kv = ((width, HQ, D), BF), ((width, HKV, D), BF)
    _compile(one_chip, ap.ragged_paged_prefill_attention_quant,
             qs, kv, kv, QPOOL, SCALE, QPOOL, SCALE, TABLE, ROW, ROW, ROW)


def test_dense_chunked_decode_compiles(one_chip):
    lane = ((B, SPAN, HKV, D), BF)
    _compile(one_chip, ap.decode_gqa_attention_chunked,
             Q, lane, lane, CHUNK, CHUNK, ROW, STEP, tile=256)


# nemotron3-nano.chat: 23 Mamba-2 layers, 32 slots, 64 heads of 64 in 8
# groups, a state of 128, a chunk of 8
SSM_POOL = ((23, 32, 4096, 128), BF)


def test_the_ssm_state_read_compiles_at_the_published_widths(one_chip):
    _compile(one_chip, ssm_pallas.state_read, SSM_POOL, STEP,
             ((32, 8, 128), F32), ((32,), I32), STEP)


def test_the_ssm_state_merge_compiles_in_place_at_the_published_widths(
        one_chip):
    """And plans no copy of the pool: its output is its operand."""
    compiled = _compile(
        one_chip, ssm_pallas.state_merge, SSM_POOL, ((23, 32, 64), F32),
        ((23, 32, 8, 4096), F32), ((23, 32, 8, 8, 128), F32), ((32,), I32),
        STEP)
    assert compiled.memory_analysis().temp_size_in_bytes < 23 * 2 ** 20


def test_dense_decode_compiles_to_its_span_limit(one_chip):
    """The whole-lane dense kernel (opt-in) compiles at S=512 and refuses
    S=1024 at trace time with the reason — the compiler's own refusal
    (16.32 MB against the 16 MiB scoped-VMEM limit) takes seconds and
    names no remedy."""
    def lane(s):
        return ((B, s, HKV, D), BF)

    _compile(one_chip, ap.decode_gqa_attention, Q, lane(512), lane(512), ROW)
    with pytest.raises(ValueError, match="whole KV lanes in VMEM"):
        _compile(one_chip, ap.decode_gqa_attention,
                 Q, lane(SPAN), lane(SPAN), ROW)


@pytest.mark.parametrize("kv_dtype", ("bfloat16", "int8"))
def test_chunked_decode_forward_reads_pool_in_place(one_chip, monkeypatch,
                                                    kv_dtype):
    """The whole chunked decode forward over a multi-layer pool plans no
    temporary as large as one layer's K slice: the layer scan hands the
    kernel the flat pool and ``table + l * P``. Scanning the pool made
    XLA copy each layer's K and V slice out in every step (two
    pool-slice temporaries, 30% of the device's time in the chat cell)."""
    from swarmdb_tpu.models import llama
    from swarmdb_tpu.models.configs import ModelConfig

    cfg = ModelConfig(name="aot-4-layers", vocab_size=1024, dim=HQ * D,
                      n_layers=4, n_heads=HQ, n_kv_heads=HKV, ffn_dim=1024)
    pool_pages = 4096
    # layers.py asks the backend to choose kernel or gather fallback
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def shapes(fn):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            jax.eval_shape(fn))

    params = shapes(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: llama.init_paged_cache(
        cfg, B, SPAN, pool_pages, PS, dtype=jnp.dtype(kv_dtype)))
    chunk = shapes(lambda: llama.init_chunk_kv(cfg, B, KC))
    tok = jax.ShapeDtypeStruct((B, 1), I32, sharding=one_chip)
    step = jax.ShapeDtypeStruct((), I32, sharding=one_chip)

    compiled = jax.jit(
        lambda p, t, pos, c, ck, s: llama.forward_paged_chunked(
            p, cfg, t, pos, c, ck, s)
    ).lower(params, tok, tok, cache, chunk, step).compile()
    assert "tpu_custom_call" in compiled.as_text()
    k = jax.tree.leaves(cache["k"])[0]          # payload [L, P, ps, Hkv, D]
    layer_slice = k.size // cfg.n_layers * k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_slice
