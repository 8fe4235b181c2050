"""AOT compiles for a described TPU v5e of what PR 50 brought to the
kernels the cells share: the expert stream kernel's un-gated form at
``nemotron3-nano.chat``'s widths as laid out, and both paged attention
kernels at 16 query heads a KV head over its 2-KV-head pool; and of PR
53's ``ssm_wave_scan`` at the cell's widths. As
``tests/test_tpu_compile.py`` (whose fixture and helper these are): nothing
runs, and a compile that passes says nothing about results or times. A
file of its own so that it runs beside that one, not after it."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from swarmdb_tpu.models import nemotron_h
from swarmdb_tpu.ops import attention_pallas as ap
from swarmdb_tpu.ops import moe_pallas, ssm_pallas

PS, KC = 16, 8
BF, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32
HQ, HKV, D, ROWS, MAXP = 32, 2, 128, 32, 256
POOL = ((6 * 8193, PS, HKV, D), BF)       # the 6 attention layers' flat pool


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
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("rows", [32, 512], ids=["a-decode-step",
                                                 "the-widest-wave-it-takes"])
def test_the_ungated_expert_stream_compiles(one_chip, rows):
    """2688 x 1856 kept at 1920 columns, the scanned segment's flat stack
    of 5 x 16 held experts left in HBM as it is stored: two
    matrices an expert, three tiles of 640, under the same name."""
    d, f, held = 2688, nemotron_h.lanes_up(1856), 16
    assert f == 1920 and moe_pallas.tile_of(f) == 640
    up, down = ((5 * held, d, f), BF), ((5 * held, f, d), BF)
    compiled = _compile(
        one_chip,
        lambda x, gate, hit, wu, wd, base: moe_pallas.stream_experts(
            x, gate, hit, None, wu, wd, base),
        ((rows, d), BF), ((rows, held), F32), ((held,), jnp.bool_), up, down,
        ((), I32))
    assert "%moe_stream_experts" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < d * f * 2


def test_paged_decode_compiles_at_16_query_heads_a_kv_head(one_chip):
    chunk = ((ROWS, KC, HKV, D), BF)
    compiled = _compile(
        one_chip, ap.paged_decode_gqa_attention_chunked,
        ((ROWS, HQ, D), BF), POOL, POOL, ((ROWS, MAXP), I32), chunk, chunk,
        ((ROWS,), I32), ((), I32), ((ROWS,), I32), ((1,), I32))
    # no copy of the 2-KV-head pool beside the kernel (ROADMAP Reach B7)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_ragged_prefill_compiles_at_16_query_heads_a_kv_head(one_chip):
    """The kernel alone, one query block. (Alone, a second block takes it
    to 17.4 MB of the 16 MB of VMEM a call is given by default; inside the
    engine's wave program the chip's compiler passes it up to 1,024 tokens
    and refuses it at 2,048, ``benchmark/aot_rehearsal.py``, which is why
    this family's engine builds no wider wave: ``PERF.md`` section 7.)"""
    width = 128
    qs, kv = ((width, HQ, D), BF), ((width, HKV, D), BF)
    row = ((ROWS,), I32)
    compiled = _compile(one_chip, ap.ragged_paged_prefill_attention,
                        qs, kv, kv, POOL, POOL, ((ROWS, MAXP), I32), row, row,
                        row)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("width", [8, 1024], ids=["the-narrowest-rung",
                                                  "the-widest-rung"])
def test_the_wave_scan_compiles_at_the_cells_widths(one_chip, width):
    """64 heads of 64 in 8 groups, state 128, the 23 layers' pools of 32
    slots and 100 snapshots left in HBM where they are (aliased in and
    out: no copy beside the kernel), a table for 32 rows."""
    H, P, G, N, L = 64, 64, 8, 128, 23
    slot, snap = ((L, ROWS, H * P, N), BF), ((L, 101, H * P, N), BF)
    assert ssm_pallas.WAVE_SEGMENT == nemotron_h.SCAN_CHUNK
    columns = -(-width // ssm_pallas.WAVE_SEGMENT) + 2 * ROWS
    compiled = _compile(
        one_chip, ssm_pallas.ssm_wave_scan,
        ((width, H * P + 2 * G * N), F32), ((width, H), F32),
        ((width, H), F32),
        ((6, columns), I32), ((), I32), ((), I32), slot, snap)
    assert "%ssm_wave_scan" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20
