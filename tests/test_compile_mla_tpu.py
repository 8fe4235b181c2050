"""AOT compiles for a described TPU v5e of what PR 44 added: the two latent
(MLA) attention kernels at ``deepseek-v2.chat``'s shapes and the expert
stream kernel at its widths. As ``tests/test_tpu_compile.py`` (whose
fixture and helper these are): nothing runs, and a compile that passes says
nothing about results or times. A file of its own so that it runs beside
that one, not after it."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from swarmdb_tpu.ops import attention_pallas as ap
from swarmdb_tpu.ops import moe_pallas

PS, KC = 16, 8
BF, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32
STEP = ((), I32)


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


# deepseek-v2.chat: latent pages, 128 heads over rows of 640
# lanes, the flat 9-layer pool of 12,801 pages a layer; 20 held experts of
# 5120 x 1536

MLA_H, MLA_W, MLA_ROWS, MLA_MAXP = 128, 640, 32, 256
MLA_POOL = ((9 * 12801, PS, MLA_W), BF)


def test_mla_paged_decode_compiles(one_chip):
    """The absorbed decode walk over ONE pool (a page copied once, key and
    value both), named as the benchmark's trace reader looks for it, and
    nothing pool-shaped planned beside it."""
    compiled = _compile(
        one_chip, ap.mla_paged_decode_attention_chunked,
        ((MLA_ROWS, MLA_H, MLA_W), BF), MLA_POOL,
        ((MLA_ROWS, MLA_MAXP), I32), ((MLA_ROWS, KC, MLA_W), BF),
        ((MLA_ROWS,), I32), STEP)
    assert "%mla_paged_decode_attention_chunked" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("width", (8, 16, 64, 128, 256, 1024, 4096))
def test_mla_ragged_prefill_rung_compiles(one_chip, width):
    """A query block is 16 tokens of 128 heads of 640 lanes: its float32
    accumulator and a fold's temporaries are over the 16 MiB a Pallas call
    is given by default, which only the chip's compiler counts. A wave
    narrower than a block is padded to one beside the call, and nothing
    pool-shaped is planned."""
    row = ((MLA_ROWS,), I32)
    compiled = _compile(
        one_chip, ap.mla_ragged_prefill_attention,
        ((width, MLA_H, MLA_W), BF), ((width, MLA_W), BF), MLA_POOL,
        ((MLA_ROWS, MLA_MAXP), I32), row, row, row)
    assert "%mla_ragged_prefill_attention" in compiled.as_text()
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= 2 * 16 * MLA_H * MLA_W * 2)


@pytest.mark.parametrize("rows", [
    pytest.param(32, id="dsv2-cell-decode-step"),
    pytest.param(256, id="rung-256"),
    pytest.param(512, id="widest-rung-that-takes-it"),
])
def test_expert_stream_kernel_compiles_at_deepseek_widths(one_chip, rows):
    """``deepseek-v2.chat``'s routed layer: 20 held experts of 5120 x 1536
    in the scanned segment's flat stack of 8 x 20. ``tile_of(1536)`` is
    768: three double-buffered tiles are 47 MB of VMEM where lfm2's 22 MB
    ran, under the limit the call asks for."""
    d, f, e = 5120, 1536, 20
    assert moe_pallas.tile_of(f) == 768
    wide, tall = ((8 * e, d, f), BF), ((8 * e, f, d), BF)
    compiled = _compile(
        one_chip, moe_pallas.stream_experts,
        ((rows, d), BF), ((rows, e), F32), ((e,), jnp.bool_),
        wide, wide, tall, ((), I32))
    assert "%moe_stream_experts" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < d * f * 2
