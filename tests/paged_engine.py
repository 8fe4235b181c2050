"""The one place the tests wire a page pool by hand.

A test that can take the deployment's wiring whole builds its engine with
``build_backend_engine(cfg, paged=True, ...)``. One that must bring its own
parameters, pool size, allocator, prefill buckets or eos id builds it here:
the same pieces ``backend/service.build_backend_engine`` puts together (the
pool, the chunk triple it is decoded with, in-place prefix caching over the
main pool), with the row-bucketed prefill those tests size their buckets
for.
"""

from swarmdb_tpu.backend.engine import Engine, PagedKV
from swarmdb_tpu.models import llama
from swarmdb_tpu.ops.paged_kv import PageAllocator


def paged_chunk_fns(cfg):
    """The chunk triple a page pool of ``cfg`` is decoded with."""
    return (
        lambda p, t, pos, c, hkv, s: llama.forward_paged_chunked(
            p, cfg, t, pos, c, hkv, s),
        lambda b, k: llama.init_chunk_kv(cfg, b, k),
        llama.merge_paged_chunk,
    )


def paged_engine(cfg, params, *, max_batch, max_seq, page_size, num_pages,
                 allocator=None, prefix=False, **engine_kw) -> Engine:
    """A single-device paged engine over ``params``. ``allocator``
    defaults to a plain ``PageAllocator`` of the pool's size;
    ``prefix=True`` wires prefix caching in place over the main pool.
    Everything else (``eos_id``, ``prefill_buckets``, ``decode_chunk``,
    ...) goes to ``Engine`` as given. Not started."""
    spec = PagedKV(
        chunked_fns=paged_chunk_fns(cfg),
        init_pool=lambda: llama.init_paged_cache(
            cfg, max_batch, max_seq, num_pages, page_size),
        page_size=page_size,
        num_pages=num_pages,
        allocator=allocator or PageAllocator(num_pages, page_size, max_seq,
                                             max_batch),
    )
    if prefix:
        engine_kw["prefix_fns"] = (
            lambda p, t, tab, pl, pk, pv, logits_at=None:
                llama.forward_prefix_pages(p, cfg, t, tab, pl, pk, pv,
                                           logits_at=logits_at),
            None,
        )
    return Engine(
        lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c),
        lambda b, s: llama.init_kv_cache(cfg, b, s),
        params, max_batch=max_batch, max_seq=max_seq, paged=spec,
        **engine_kw)
