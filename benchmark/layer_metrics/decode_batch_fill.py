"""Scheduler: live slots per emitted decode chunk as a share of
``max_batch``, over the window (counters ``decode_slot_chunks`` /
(``engine_resident_chunks`` x ``max_batch``)): what ``batch_occupancy``
samples every 50 ms from outside, counted by the engine where a chunk is
emitted, so weighted by decode work and not by time."""


def read(ctx):
    c = ctx["counters"]
    chunks = c.get("engine_resident_chunks", 0)
    if not chunks or "decode_slot_chunks" not in c:
        return None
    return 100.0 * c["decode_slot_chunks"] / (chunks * ctx["max_batch"])
