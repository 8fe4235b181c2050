"""KV stores: of the pages that running requests own, the share their
written extent covers, over the window (counters
``kv_page_chunks_written`` / ``kv_page_chunks_reserved``, both summed per
emitted chunk over its live slots, so the ratio is weighted by decode
work). Admission reserves a request's worst case (prompt +
``max_new_tokens`` + one chunk); the rest of 100% is pool that holds
nothing yet and admits nobody."""


def read(ctx):
    c = ctx["counters"]
    reserved = c.get("kv_page_chunks_reserved", 0)
    if not reserved:
        return None
    return 100.0 * c.get("kv_page_chunks_written", 0) / reserved
