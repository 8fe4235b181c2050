"""Scheduler: how often the resident loop's vote went stale, as a share of
the window's chunks (counters ``resident_votes_stale`` /
``engine_resident_chunks``). The vote on a chunk is taken before the
engine thread processes the chunk's block; it is stale where that
processing then found what would have stopped the loop (a cancel that
another thread flagged in between freed a slot with work queued, or
retired the last live lane) and the vote had said continue: a session a
chunk longer than it had to be, which the queue waits out. 0 where chunks
ran and no vote went stale; nothing where no chunk ran, or where the
program does not count them (the engine registers the counter at 0 when
it builds its resident programs, so its absence is an older program)."""


def read(ctx):
    c = ctx["counters"]
    chunks = c.get("engine_resident_chunks", 0)
    if "resident_votes_stale" not in c or not chunks:
        return None
    return 100.0 * c["resident_votes_stale"] / chunks
