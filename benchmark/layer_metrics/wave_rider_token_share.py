"""Scheduler: the share of the window's generated tokens that running
requests sampled as riders of a prefill wave (counters
``wave_rider_tokens`` / ``tokens_generated``). When an admission round
dispatches its ragged wave, a request that is already decoding joins it
as a one-token row in a seat the wave pays for anyway, and the pass over
the weights that admits the newcomers is a decode step for it: a token it
would else have waited through the wave for. 0 where tokens were
generated and nobody rode; nothing where none was generated, or where the
program takes no riders (the engine registers the counter at 0 when it
builds its ragged programs, so its absence is an older program)."""


def read(ctx):
    c = ctx["counters"]
    tokens = c.get("tokens_generated", 0)
    if "wave_rider_tokens" not in c or not tokens:
        return None
    return 100.0 * c["wave_rider_tokens"] / tokens
