"""Scheduler: prompt tokens computed per prefill dispatch over the window
(counters ``prefill_packed_tokens`` / ``prefill_device_waves``: every
ragged wave and every bucketed prefill call is one dispatch, and each
streams all weights once)."""


def read(ctx):
    c = ctx["counters"]
    waves = c.get("prefill_device_waves", 0)
    return c.get("prefill_packed_tokens", 0) / waves if waves else None
