"""Model: device time of the prefill programs in the traced span, per
thousand prompt tokens computed in it (counter ``prefill_packed_tokens``).
Device time is operation time inside the programs' module events."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    busy = sum(p["busy_s"] for n, p in tr["programs"].items()
               if "prefill" in n)
    tokens = ctx["trace_counters"].get("prefill_packed_tokens", 0)
    return 1e3 * busy / (tokens / 1e3) if tokens and busy else None
