"""Model: device time of the decode programs in the traced span, per
decode step taken in it. Steps are the engine's resident chunks times the
chunk length (counter ``engine_resident_chunks``). The engine jits its
decode functions as ``functools.partial`` objects, which have no name, so
JAX calls those programs ``jit__unknown``: every program that is not a
prefill program and is named so, or has ``decode`` in its name, counts."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    busy = sum(p["busy_s"] for n, p in tr["programs"].items()
               if "prefill" not in n and ("decode" in n or n == "_unknown"))
    steps = (ctx["trace_counters"].get("engine_resident_chunks", 0)
             * ctx["decode_chunk"])
    return 1e3 * busy / steps if steps and busy else None
