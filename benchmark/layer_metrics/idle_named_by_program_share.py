"""Device: of the device's idle seconds in the traced span that the trace
reduction could give to a host event at all (``breakdown.idle_gaps``
without ``short gaps between operations`` and ``host: nothing traced``),
the share given to a phase of the program (an ``engine.*`` annotation,
``obs/tracer.py`` ``phase_begin``) and not to one of JAX's own events. The
list holds the ten names with the most idle time."""

UNNAMED = ("short gaps between operations", "host: nothing traced")


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    named = [(n, s) for n, s in tr["breakdown"]["idle_gaps"]
             if n not in UNNAMED]
    total = sum(s for _, s in named)
    if not total:
        return None
    return 100.0 * sum(s for n, s in named if n.startswith("engine.")) / total
