"""KV stores: prompt tokens served from cached pages, as a share of all
prompt tokens admitted in the window (counters ``prefix_reused_tokens`` /
``prompt_tokens``)."""


def read(ctx):
    c = ctx["counters"]
    total = c.get("prompt_tokens", 0)
    return 100.0 * c.get("prefix_reused_tokens", 0) / total if total else None
