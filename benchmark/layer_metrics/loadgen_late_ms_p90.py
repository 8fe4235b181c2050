"""Load generator: how late messages were sent, sent - due, 90th
percentile over the window's messages. A starved generator must not read
as a fast server."""
from benchmark.harness.stats import percentile


def read(ctx):
    late = [(r["sent_t"] - r["due"]) * 1e3 for r in ctx["window_rows"]
            if r["sent_t"] is not None]
    return percentile(late, 90)
