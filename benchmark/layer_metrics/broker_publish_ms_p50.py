"""Runtime + broker: median of the program's ``broker_publish`` histogram
(runtime send -> broker accepted the produce; host clock round host work),
from its bucket counts' change over the window."""
from benchmark.harness.stats import histogram_quantile


def read(ctx):
    h0, h1 = ctx["publish_hist"]
    if h0 is None or h1 is None:
        return None
    counts = [b - a for a, b in zip(h0["counts"], h1["counts"])]
    q = histogram_quantile(h1["boundaries"], counts, 50)
    return None if q is None else q * 1e3
