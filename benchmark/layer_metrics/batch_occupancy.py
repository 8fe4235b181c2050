"""Scheduler: mean of active slots / ``max_batch``, sampled every 50 ms
over the window."""


def read(ctx):
    occ = [s["occupancy"] for s in ctx["window_samples"]]
    return 100.0 * sum(occ) / len(occ) if occ else None
