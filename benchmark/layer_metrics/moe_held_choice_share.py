"""Model: of the token-choices the router made, the share that fell on
experts this chip holds, over the requests that finished in the window
(counters ``moe_held_assignments`` / ``moe_assignments``, written at
retirement from each request's routing record: an entry ``e`` is held,
``~e`` is an expert another chip of the layer holds). With 20 of 160
experts held an even router reads 12.5; the weights come from the seed, so
the share is the seed's, and with it how much expert work this chip's
steps do. A program that holds every expert writes no
``moe_held_assignments`` and reads nothing."""


def read(ctx):
    c = ctx["counters"]
    made = c.get("moe_assignments", 0)
    if not made or "moe_held_assignments" not in c:
        return None
    return 100.0 * c["moe_held_assignments"] / made
