"""KV stores: of the snapshot pool's slots, the share that held a live
snapshot, over the window's admission rounds (counters
``ssm_snapshot_slots_live`` / ``ssm_snapshot_slots``, both summed a round,
so the ratio is weighted by admissions). A pool that stays under 100 keeps
every snapshot it was given; at 100 it evicts (``ssm_snapshot_evicted_
share``). A program without snapshots reads nothing."""


def read(ctx):
    c = ctx["counters"]
    slots = c.get("ssm_snapshot_slots", 0)
    if not slots:
        return None
    return 100.0 * c.get("ssm_snapshot_slots_live", 0) / slots
