"""KV stores: of the recurrent-state snapshots taken in the window, the
share whose slot was taken from another snapshot that still had its page
(counters ``ssm_snapshots_evicted`` / ``ssm_snapshots_taken``, written
where an admission round takes the slots for its rows' page-end states).
An evicted snapshot's page stays cached, and the next hit on it is forgone
(``prefix_state_forgone_share``): the turn pays its history again. 0 in a
pool that holds every live conversation's newest snapshot. A program
without snapshots writes neither counter and reads nothing."""


def read(ctx):
    c = ctx["counters"]
    taken = c.get("ssm_snapshots_taken", 0)
    if not taken or "ssm_snapshots_evicted" not in c:
        return None
    return 100.0 * c["ssm_snapshots_evicted"] / taken
