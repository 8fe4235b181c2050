"""Process: the share of the window in which an engine's loop thread was
runnable and had no CPU: counters ``engine_thread_runq_wait_us`` /
``engine_thread_watch_us`` (``swarmdb_tpu/obs/procwatch.py``: the second
field of ``/proc/self/task/<tid>/schedstat`` of each engine loop thread,
and the time it was watched, both summed over the threads: two lanes
watched for a whole window make twice its length)."""


def read(ctx):
    c = ctx["counters"]
    watched = c.get("engine_thread_watch_us", 0)
    if not watched or "engine_thread_runq_wait_us" not in c:
        return None
    return 100.0 * c["engine_thread_runq_wait_us"] / watched
