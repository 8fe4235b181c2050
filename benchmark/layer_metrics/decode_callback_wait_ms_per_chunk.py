"""Scheduler: how long the device stands at a chunk boundary of the
resident decode loop, a chunk: the device time of the loop's ordered
``io_callback`` in the traced span, over the chunks run in it (counter
``engine_resident_chunks``, the denominator of ``decode_ms_per_step``:
an eighth of this is the callback's part of a step).

On the chip the callback is several operations a chunk, all named
``io_callback.<n>`` (``trace_reduce.kernel_name`` gathers them under
``io_callback``): a ``send`` and a ``send-done`` an operand, a ``recv``
and a ``recv-done`` for the answer. Nearly all of the time is the
``recv-done``: from the operand's departure to the answer's arrival,
which holds the operand's trip, what the host does before it answers and
the answer's trip back. The trace counts it as busy time: the idle share
does not see this wait, ``decode_ms_per_step`` does. ``notes`` holds the
seconds and the events. A program that makes no such call (the scan path,
the dense slab engine) reads nothing."""

KERNEL = "io_callback"


def read(ctx):
    tr = ctx["trace"]
    k = tr and tr["kernels"].get(KERNEL)
    chunks = ctx["trace_counters"].get("engine_resident_chunks", 0)
    if not k or not chunks:
        return None
    ctx["notes"]["decode_callback_wait_ms_per_chunk"] = {
        "seconds": k["seconds"], "events": k["calls"], "chunks": chunks}
    return 1e3 * k["seconds"] / chunks
