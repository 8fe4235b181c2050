"""Kernels: device time of a ragged wave's Mamba-2 scan in the traced
span, per thousand prompt tokens computed in it (counter
``prefill_packed_tokens``, ``prefill_ms_per_ktok``'s denominator: this is
the scan's part of that number). The scan is the kernel
``ops/ssm_pallas.ssm_wave_scan``, one call a Mamba-2 layer a wave, whose
custom calls the trace names ``ssm_wave_scan.<n>`` (``trace_reduce.
kernel_name`` gathers them). ``notes`` holds the seconds, the calls and,
from the program's counters ``ssm_wave_segments`` /
``ssm_wave_segment_tokens`` over the span, the live segments a layer and
the tokens in them. A program whose waves are scanned by XLA's loop (the
parent of PR 53) has no such kernel and reads nothing."""

KERNEL = "ssm_wave_scan"


def read(ctx):
    tr = ctx["trace"]
    k = tr and tr["kernels"].get(KERNEL)
    tokens = ctx["trace_counters"].get("prefill_packed_tokens", 0)
    if not k or not tokens:
        return None
    ctx["notes"]["ssm_wave_scan_ms_per_ktok"] = {
        "seconds": k["seconds"], "calls": k["calls"], "tokens": tokens,
        "segments_a_layer": ctx["trace_counters"].get("ssm_wave_segments"),
        "segment_tokens": ctx["trace_counters"].get(
            "ssm_wave_segment_tokens")}
    return 1e3 * k["seconds"] / (tokens / 1e3)
