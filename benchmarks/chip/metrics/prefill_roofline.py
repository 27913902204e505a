"""The prefill-chunk program's share of its roofline: over the profiled
calls, the sum of each call's least time (``counts.min_seconds`` for its
staging rows and the chunk) over the sum of its device time, in percent."""
import devicecalls


def read(run):
    return devicecalls.roofline(run, "prefill_chunk")
