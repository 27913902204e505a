"""The fused SSD chunk pipeline's share of its roofline: over the profiled
prefill-chunk calls, the sum of each call's least time
(``counts.min_seconds`` for its staging rows, chunked SSD at the
configuration's ``chunk_size``) over the sum of its device time, in
percent."""
import devicecalls


def read(run):
    return devicecalls.roofline(run, "prefill_chunk")
