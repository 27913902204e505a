"""The SSD decode program's share of its roofline: over the profiled
calls, the sum of each call's least time (``counts.min_seconds`` for its
live rows and the O(1) recurrence) over the sum of its device time, in
percent."""
import devicecalls


def read(run):
    return devicecalls.roofline(run, "decode_step")
