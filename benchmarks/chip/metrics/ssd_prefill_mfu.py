"""The whole SSD prefill-chunk step's share of the chip's bf16 peak:
model operations of the staging rows (``counts.call``) over device time
times peak, summed over the profiled calls, in percent."""
import devicecalls


def read(run):
    return devicecalls.mfu(run, "prefill_chunk")
