"""The whole SSD decode step's share of the chip's bf16 peak: model
operations of the live rows (``counts.call``) over device time times
peak, summed over the profiled calls, in percent."""
import devicecalls


def read(run):
    return devicecalls.mfu(run, "decode_step")
