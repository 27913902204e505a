"""Device milliseconds per call of the SSD model's prefill-chunk program
(the staging rows advanced by one chunk through the fused SSD chunk
pipeline), from the profiled slice."""
import devicecalls


def read(run):
    return devicecalls.mean_ms(run, "prefill_chunk")
