"""Device milliseconds per prefill-chunk program call, from the profiled
slice."""
import devicecalls


def read(run):
    return devicecalls.mean_ms(run, "prefill_chunk")
