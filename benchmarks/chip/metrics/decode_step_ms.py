"""Device milliseconds per decode program call, from the profiled slice."""
import devicecalls


def read(run):
    return devicecalls.mean_ms(run, "decode_step")
