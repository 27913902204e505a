"""Device milliseconds per call of the SSD model's decode program (one
token for each live row), from the profiled slice."""
import devicecalls


def read(run):
    return devicecalls.mean_ms(run, "decode_step")
