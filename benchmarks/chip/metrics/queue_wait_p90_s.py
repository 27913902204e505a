"""90th percentile of due time to admission (``Request.admit_pc``, when
the engine took the request off its queue into a slot)."""
import timing


def read(run):
    return timing.percentile(timing.queue_waits(run.window), 90)
