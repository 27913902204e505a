"""95th percentile of every gap between consecutive output tokens of one
request, over the gaps that end inside the window, in milliseconds."""
import timing


def read(run):
    p = timing.percentile(timing.itl_gaps(run.window), 95)
    return None if p is None else p * 1e3
