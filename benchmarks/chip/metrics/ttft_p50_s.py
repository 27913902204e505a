"""Median time to first token, from each request's due time, over the
requests due in the window."""
import timing


def read(run):
    return timing.percentile(timing.ttfts(run.window), 50)
