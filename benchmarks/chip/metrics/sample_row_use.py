"""Share of the rows drawn whose token is kept (the engine's ``sample``
spans, decode and prefill: 100 * sum of ``used`` / sum of ``rows``), in
percent."""
import hostspans


def read(run):
    return hostspans.row_use(run)
