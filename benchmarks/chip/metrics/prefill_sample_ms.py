"""Host milliseconds per draw of first tokens after a prefill call (the
engine's ``sample`` spans of phase ``prefill``), averaged over the
window."""
import hostspans


def read(run):
    return hostspans.mean_ms(run, "sample", "prefill")
