"""Host milliseconds per draw of a decode step's next tokens (the
engine's ``sample`` spans of phase ``decode``), averaged over the
window."""
import hostspans


def read(run):
    return hostspans.mean_ms(run, "sample", "decode")
