"""Milliseconds per copy of a decode step's logits to the host (the
engine's ``logits_to_host`` spans of phase ``decode``), averaged over
the window."""
import hostspans


def read(run):
    return hostspans.mean_ms(run, "logits_to_host", "decode")
