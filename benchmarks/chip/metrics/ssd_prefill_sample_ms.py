"""``prefill_sample_ms``'s reading in the SSD cell: host milliseconds per
draw of first tokens after a prefill call, averaged over the window."""
import catalog

read = catalog.reader("prefill_sample_ms")
