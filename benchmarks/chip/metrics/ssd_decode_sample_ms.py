"""``decode_sample_ms``'s reading in the SSD cell: host milliseconds per
draw of a decode step's next tokens, averaged over the window."""
import catalog

read = catalog.reader("decode_sample_ms")
