"""``device_idle_share``'s reading in the SSD cell: one minus the union of
device operation intervals over the profiled slice, in percent."""
import catalog

read = catalog.reader("device_idle_share")
