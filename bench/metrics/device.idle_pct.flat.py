"""``device.idle_pct`` in a cell offered more than it serves, where the
device's idle share is what the host's work per batch leaves of it
(``answered_qps``): 1 - the union of device op intervals over the traced
window, in %."""
from benchlib.spec import metric_reader

read = metric_reader("device.idle_pct")
