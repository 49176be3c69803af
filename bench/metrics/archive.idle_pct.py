"""Device idle share of the traced part of the archive cell's window
(``device.idle_pct``'s reading: 1 minus the union of device-operation
intervals over the traced time).  In this cell the gaps lie between two
writes' rounds-program runs: the block encode and the host work of a
write."""
from bench import metrics


def read(r):
    return metrics.reader("device.idle_pct")(r)
