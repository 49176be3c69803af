"""Roofline share of the ranking kernel ``window_rows_pallas`` in the
archive cell, where it ranks 18,432-row buckets (``window_rows_roofline``'s
reading: the least time the chip could take for the calls wholly inside
the traced part over their device time; the bound that applies goes to
``notes``)."""
from bench import metrics


def read(r):
    return metrics.reader("window_rows_roofline")(r)
