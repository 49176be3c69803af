"""Host milliseconds per dashboard query inside the program in the
archive cell, over ranges of up to a whole 17,520-point series
(``query.program_ms``'s reading: the mean of the ``query`` span over the
measured window)."""
from bench import metrics


def read(r):
    return metrics.reader("query.program_ms")(r)
