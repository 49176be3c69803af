"""Blocks the pushdown touched per dashboard query in the archive cell
(``query.blocks_per_query``'s reading: headers answered from metadata plus
edge blocks decoded, over the queries answered in the measured window)."""
from bench import metrics


def read(r):
    return metrics.reader("query.blocks_per_query")(r)
