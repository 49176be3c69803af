"""Blocks the pushdown touched per dashboard query over the measured
window: block headers answered from metadata plus edge blocks decoded
(the program's ``query.segments_meta`` and ``query.segments_edge``
counters) over the queries answered (``query.count``)."""


def read(r):
    n = r.counter("query.count")
    if n <= 0:
        return None
    return (r.counter("query.segments_meta")
            + r.counter("query.segments_edge")) / n
