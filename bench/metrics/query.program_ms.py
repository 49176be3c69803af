"""Host milliseconds per answered query inside the program: the mean of
its ``query`` span over the measured window.  The dashboard's own p95
(``query_p95_ms``) adds the wait for the interpreter and the client."""


def read(r):
    n, total = r.hist("span.query.seconds")
    if n <= 0:
        return None
    return 1000.0 * total / n
