"""Host milliseconds per push on the push path itself: the program's
``server.push`` span (lock wait, journal record, the stream's buffer)
less the window closes (``stream.window``) and store appends
(``store.append``) inside it, over the pushes of the measured window."""


def read(r):
    n, push = r.hist("span.server.push.seconds")
    if n <= 0:
        return None
    window = r.hist("span.stream.window.seconds")[1]
    append = r.hist("span.store.append.seconds")[1]
    return 1000.0 * (push - window - append) / n
