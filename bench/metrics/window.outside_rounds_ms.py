"""Host milliseconds per compressed window outside the rounds program:
the close of a window (the program's ``stream.window`` span) less the
rounds program's dispatch and the wait for it (``stream.window.rounds``),
plus the block planning, encode and write of the pushes that closed
windows (``store.append``), over the windows closed in the measured
window.  A queued drain (``queue_depth`` > 1) times its one batched
dispatch outside the window spans; the served cell never takes that
path."""


def read(r):
    n, window = r.hist("span.stream.window.seconds")
    if n <= 0:
        return None
    rounds = r.hist("span.stream.window.rounds.seconds")[1]
    append = r.hist("span.store.append.seconds")[1]
    return 1000.0 * (window - rounds + append) / n
