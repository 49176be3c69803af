"""Device milliseconds per elimination round: the device time of the
rounds program's runs (``jit__rounds_padded`` on the trace's ``XLA
Modules`` line) inside the traced part, over the rounds that the
program's ``stream.window_rounds`` histogram counted for the window
compressions of that part.  The benchmark starts and stops the trace where
no compression is in flight, so both count the same runs."""

PROGRAM = "jit__rounds_padded"


def read(r):
    t = r.trace
    if t is None:
        return None
    lo, hi = t.window
    ns = sum(e - s for mods in t.modules.values() for name, s, e in mods
             if name.startswith(PROGRAM) and s >= lo and e <= hi)
    rounds = r.hist("stream.window_rounds", traced=True)[1]
    if ns <= 0 or rounds <= 0:
        return None
    return ns * 1e-6 / rounds
