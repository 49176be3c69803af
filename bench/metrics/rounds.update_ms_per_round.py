"""Device milliseconds per elimination round in the dense-update phase: operations under the ``update`` scope (the
Eq. 10/11 dense evaluation of a proposal and its deviation, wherever it
runs, the probes' included).

Their self time inside the rounds program's runs (``jit__rounds_padded``)
wholly in the traced part, over the rounds ``stream.window_rounds``
counted for that part (``bench/program_trace.py`` ``phase_split``, which
notes the residual and each phase's share: the three phases and the
residual add up to ``rounds.device_ms_per_round``).  The split is exact
only to a fusion boundary (a fusion takes its root's scope).  A queued
drain (``queue_depth`` > 1) runs the batched programs instead, which this
does not read; the served cell never takes that path."""
from bench import program_trace as pt


def read(r):
    split = pt.phase_split(r)
    return None if split is None else split["update"]
