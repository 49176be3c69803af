"""Device milliseconds per elimination round of the one-shot archive
write in the ranking phase: the self time of the operations under the
``rank`` scope (the Eq. 8 single-delta impacts and the Eq. 9 tier impacts
with their kernel, ``window_rows_pallas``) in the rounds program's runs
wholly inside the traced part, over the rounds ``write.rounds`` counted
for that part (``bench/program_trace.py`` ``phase_split``, which notes the
residual and each phase's share as ``rounds.phases``).  The split is exact
only to a fusion boundary."""
from bench import program_trace as pt
from bench import write_rounds


def read(r):
    split = pt.phase_split(write_rounds.readings(r))
    return None if split is None else split["rank"]
