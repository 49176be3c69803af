"""Device milliseconds per elimination round of the one-shot archive
write in the dense-update phase: the self time of the operations under
the ``update`` scope (the Eq. 10/11 dense evaluation of a proposal and its
deviation, the probes' included) in the rounds program's runs wholly
inside the traced part, over the rounds ``write.rounds`` counted for that
part (``rounds.update_ms_per_round``'s reading, with the write's count of
rounds).  The split is exact only to a fusion boundary."""
from bench import program_trace as pt
from bench import write_rounds


def read(r):
    split = pt.phase_split(write_rounds.readings(r))
    return None if split is None else split["update"]
