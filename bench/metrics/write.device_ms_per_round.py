"""Device milliseconds per elimination round of the one-shot archive
write: the device time of the rounds program's runs (``jit__rounds_padded``
on the trace's ``XLA Modules`` line) wholly inside the traced part, over
the rounds that the program's ``write.rounds`` histogram counted for the
writes of that part (``rounds.device_ms_per_round``'s reading, with the
write's count of rounds).  The benchmark starts and stops the trace
between two writes, so both count the same runs."""
from bench import metrics
from bench import write_rounds


def read(r):
    return metrics.reader("rounds.device_ms_per_round")(
        write_rounds.readings(r))
