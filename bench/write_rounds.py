"""The rounds of the one-shot archive write, for the rounds-loop readers.

``Dataset.write`` runs the same rounds program (``jit__rounds_padded``,
with its ``rank``/``select``/``update`` scopes) as a served window close,
but the program counts its rounds in the ``write.rounds`` histogram, not
in ``stream.window_rounds``, which ``program_trace`` and the served
cell's rounds readers read.  :func:`readings` hands them the write's
count under the name they read; the notes stay shared, so what they note
is printed with the run.
"""
from __future__ import annotations

import dataclasses

COUNTER = "write.rounds"
SERVED = "stream.window_rounds"


def readings(r):
    """``r`` with the traced part's ``write.rounds`` in place of
    ``stream.window_rounds``."""
    hists = {**r.obs_traced["hists"], SERVED: r.hist(COUNTER, traced=True)}
    return dataclasses.replace(r, obs_traced={**r.obs_traced,
                                              "hists": hists})

