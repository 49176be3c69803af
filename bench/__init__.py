"""On-chip benchmark of the CAMEO compress -> store -> serve path.

One command runs one cell (a deployment under a traffic mix) once::

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: ``configs/<config>.json``
(the deployment), ``traffic/<traffic>.json`` (the mix), ``entries/<entry>.py``
(the runner of the deployment's entry point), ``metrics/<metric>.py`` (one
reader per per-layer metric) and ``kernels/<kernel>.py`` (operations and
bytes of one kernel).  ``BENCHMARK.json`` at the checkout's root lists the
cells and metrics.
"""
