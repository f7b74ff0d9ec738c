"""The chip benchmark: one command, one cell per process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the repository root names every cell, metric and
bound. Everything that belongs to one configuration, traffic mix, driver or
per-layer metric is a file of its own under this directory, found by name:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``drivers/<driver>.py`` and ``metrics/<metric>.py``.
"""
