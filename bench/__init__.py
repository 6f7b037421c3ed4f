"""On-chip benchmark of the served filtered-search path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
configuration, traffic mix or per-layer metric sits in a file of its own
(``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``,
``bench/metrics/<metric>.py``), found by the name ``BENCHMARK.json`` gives.
The yardstick (traffic generation, the plain reference, the comparison that
decides ``correct``, the trace reduction and the peak table) lives here and
imports nothing of the program under ``src/``.
"""
