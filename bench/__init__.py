"""The chip benchmark: one command runs one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: a cell's configuration is
``bench/configs/<config>.json``, its traffic mix ``bench/traffic/<traffic>.json``
(data), the mix's generator ``bench/generators/<generator>.py``, each
per-layer metric a reader ``bench/metrics/<metric>.py``, each kernel's
least work ``bench/work/<kernel>.py``, and each configuration's plain
reference ``bench/references/<reference>.py``.
"""
