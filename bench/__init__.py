"""On-chip benchmark of the served MNF path.

``python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Everything that belongs to one
configuration, traffic mix or metric is a file of its own, found by name:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric family>.py``. The yardstick (traffic generation, the plain
reference, the counts, the trace reduction and the peaks) lives here and
imports nothing of the program; only ``system.py`` touches ``repro``.
"""
