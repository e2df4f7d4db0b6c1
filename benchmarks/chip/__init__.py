"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<traffic>.json``, ``data/<generator>.py``,
``runners/<runner>.py``, ``checks/<check>.py`` and ``metrics/<metric>.py``.
"""
