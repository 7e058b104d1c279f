"""On-chip benchmark harness for the coded training and serving system.

``benchmarks/chip/run.py`` is the entry point.  Everything that belongs
to one model configuration, one traffic mix, one metric or one cell's
correctness limits sits in a file of its own (``configs/``,
``traffic/``, ``metrics/``, ``limits/``) and is found by the name that
``BENCHMARK.json`` gives it; this package holds the general code.
"""
