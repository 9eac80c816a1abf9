"""The benchmark: one cell per run, on the chip, driven by data.

``python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one entry of ``BENCHMARK.json``'s ``workloads`` once and prints one JSON
line.  Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own found by name (``configs/``, ``workloads/``,
``layers/``), and code is found by name too (``kinds/``, ``flops/``,
``readers/``), so a later PR adds files and ``BENCHMARK.json`` entries and
edits nothing that is here.  The yardstick — traffic generation, percentile
arithmetic, the trace reduction, the peaks table, the FLOPs functions, the
plain references and the comparison that decides ``correct`` — lives here and
takes from the program only the system under test, its counters, its
annotations and its kernel names.
"""
