"""The benchmark (BENCHMARK.json's one entry in ``paths``): ``run.py`` is the
command, everything else is the yardstick — wrappers, trace reduction, peak
table, FLOP and byte counts, plain references — or data: one file per cell,
configuration, role adapter and metric."""
