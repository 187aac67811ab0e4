"""The chip benchmark of Big-means: one command, driven by data.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the accelerator it is started
on and prints one JSON result line.  Configurations, traffic mixes,
traffic kinds and per-layer metric readers are files found by name (see
:mod:`bench.spec`), so a new cell needs new files and no edit.
"""
