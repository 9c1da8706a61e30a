"""The on-chip benchmark of rnb-tpu: one command, cells driven by data.

``BENCHMARK.json`` at the repo root names the command
(``python3 benchmarks/run.py``), the configurations
(``benchmarks/configs/<name>.json``), the cells and the metrics. A
traffic mix is ``benchmarks/traffic/<name>.json``, read by the one
generator in :mod:`benchmarks.traffic`; a per-layer metric is one file
``benchmarks/layer_metrics/<name>.py`` holding its description and its
reader; a family of models is one file ``benchmarks/families/<name>.py``
(its inputs, weights, operation count and check against its plain
reference ``benchmarks/references/<name>.py``), named by the
configurations that belong to it. The harness finds all of them by the
names in the manifest and in the configuration's file, so a later PR
adds a configuration, a mix, a cell, a metric or a family by adding
files and manifest entries.

From the program the benchmark takes the system under test
(``rnb_tpu.benchmark.run_benchmark``), its per-request stamp tables,
its counters (``BenchmarkResult``) and its kernel names in the device
trace. The yardstick is here: the schedule, the request files, the
reduction from stamps and from the profiler's trace, the peaks, the
operation counts and the float32 references that decide ``correct``.
"""
