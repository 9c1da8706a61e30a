"""The on-chip benchmark of rnb-tpu: one command, cells driven by data.

``BENCHMARK.json`` at the repo root names the command
(``python3 benchmarks/run.py``), the configurations
(``benchmarks/configs/<name>.json``), the cells and the metrics. A
traffic mix is ``benchmarks/traffic/<name>.json``, read by the one
generator in :mod:`benchmarks.traffic`; a per-layer metric is one file
``benchmarks/layer_metrics/<name>.py`` holding its description and its
reader. The harness finds all of them by the names in the manifest, so
a later PR adds a configuration, a mix, a cell or a metric by adding
files and one manifest entry.

From the program the benchmark takes the system under test
(``rnb_tpu.benchmark.run_benchmark``), its per-request stamp tables,
its counters (``BenchmarkResult``) and its kernel names in the device
trace. The yardstick is here: the schedule, the dataset, the reduction
from stamps and from the profiler's trace, the peaks, the operation
counts and the float32 reference that decides ``correct``.
"""
