"""The cell benchmark (BENCHMARK.json): one command runs one cell once.

    python -m cellbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a data file found by name (``configs/``, ``traffic/``,
``layer_metrics/``); the code here is the yardstick: data generation, the
plain reference, the trace reduction, the table of peaks and the cost of a
grad step from shapes. See ``README.md`` in this directory.
"""
