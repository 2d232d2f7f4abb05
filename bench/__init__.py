"""Chip benchmark of the FedAIS training and serving paths.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name (see ``bench/harness.py``).
"""
