"""Chip benchmark of the serving path: one cell per run, driven by data.

``python chipbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs the cell ``<name>`` of ``BENCHMARK.json`` once and
prints one JSON result line.  Configurations, traffic mixes, limits and
per-layer metric readers are files of their own under this directory,
found by the names ``BENCHMARK.json`` gives them.
"""
