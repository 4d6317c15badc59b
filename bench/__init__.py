"""Chip benchmark of the AMB trainer: harness, yardstick and cells.

Entry point: ``python3 bench/run.py --workload NAME --seed N --seconds S
--trace 0|1``.  Cells, configurations, traffic mixes and per-layer metric
readers are data and small files found by name; see ``harness.py``.
"""
