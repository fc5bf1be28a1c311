"""Chip benchmark of the PDX vector-search server: one run of one cell is
``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``; ``BENCHMARK.json`` at the repository root names the cells,
their configurations, traffic mixes and metrics."""
