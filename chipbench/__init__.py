"""Chip benchmark of the MSR checkpointer.

Run one cell with ``python chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; ``BENCHMARK.json`` at the repository's
root names the cells, their configurations, traffic mixes and metrics.
"""
