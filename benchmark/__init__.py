"""The benchmark of this repository: one command runs any cell of
BENCHMARK.json by name (``python3 benchmark/run.py --workload <name> ...``)."""
