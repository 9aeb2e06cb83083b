"""Chip benchmark of the compressed string store.

``python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. See ``bench/README.md``.
"""
