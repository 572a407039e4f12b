"""Chip benchmark of the GreenFlow serving path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``: a configuration from ``bench/configs``
under a traffic mix from ``bench/traffic``, on the chips JAX finds.
"""
