"""On-chip benchmark of the rearrangement library and the serving stack.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything a cell needs is found by
name: its configuration in ``configs/``, its traffic mix in ``traffic/``,
its runner in ``runners/``, each library op in ``ops/``, each kernel's
work function in ``work/`` and each metric's reader in ``metrics/``.
"""
