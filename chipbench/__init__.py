"""On-chip benchmark of the JAX/Pallas serving and training paths.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON result line.  Everything a cell needs is found by name: its
configuration in ``configs/``, its traffic mix in ``traffic/``, the driver
for the traffic's kind in ``drivers/``, its limits in ``limits/`` and each
per-layer metric's reader in ``metrics/``.
"""
