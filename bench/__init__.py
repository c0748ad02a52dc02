"""Chip benchmark of the RankGraph-2 lifecycle (see ``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on the accelerator and prints one JSON
result line.  Everything a cell needs is found by name: its
configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` (which names the driver in
``bench/cells/``), and each metric's reader in
``bench/metrics/<metric>.py``.
"""
