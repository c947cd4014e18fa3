"""Chip benchmark of the secure-aggregation training step and the party engine.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``bench/configs/<config>.json``
holds the sizes (with ``<config>.reference.py``, the plain reference,
beside it), ``bench/traffic/<traffic>.json`` the mix,
``bench/cells/<cell>.json`` the limits of the comparison that decides
``correct``, and ``bench/metrics/<metric>.py`` the reader of each metric.
"""
