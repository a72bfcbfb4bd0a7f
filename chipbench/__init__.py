"""Chip benchmark: cells of one model configuration under one traffic mix,
served through the repository's paged ``Engine`` on a TPU.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py``.  Run a cell from the checkout root with
``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.
"""
