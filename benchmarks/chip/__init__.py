"""The chip benchmark: cells of SGNS training, timed on a TPU and checked
against plain references.

One command runs one cell by name::

    python3 -m benchmarks.chip.run --workload train.wiki.n4 --seed 7 \
        --seconds 30 --trace 0

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own under this directory, found by the name
``BENCHMARK.json`` gives it: ``configs/<config>.json``,
``traffic/<traffic>.json`` (read by ``kinds/<kind>.py``) and
``metrics/<metric>.py``.
"""
