"""On-chip benchmark of the ridge encoding system.

One command runs one cell of ``BENCHMARK.json``::

    python3 -m bench.run --workload parcels.fit --seed 7 --seconds 30 --trace 0

Everything a cell needs is found by name: its configuration in
``bench/configs/<file>``, its traffic mix in ``bench/traffic/<name>.json``,
its correctness limits in ``bench/limits/<cell>.json``, the driver of its
traffic's ``kind`` in ``bench/drivers/`` and each metric's reader in
``bench/metrics/<metric>.py``.  A new cell, configuration, metric or kind
of run is new files plus ``BENCHMARK.json`` entries.
"""
