"""The on-chip benchmark of the decentralized trainer.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json``.  What belongs to one configuration,
traffic mix, cell or per-layer metric sits in a file of its own, found by
its name: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<cell>.json``, ``metrics/<metric>.py``.  The yardstick (token
generator, reference, operation and byte counts, peaks, trace reduction)
lives here too, apart from the program it measures.
"""
