"""On-chip benchmark of the tuning system: ``python bench/run.py --workload <cell> ...``.

Everything that belongs to one configuration, traffic mix or per-layer metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``workloads/<cell>.json``, ``drivers/<driver>.py``
and ``metrics/<metric>.py``.  ``harness.py`` is the one general runner.
"""
