"""The benchmark: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.

Cells, configurations and per-layer metrics are found by name from
``BENCHMARK.json``: ``configs/<config>.json``, ``workloads/<cell>.json``
(entry kind and traffic), ``entries/<kind>.py`` and ``metrics/<metric>.py``.
"""
