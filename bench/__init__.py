"""On-chip benchmark of the streaming K-Means mini-app.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip it is started on.
Configurations, traffic mixes and metric readers are files found by name
under ``bench/configs``, ``bench/traffic`` and ``bench/metrics``.
"""
