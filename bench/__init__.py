"""On-chip benchmark of the streaming mini-app's served path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip it is started on.
Configurations, traffic mixes, metric readers and the models the
configurations name are files found by name under ``bench/configs``,
``bench/traffic``, ``bench/metrics`` and ``bench/apps``.
"""
