"""On-chip serving benchmark: one cell (configuration x traffic mix) per run.

Run ``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout on a machine with a TPU.
Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``, found by the name that
``BENCHMARK.json`` gives it.
"""
