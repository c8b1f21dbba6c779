"""On-chip benchmark of the durable executor: one cell per run, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Everything that belongs to one configuration, traffic mix, cell, metric or
kernel lives in a file of its own under this directory, found by its name.
"""
