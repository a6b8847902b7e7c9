"""Push's on-chip benchmark: one harness driven by the data in BENCHMARK.json.

Run from the root of a checkout:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Each configuration, traffic mix, training or serving mode and per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it (see ``bench/core/registry.py``).
"""
