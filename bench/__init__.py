"""On-chip benchmark of the replicated object store (see BENCHMARK.json).

Run one cell as ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.
"""
