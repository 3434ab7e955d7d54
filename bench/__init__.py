"""The chip benchmark of the HCiM serving stack (see BENCHMARK.json).

Run one cell once from the root of a checkout::

    python3 -m bench.run --workload m7b-chat --seed 7 --seconds 51 --trace 0
"""
