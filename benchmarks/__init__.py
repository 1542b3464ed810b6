"""The benchmark: the driver's yardstick for `accelerate_tpu` on the chip.

`BENCHMARK.json` at the root of the repository names the cells; everything
they need lives here, found by name (`benchmarks/run.py`).
"""
