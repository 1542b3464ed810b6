"""The cases of `benchmarks/tests/test_olmo_hybrid_cell.py`, run and counted in
tier-1 (`pytest tests/`) from the one copy the benchmark keeps."""

from benchmarks.tests.test_olmo_hybrid_cell import *  # noqa: F401,F403
