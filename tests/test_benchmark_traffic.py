"""The cases of `benchmarks/tests/test_traffic.py`, run and counted in tier-1
(`pytest tests/`) from the one copy the benchmark keeps."""

from benchmarks.tests.test_traffic import *  # noqa: F401,F403
