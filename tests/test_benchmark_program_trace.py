"""The cases of `benchmarks/tests/test_program_trace.py`, run and counted in tier-1
(`pytest tests/`) from the one copy the benchmark keeps."""

from benchmarks.tests.test_program_trace import *  # noqa: F401,F403
