"""Median time, in milliseconds, in which the Pallas kernels whose name
matches ``kernels`` ran inside one execution of the programs matching
``programs``: the union of those kernels' operations between the program's
start and end, over the executions that lie whole inside the traced stretch,
on the first device. With ``bucket``, only the executions that a
``serve_dispatch`` span with that ``bucket`` dispatched.
``None`` where no kernel carries such a name (a program from before
ISSUE 27 names none)."""

from benchmarks.trace import program, xplane


def read(reading, programs: str, kernels: str, bucket=None):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    if bucket is None:
        runs = program.executions(trace, programs)
    else:
        runs = program.executions_of_bucket(reading, programs, bucket)
    seconds = program.kernel_seconds(trace, runs, kernels)
    if not any(seconds):
        return None
    return xplane.median_ms(seconds)
