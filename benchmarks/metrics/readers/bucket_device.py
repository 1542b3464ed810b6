"""As `program_device`, over the executions of the prefill program that a
``serve_dispatch`` span with ``bucket`` dispatched: the program's spans and
the executions of ``programs`` pair in dispatch order
(`trace.program.pair_dispatches`). ``None`` where the trace holds no such
span (a program from before ISSUE 27)."""

from benchmarks.trace import program, xplane


def read(reading, programs: str, bucket: int):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    device = trace.devices[0]
    runs = program.executions_of_bucket(reading, programs, bucket)
    return xplane.median_ms([xplane.busy_inside(device, (s, e)) for _, s, e in runs])
