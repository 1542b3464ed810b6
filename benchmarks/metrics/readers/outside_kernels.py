"""Median busy time, in milliseconds, inside one execution of the programs
matching ``programs`` in which no Pallas kernel ran, named or not: the union
of all operations minus the union of the `tpu_custom_call`s (cache slices,
weight copies, norms, sampling: what XLA made around the kernels), over the
executions that lie whole inside the traced stretch, on the first device."""

from benchmarks.trace import program, xplane


def read(reading, programs: str):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    device = trace.devices[0]
    runs = program.executions(trace, programs)
    kernels = program.kernel_seconds(trace, runs, None)
    return xplane.median_ms(
        [xplane.busy_inside(device, (s, e)) - k for (_, s, e), k in zip(runs, kernels)]
    )
