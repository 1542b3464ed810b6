"""Median busy time on the device inside one execution of the programs
matching ``programs``, in milliseconds: the union of the operations that
ran between the program's start and end, over the executions that lie whole
inside the traced stretch, on the first device."""

from benchmarks.trace import xplane


def read(reading, programs: str):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    device = trace.devices[0]
    runs = xplane.whole(xplane.matching(device.modules, programs), trace.window)
    return xplane.median_ms([xplane.busy_inside(device, (s, e)) for _, s, e in runs])
