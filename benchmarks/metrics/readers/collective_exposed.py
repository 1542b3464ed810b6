"""Share of the step programs' time during which only a collective ran on a
device (nothing to hide it behind), on the worst device, in per cent."""

from benchmarks.trace import xplane


def read(reading, programs: str):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    worst = None
    for device in trace.devices:
        runs = xplane.whole(xplane.matching(device.modules, programs), trace.window)
        total = sum(e - s for _, s, e in runs)
        if not total:
            continue
        exposed = 0.0
        for _, lo, hi in runs:
            inside = xplane.Device(
                device.index, [], xplane.clip(device.ops, (lo, hi)), xplane.clip(device.async_ops, (lo, hi))
            )
            exposed += xplane.exposed_collective_seconds(inside)
        share = 100.0 * exposed / total
        worst = share if worst is None else max(worst, share)
    return worst
