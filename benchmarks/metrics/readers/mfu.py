"""Model FLOP/s utilization of training, from the device's own clock: the
operations the forward and backward passes of one step need
(`benchmarks/flops.py`: no recomputation, no embedding lookup) over the
step's period on the device - the median length of the executions of the
programs matching ``programs`` that lie whole inside the traced stretch
plus the median idle gap between two of them, on the first device - over
chips times the bf16 peak. The host's clock and the profiler's start and
stop have no part in it. Not a kernel's roofline share."""

from benchmarks import flops
from benchmarks.stats import median
from benchmarks.trace import xplane


def read(reading, programs: str):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    runs = xplane.matching(trace.devices[0].modules, programs)
    lengths = [e - s for _, s, e in xplane.whole(runs, trace.window)]
    if not lengths:
        return None
    gaps = [b - a for a, b in xplane.gaps_between(runs)]
    period = median(lengths) + (median(gaps) if gaps else 0.0)
    counters = reading.outcome["counters"]
    per_step = flops.train_flops_per_token(reading.config, counters["seq_len"]) * counters["tokens_per_step"]
    return 100.0 * per_step / period / (reading.chips * reading.peaks["bf16_flops_per_s"])
