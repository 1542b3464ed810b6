"""Roofline share of the flash-attention forward and backward kernels in a
training step: the least time the chip could take for what those calls
must do (`flops.flash_attention_train_cost`: the larger of operations over
the bf16 peak and bytes over the memory bandwidth) over the time the kernels
matching ``kernels`` took, per execution of the step program ``programs``,
on the first device. Each device runs its share of heads and rows, so the
cost is divided by the chips."""

from benchmarks import flops
from benchmarks.trace import xplane


def read(reading, programs: str, kernels: str):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    device = trace.devices[0]
    runs = xplane.whole(xplane.matching(device.modules, programs), trace.window)
    calls = xplane.matching(device.ops, kernels)
    if not runs or not calls:
        return None
    inside = sum(
        min(e, hi) - max(s, lo) for _, lo, hi in runs for _, s, e in calls if e > lo and s < hi
    )
    if inside <= 0:
        return None
    c = reading.outcome["counters"]
    cost = flops.flash_attention_train_cost(
        reading.config, c["batch_size"], c["seq_len"], reading.config["num_hidden_layers"]
    )
    least = max(
        cost["flops"] / reading.peaks["bf16_flops_per_s"],
        cost["bytes"] / reading.peaks["hbm_bytes_per_s"],
    ) / reading.chips
    return 100.0 * least * len(runs) / inside
