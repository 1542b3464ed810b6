"""Roofline share of the expert kernels in a decode step, which is bound by
memory: the bytes of the experts that the steps' routing touched
(`flops_moe.experts_touched_bytes` of the program's exact count
``moe_experts_touched``, a mean over the window's decode steps) over the
memory bandwidth, over the median time the kernels matching ``kernels`` ran
inside one execution of ``programs``. ``None`` where the program has no such
counter or kernel."""

from benchmarks import flops_moe
from benchmarks.metrics.readers import kernel_device


def read(reading, programs: str, kernels: str):
    counters = reading.outcome["counters"]
    kernel_ms = kernel_device.read(reading, programs, kernels)
    if not kernel_ms or not counters.get("moe_experts_touched") or not counters.get("decode_steps"):
        return None
    touched = counters["moe_experts_touched"] / counters["decode_steps"]
    least = flops_moe.experts_touched_bytes(reading.config, touched) / reading.peaks["hbm_bytes_per_s"]
    return 100.0 * least * 1e3 / kernel_ms
