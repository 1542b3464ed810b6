"""Roofline share of one decode step of a sparse-expert decoder with two
kinds of cache layer, which is bound by memory: the bytes it must read
(`flops_moe.decode_step_bytes`: attention and router weights, the experts
touched, the head, the live rows of both kinds of cache, each from the
program's exact counts as a mean over the window's decode steps) over the
memory bandwidth, over the median device time of the decode program.
``None`` where the program makes no such counts."""

from benchmarks import flops_moe
from benchmarks.metrics.readers import program_device


def read(reading, programs: str):
    counters = reading.outcome["counters"]
    step_ms = program_device.read(reading, programs)
    steps = counters.get("decode_steps")
    if not step_ms or not steps or "moe_experts_touched" not in counters:
        return None
    least = flops_moe.decode_step_bytes(
        reading.config,
        counters["moe_experts_touched"] / steps,
        counters["kv_rows_live_full"] / steps,
        counters["kv_rows_live_window"] / steps,
    ) / reading.peaks["hbm_bytes_per_s"]
    return 100.0 * least * 1e3 / step_ms
