"""Roofline share of one decode step of a hybrid decoder (recurrent states
beside KV rows), which is bound by memory: the bytes it must move
(`flops_gdn.decode_step_bytes`: every layer's weights and the head, the live
states read and written, the live rows of the KV cache, each from the
program's exact counts as a mean over the window's decode steps) over the
memory bandwidth, over the median device time of the decode program.
``None`` where the program makes no such counts."""

from benchmarks import flops_gdn
from benchmarks.metrics.readers import program_device


def read(reading, programs: str):
    counters = reading.outcome["counters"]
    steps = counters.get("decode_steps")
    if not steps or "state_slots_live" not in counters:
        return None
    step_ms = program_device.read(reading, programs)
    if not step_ms:
        return None
    least = flops_gdn.decode_step_bytes(
        reading.config, counters["state_slots_live"] / steps, counters["kv_rows_live_full"] / steps
    ) / reading.peaks["hbm_bytes_per_s"]
    return 100.0 * least * 1e3 / step_ms
