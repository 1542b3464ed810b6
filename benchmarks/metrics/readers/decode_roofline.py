"""Roofline share of one decode step, which is bound by memory: the bytes it
must read (`flops.decode_step_bytes`: int8 layer weights, the bf16 head, and
the live rows of the KV cache as the client counted them) over the memory
bandwidth, over the median device time of the decode program."""

from benchmarks import flops

from benchmarks.metrics.readers import program_device


def read(reading, programs: str):
    step_ms = program_device.read(reading, programs)
    if not step_ms:
        return None
    live = reading.outcome["counters"]["live_kv_tokens_mean"]
    least = flops.decode_step_bytes(reading.config, live) / reading.peaks["hbm_bytes_per_s"]
    return 100.0 * least * 1e3 / step_ms
