"""Roofline share of the delta rule's decode kernel, which is bound by
memory: the bytes the live states must move (`flops_gdn.gdn_decode_bytes` of
the program's exact count ``state_slots_live``, a mean over the window's
decode steps: each (slot, layer) state read and written once, with its
vectors) over the memory bandwidth, over the median time the kernels
matching ``kernels`` ran inside one execution of ``programs``. ``None`` where
the program has no such counter or kernel."""

from benchmarks import flops_gdn
from benchmarks.metrics.readers import kernel_device


def read(reading, programs: str, kernels: str):
    counters = reading.outcome["counters"]
    if not counters.get("state_slots_live") or not counters.get("decode_steps"):
        return None
    kernel_ms = kernel_device.read(reading, programs, kernels)
    if not kernel_ms:
        return None
    live = counters["state_slots_live"] / counters["decode_steps"]
    least = flops_gdn.gdn_decode_bytes(reading.config, live) / reading.peaks["hbm_bytes_per_s"]
    return 100.0 * least * 1e3 / kernel_ms
