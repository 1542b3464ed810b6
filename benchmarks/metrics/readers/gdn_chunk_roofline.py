"""Share of the bf16 peak that the delta rule's chunk kernel reaches in a
prefill chunk of ``bucket`` rows: the operations of what the kernel holds,
the chunk-to-chunk state pass (`flops_gdn.chunk_state_pass_flops`, for the
mean real rows of a chunk over the window from the program's exact counts
``state_rows_real`` / ``state_rows_padded``, scaled to this bucket), over the
peak, over the median time the kernels matching ``kernels`` ran inside one
execution that a ``serve_dispatch`` span with that ``bucket`` dispatched.
The WY factors are made outside the kernel and are in neither the
operations nor the time. ``None`` where the trace holds no such kernel or
span, or the program makes no such count."""

from benchmarks import flops_gdn
from benchmarks.metrics.readers import kernel_device


def read(reading, programs: str, kernels: str, bucket: int):
    counters = reading.outcome["counters"]
    if not counters.get("state_rows_padded"):
        return None
    kernel_ms = kernel_device.read(reading, programs, kernels, bucket)
    if not kernel_ms:
        return None
    real = bucket * counters["state_rows_real"] / counters["state_rows_padded"]
    least = flops_gdn.chunk_state_pass_flops(reading.config, real) / reading.peaks["bf16_flops_per_s"]
    return 100.0 * least * 1e3 / kernel_ms
