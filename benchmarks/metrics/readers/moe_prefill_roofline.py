"""Share of the bf16 peak that the expert kernels reach in a prefill chunk
of ``bucket`` rows: the operations of its ``bucket x experts-per-token``
assignments in every layer (`flops_moe.expert_flops`; every row of the
bucket is routed, a final chunk's pad tail too) over the peak, over the
median time the kernels matching ``kernels`` ran inside one execution that a
``serve_dispatch`` span with that ``bucket`` dispatched. ``None`` where the
trace holds no such kernel or span."""

from benchmarks import flops_moe
from benchmarks.metrics.readers import kernel_device


def read(reading, programs: str, kernels: str, bucket: int):
    kernel_ms = kernel_device.read(reading, programs, kernels, bucket)
    if not kernel_ms:
        return None
    config = reading.config
    assignments = bucket * config["moe_num_active_primary_experts"] * config["num_hidden_layers"]
    least = flops_moe.expert_flops(config, assignments) / reading.peaks["bf16_flops_per_s"]
    return 100.0 * least * 1e3 / kernel_ms
