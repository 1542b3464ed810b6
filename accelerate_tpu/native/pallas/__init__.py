"""Pallas hot-path kernel tier (ROADMAP direction 3).

Custom TPU kernels for the hot paths the XLA lowerings leave on the
table: flash-decode attention over the slot KV cache (the fallback ignores
KV-quantization bandwidth headroom), a prefill chunk's flash attention over
the same cache up to its cursor (the fallback scores every row of the slot
and writes the scores out), fused quantize→dot→rescale matmuls for
the int8/fp8 paths (fp8 round-trips through XLA's upcast), a single-pass
fused AdamW update (the host-offloaded optimizer tier), and the grouped
expert feed-forward of the dropless MoE layer (the fallback slices a
layer's experts out of the weight stack first), and the gated delta rule's
state kernels (`gated_delta.py`: a decode step on the state stack in place,
a prefill chunk's state pass).

Every kernel sits behind the dispatch-by-availability registry in
`dispatch.py`: TPU backend + pallas importable + shape/dtype supported →
kernel; anything else → the exact current lowering, byte-identical to a
build without this package. `force_kernels(mode, name=None)` pins any kernel
off, on, or into interpret mode (the CPU bit-parity test path).
"""

from __future__ import annotations

from .dispatch import (  # noqa: F401
    force_kernels,
    kernel_mode,
    kernel_status,
    pallas_available,
    register_kernel,
)

# Importing a kernel module registers it: `kernel_status()` lists every
# kernel as soon as the package is imported, not only those a trace has
# already reached.
from . import (  # noqa: E402,F401
    decode_attention,
    fused_adamw,
    gated_delta,
    moe_experts,
    prefill_attention,
    quant_matmul,
)
