"""fp8 matmuls with dynamic per-tensor scaling.

The reference ships three fp8 backends (TransformerEngine
`utils/transformer_engine.py:26-88`, torchao `utils/ao.py:103`
`convert_model_to_fp8_ao`, MS-AMP `accelerator.py:2164-2211`) that swap
`nn.Linear` for fp8-scaled variants. The TPU-native analog is a *function*, not
a module swap: every matmul-shaped einsum in `models/layers.py` routes through
:func:`matmul_einsum`, which under the fp8 mode quantizes both operands and
runs the contraction on fp8 values.

Recipe (the torchao "dynamic scaling" recipe — no amax history to carry in the
train state, unlike TE's delayed scaling):

- forward: x and w quantized to **e4m3** (max 448) with per-tensor scales
  ``amax/448``; the dot accumulates in fp32 and the result is rescaled by
  ``scale_x * scale_w``.
- backward: the cotangent is quantized to **e5m2** (max 57344 — gradients
  need exponent range, not mantissa) and both transposed dots run on fp8
  values the same way.
- first/last layers (embedding lookup, logits head) are *not* routed through
  fp8 — the reference's torchao path filters them too (`utils/ao.py:31-92`)
  because they dominate quantization error.

On hardware with fp8 MXU support XLA lowers these dots natively; elsewhere
(CPU simulation, older TPUs) XLA upcasts the fp8 *values* — numerics are
identical (the quantization happened on the way in), only the speed benefit
is hardware-dependent.
"""

from __future__ import annotations

import contextlib
import threading
from functools import partial

import jax
import jax.numpy as jnp

E4M3 = jnp.float8_e4m3fn
E5M2 = jnp.float8_e5m2

_MODE = threading.local()


def fp8_enabled() -> bool:
    return getattr(_MODE, "fp8", False)


def fp8_hits() -> int:
    """How many matmuls were routed to fp8 inside the current (innermost)
    `fp8_matmuls` context — lets callers detect a model that never touches
    `matmul_einsum` (for which fp8 mode would be a silent no-op)."""
    return getattr(_MODE, "hits", 0)


@contextlib.contextmanager
def fp8_matmuls(enabled: bool = True):
    """While active (including during jit tracing), `matmul_einsum` lowers to
    fp8-quantized contractions."""
    prev = getattr(_MODE, "fp8", False)
    prev_hits = getattr(_MODE, "hits", 0)
    _MODE.fp8 = enabled
    _MODE.hits = 0
    try:
        yield
    finally:
        _MODE.fp8 = prev
        _MODE.hits = prev_hits


def matmul_einsum(eq: str, x: jax.Array, w) -> jax.Array:
    """The one matmul entry point for every projection in the model zoo
    (`models/layers.py`, `ops/moe.py`).

    Normally a plain einsum with the weight cast to the activation dtype
    (the bf16-compute / fp32-master policy). Inside an `fp8_matmuls()`
    context — which `Accelerator` enters when ``mixed_precision='fp8'`` —
    it lowers to a dynamically-scaled fp8 contraction instead (reference fp8
    backends: `utils/ao.py:103`, `utils/transformer_engine.py:26-88`).

    ``w`` may also be a quantized-weight node from `utils/quantization.py`:
    inside an `ops.int8.int8_compute()` context the contraction runs
    int8×int8→int32 on the int8 MXU (`ops/int8.py`); otherwise the node
    dequantizes to the activation dtype and takes the normal path."""
    if isinstance(w, dict):
        from ..utils.quantization import dequantize_array
        from .int8 import int8_compute_enabled, int8_einsum_quantized

        if int8_compute_enabled() and not fp8_enabled():
            return int8_einsum_quantized(eq, x, w)
        w = dequantize_array(w, x.dtype)
    if fp8_enabled():
        _MODE.hits = getattr(_MODE, "hits", 0) + 1
        return fp8_einsum(eq, x, w.astype(x.dtype))
    return jnp.einsum(eq, x, w.astype(x.dtype))


def quantize(x: jax.Array, dtype=E4M3) -> tuple[jax.Array, jax.Array]:
    """Per-tensor dynamic scaling: returns ``(q, scale)`` with
    ``q ≈ x / scale`` in ``dtype`` and ``scale = amax / finfo(dtype).max``
    (fp32 scalar), so ``q`` spans the full fp8 range."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    fmax = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(amax, 1e-12) / fmax
    q = (xf / scale).astype(dtype)
    return q, scale


def _grad_equations(eq: str) -> tuple[str, str]:
    """Transpose equations for ``einsum(eq, x, w)``: returns
    ``(dx_eq, dw_eq)`` with ``dx = einsum(dx_eq, g, w)`` and
    ``dw = einsum(dw_eq, x, g)``. Valid for matmul-shaped equations where
    every label of each operand appears in the output or the other operand
    (true for all projections in `models/layers.py`)."""
    ins, out = eq.split("->")
    a, b = ins.split(",")
    return f"{out},{b}->{a}", f"{a},{out}->{b}"


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_einsum(eq: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """``einsum(eq, x, w)`` computed on dynamically-scaled fp8 operands
    (e4m3 forward / e5m2 cotangent), fp32 accumulation."""
    return _fp8_einsum_fwd(eq, x, w)[0]


def _contract(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _scaled_contract(eq, qa, qb, scale, out_dtype):
    """``(dot(qa, qb) * scale).astype(out_dtype)`` — through the `fp8_matmul`
    Pallas kernel when enabled (fp8 operands straight to the MXU, no
    materialized upcast), else the exact reference expression."""
    from ..native.pallas.quant_matmul import maybe_scaled_matmul

    out = maybe_scaled_matmul(eq, qa, qb, scale, out_dtype)
    if out is not None:
        return out
    return (_contract(eq, qa, qb) * scale).astype(out_dtype)


def _fp8_einsum_fwd(eq, x, w):
    qx, sx = quantize(x, E4M3)
    qw, sw = quantize(w, E4M3)
    out = _scaled_contract(eq, qx, qw, sx * sw, x.dtype)
    # Zero-size sentinels carry the primal dtypes (x and w may differ) so the
    # cotangents come back dtype-exact, as custom_vjp requires.
    return out, (qx, sx, qw, sw, jnp.zeros((), x.dtype), jnp.zeros((), w.dtype))


def _fp8_einsum_bwd(eq, res, g):
    qx, sx, qw, sw, x_proto, w_proto = res
    dx_eq, dw_eq = _grad_equations(eq)
    qg, sg = quantize(g, E5M2)
    dx = _scaled_contract(dx_eq, qg, qw, sg * sw, x_proto.dtype)
    dw = _scaled_contract(dw_eq, qx, qg, sx * sg, w_proto.dtype)
    return dx, dw


fp8_einsum.defvjp(_fp8_einsum_fwd, _fp8_einsum_bwd)
