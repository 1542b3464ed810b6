"""int8×int8→int32 matmuls on quantized weights — the int8 MXU path.

The v5e (and every TPU since v4i) runs int8×int8 contractions at ~2× the
bf16 MXU rate. The weight-only quantization in `utils/quantization.py`
(the bitsandbytes analog, reference `utils/bnb.py:44`) stores int8 weights
but dequantizes to bf16 before every matmul — fine for bandwidth-bound
B=1 decode, where HBM bytes are the roofline, but prefill and speculative
VERIFY are compute-bound: dequantizing first leaves the 2× int8 MXU rate
on the table.

This module closes that gap with the fp8 module's recipe at int8 dtypes:

- activations are dynamically quantized per tensor (symmetric,
  ``amax/127`` — one fp32 scale, no calibration state);
- the contraction runs on int8 values with int32 accumulation
  (``preferred_element_type``), which XLA lowers onto the int8 MXU;
- the int32 result is rescaled by ``act_scale × weight_scale`` where the
  weight scales are the per-output-channel scales the quantized pytree
  already carries — so the WEIGHT quantization error is identical to the
  dequantize-first path and only the activation rounding is new.

Enablement mirrors `fp8_matmuls`: inside an :func:`int8_compute` context
(read at trace time), `ops.fp8.matmul_einsum` routes quantized-dict
weights through :func:`int8_einsum_quantized` instead of dequantizing.
Packed int4 weights unpack to int8 values first (elementwise) and then
take the same int8 MXU contraction.

A model that scans over layer-stacked weights keeps the int8 value stacks
out of the scan's ``xs`` (:func:`hoist_layer_stacks`) and hands each
contraction the whole stack with the layer index (:func:`at_layer`): the
`int8_matmul` kernel reads the layer where it lies. Sliced by the scan, a
layer's matrix is copied out of the stack before the kernel reads the copy.

Inference-only by design: the backward of an int8 contraction would need
requantized gradients; training stays on the bf16/fp8 paths.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import threading
from typing import Any

import jax
import jax.numpy as jnp

_MODE = threading.local()
# Key of a quantized node whose values are a layer stack: the layer to read.
_LAYER_KEY = "__layer__"


def int8_compute_enabled() -> bool:
    return getattr(_MODE, "int8", False)


@contextlib.contextmanager
def int8_compute(enabled: bool = True):
    """While active (including during jit tracing), `matmul_einsum` runs
    quantized-weight contractions on the int8 MXU instead of dequantizing
    to the compute dtype first.

    CAVEAT (jit cache): the mode is read at TRACE time, and jax shares the
    trace cache across ``jax.jit`` wrappers of the SAME function object —
    ``jax.jit(f)`` traced outside the context and ``jax.jit(f)`` called
    inside it silently reuse one jaxpr. To jit a function per-mode, wrap it
    with :func:`with_int8_compute` (a fresh function object whose every
    trace happens inside the context)."""
    prev = getattr(_MODE, "int8", False)
    _MODE.int8 = enabled
    try:
        yield
    finally:
        _MODE.int8 = prev


def with_int8_compute(fn):
    """Return a NEW callable that always executes (and therefore always
    TRACES) ``fn`` inside :func:`int8_compute` — the safe way to build an
    int8-mode jit next to a normal-mode jit of the same function:

        f_bf16 = jax.jit(fwd)
        f_int8 = jax.jit(with_int8_compute(fwd))

    The fresh function object gives the int8 variant its own jit cache
    entry, so it can never alias the bf16 trace."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with int8_compute():
            return fn(*args, **kwargs)

    return wrapped


def quantize_act(
    x: jax.Array, reduce_axes: tuple[int, ...] | None = None
) -> tuple[jax.Array, jax.Array]:
    """Dynamic int8 scaling: ``(q, scale)`` with ``q ≈ x/scale`` in int8 and
    ``scale = amax/127`` (fp32). ``reduce_axes=None`` gives one per-tensor
    scalar; a tuple gives PER-ROW scales (amax over the contracted axes,
    keepdims) — one scale per token, which cuts the activation-rounding
    drift that per-tensor scaling accumulates with depth (outlier tokens no
    longer squash everyone else's range)."""
    xf = x.astype(jnp.float32)
    if reduce_axes is None:
        amax = jnp.max(jnp.abs(xf))
    else:
        amax = jnp.max(jnp.abs(xf), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _w_scale_to_out(eq: str, w_scale: jax.Array) -> jax.Array:
    """Align a per-output-channel weight scale (w's shape with contracted
    dims kept as size 1) to the OUTPUT of ``einsum(eq, x, w)``.

    Contracted axes of ``w_scale`` are size 1 (the quantizer reduces over
    them with keepdims), so summing them away via einsum is the identity;
    output labels w doesn't carry broadcast as size-1 dims."""
    ins, out = eq.split("->")
    _, b = ins.split(",")
    kept = "".join(lbl for lbl in out if lbl in b)
    squeezed = jnp.einsum(f"{b}->{kept}", w_scale.astype(jnp.float32))
    shape = tuple(
        squeezed.shape[kept.index(lbl)] if lbl in kept else 1 for lbl in out
    )
    return squeezed.reshape(shape)


def _unpack_int4(packed: jax.Array) -> jax.Array:
    """Packed uint8 (two 4-bit values per byte, `utils/quantization.py`
    layout) -> int8 values in [-7, 7], doubling the last axis."""
    hi = (packed >> 4).astype(jnp.int8) - 8
    lo = (packed & 0xF).astype(jnp.int8) - 8
    return jnp.stack([hi, lo], axis=-1).reshape(
        packed.shape[:-1] + (packed.shape[-1] * 2,)
    )


def _x_contracted_axes(eq: str) -> tuple[int, ...]:
    """Axes of x reduced by ``einsum(eq, x, w)`` (labels shared with w and
    absent from the output) — the per-row quantization group."""
    ins, out = eq.split("->")
    a, b = ins.split(",")
    return tuple(i for i, lbl in enumerate(a) if lbl in b and lbl not in out)


def _x_scale_to_out(eq: str, x_scale: jax.Array) -> jax.Array:
    """Align a per-row activation scale (x's shape with contracted dims kept
    as size 1) to the output of ``einsum(eq, x, w)`` — the x-side twin of
    `_w_scale_to_out`."""
    ins, out = eq.split("->")
    a, _ = ins.split(",")
    kept = "".join(lbl for lbl in out if lbl in a)
    squeezed = jnp.einsum(f"{a}->{kept}", x_scale.astype(jnp.float32))
    shape = tuple(
        squeezed.shape[kept.index(lbl)] if lbl in kept else 1 for lbl in out
    )
    return squeezed.reshape(shape)


_WEIGHT_PATHS: contextvars.ContextVar[list[str] | None] = contextvars.ContextVar(
    "atx_int8_weight_paths", default=None
)


@contextlib.contextmanager
def record_weight_paths():
    """Collect how every quantized contraction traced inside the block got
    its weights: ``"in_place"`` (the kernel read the layer out of the stack
    where it lies) or ``"sliced"`` (it, or the XLA fallback, was handed one
    layer's matrix); after either, ``"act_quantized_once"`` where the kernel
    took its rows as one row tile of int8 activations quantized once
    (`quant_matmul.one_row_tile_plan`). Trace-time bookkeeping only, as
    `models.layers.record_attention_paths`: the serving engine wraps its
    programs' traces in it, so a silent fall to the slice shows in
    ``Engine.stats['weights_sliced']``."""
    paths: list[str] = []
    token = _WEIGHT_PATHS.set(paths)
    try:
        yield paths
    finally:
        _WEIGHT_PATHS.reset(token)


def hoist_layer_stacks(blocks: Any) -> tuple[Any, dict]:
    """Split layer-stacked blocks for a scan whose body contracts through
    `matmul_einsum`: ``(xs, stacks)``. Where the `int8_matmul` kernel will
    run, ``xs`` is ``blocks`` without the int8 value stacks (scales, norms
    and biases stay: kilobytes a layer) and ``stacks`` holds them by path,
    for `at_layer` to hand back whole inside the body. Everywhere else
    ``stacks`` is empty and the scan slices as before: outside
    `int8_compute`, with the kernel off (XLA fuses the slice into its own
    dot), for packed int4 (unpacked elementwise first: a whole stack would
    be), and under a mesh of several devices (this runtime does not
    partition a Pallas call: it would gather the stack)."""
    from ..native.pallas.dispatch import kernel_mode
    from ..parallel.mesh import ambient_mesh
    from ..utils.quantization import _QUANT_KEY, is_quantized

    if not int8_compute_enabled() or kernel_mode("int8_matmul") is None:
        return blocks, {}
    mesh = ambient_mesh()
    if mesh is not None and mesh.axis_names and mesh.size > 1:
        return blocks, {}
    stacks: dict = {}

    def visit(path, node):
        if not (is_quantized(node) and _QUANT_KEY in node):
            return node
        stacks[path] = node[_QUANT_KEY]
        return {**node, _QUANT_KEY: None}

    return jax.tree_util.tree_map_with_path(visit, blocks, is_leaf=is_quantized), stacks


def at_layer(block: Any, stacks: dict, layer: jax.Array) -> Any:
    """One scan step's ``block`` with the stacks `hoist_layer_stacks` kept
    back, whole, and the step's ``layer`` index beside each."""
    from ..utils.quantization import _QUANT_KEY, is_quantized

    if not stacks:
        return block

    def visit(path, node):
        if path not in stacks:
            return node
        return {**node, _QUANT_KEY: stacks[path], _LAYER_KEY: layer}

    return jax.tree_util.tree_map_with_path(visit, block, is_leaf=is_quantized)


def int8_einsum(
    eq: str,
    x: jax.Array,
    wq: jax.Array,
    w_scale: jax.Array,
    layer: jax.Array | None = None,
) -> jax.Array:
    """``einsum(eq, x, dequant(wq))`` computed as int8×int8→int32 on the
    MXU: dynamic per-token activation quantization, int32 accumulation,
    exact rescale by ``per-row act scale × per-channel weight scale``.

    When the `int8_matmul` Pallas kernel is enabled (`native/pallas/`),
    the quantize -> dot -> rescale runs as one fused kernel — integer
    accumulation exact, parity within 1 ulp of the activation scale —
    without the intermediate HBM round-trips; rows it would split into
    several row tiles are quantized once, in XLA, by :func:`quantize_act`,
    and the kernel's dot and rescale equal this fallback's bit for bit
    (``"act_quantized_once"`` in `record_weight_paths`). With ``layer``, ``wq`` is a
    layer stack ``(L, ...)`` that the kernel reads in place at that index
    (``w_scale`` is the layer's own); where it cannot (kernel off, or the
    kernel's 2D view of this stack would be a relayout), the layer is
    sliced out here and everything goes on as for a lone matrix."""
    from ..native.pallas.quant_matmul import maybe_int8_matmul, one_row_tile_plan

    w = jax.ShapeDtypeStruct(wq.shape if layer is None else wq.shape[1:], wq.dtype)
    out = None
    if layer is not None:
        out = maybe_int8_matmul(eq, x, wq, w_scale, layer)
        if out is None:
            wq = jax.lax.dynamic_index_in_dim(wq, layer, 0, keepdims=False)
    paths = _WEIGHT_PATHS.get()
    if paths is not None:
        paths.append("sliced" if out is None else "in_place")
    if out is None:
        out = maybe_int8_matmul(eq, x, wq, w_scale)
    if out is not None:
        if paths is not None and one_row_tile_plan(eq, x, w) is not None:
            paths.append("act_quantized_once")
        return out
    qx, sx = quantize_act(x, _x_contracted_axes(eq))
    acc = jnp.einsum(eq, qx, wq, preferred_element_type=jnp.int32)
    scale = _x_scale_to_out(eq, sx) * _w_scale_to_out(eq, w_scale)
    return (acc.astype(jnp.float32) * scale).astype(x.dtype)


def int8_einsum_quantized(eq: str, x: jax.Array, wnode: dict) -> jax.Array:
    """`int8_einsum` over a ``{"__quant__"|"__quant4__", "scale"}`` node
    from `utils/quantization.py` (int4 unpacks to int8 values first —
    same MXU path, half the HBM bytes)."""
    from ..utils.quantization import _QUANT4_KEY, _QUANT_KEY

    if _QUANT4_KEY in wnode:
        return int8_einsum(eq, x, _unpack_int4(wnode[_QUANT4_KEY]), wnode["scale"])
    return int8_einsum(eq, x, wnode[_QUANT_KEY], wnode["scale"], wnode.get(_LAYER_KEY))
