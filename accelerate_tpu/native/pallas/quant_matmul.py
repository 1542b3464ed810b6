"""Fused quantized matmul kernels for the int8 / fp8 paths.

Every projection in the model zoo funnels through `ops.fp8.matmul_einsum`,
and every equation it (and its `_grad_equations` transposes) emits is
matmul-shaped with the contracted labels a contiguous prefix or suffix of
each operand and ``out == a_rest + b_rest`` — so each one is a 2D matmul in
one of four orientations, reached by reshape (never a physical transpose).
`_parse_matmul_eq` proves that per equation; anything it can't prove falls
back to the reference lowering.

Two kernels share the tiling — a grid over (M, N, C) tiles with the
contraction innermost, accumulated in a VMEM scratch, so the staged
operands stay a few MiB whatever the contraction length (an 8B-width
prefill, C = 14336, does not fit VMEM whole):

- :func:`int8_matmul_fused` — the `ops.int8.int8_einsum` body: per-row
  dynamic activation quantization (amax/127), int8×int8→int32 dot on the
  MXU, rescale by ``row scale × per-channel weight scale``. The per-row
  scale needs the whole row, so it is one small XLA reduction outside; the
  quantized activations and the int32 accumulator never round-trip through
  HBM. Integer accumulation is exact (tiling the contraction changes
  nothing) and the elementwise ops replicate `quantize_act` literally; the
  one divergence from the fallback is the activation-scale divide, which
  Pallas lowers with TPU semantics (reciprocal-multiply, 1 ulp off IEEE) —
  parity is ~1e-7 relative, not bitwise. The weight may be a layer stack
  ``(L, ...)`` read in place at a traced ``layer``: the index rides scalar
  prefetch and the weight block's index map picks the layer, so a scan over
  layers hands the kernel the whole stack and no layer is sliced out of it
  first (XLA cannot fuse a slice into a custom call: it would copy the
  layer's matrix, and the kernel would read the copy).
- :func:`scaled_matmul` — the fp8 contraction `(dot(qx, qw) * scale)` with
  fp8 operands fed to the MXU directly (``preferred_element_type=f32``)
  instead of XLA's materialized upcast. Quantization stays OUTSIDE (the
  custom_vjp residuals carry qx/qw for the backward); parity is to f32
  tolerance (different accumulation order), not bitwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .dispatch import kernel_mode, pallas_available, register_kernel
from .moe_experts import min_tile_rows

register_kernel(
    "int8_matmul", "fused per-row quantize -> int8 MXU dot -> rescale"
)
register_kernel(
    "fp8_matmul", "fp8 dot + scalar rescale without the XLA upcast round-trip"
)

if pallas_available():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ...ops.autotune import cached_pick_block, tuned_call_kwargs
    from ...ops.flash_attention import pick_block as _divisor

    def pick_block(dim, candidates=(512, 256, 128, 64, 32, 16, 8)):
        # Persisted autotune table first (ATX_BLOCK_QUANT_MATMUL /
        # $ATX_AUTOTUNE_DIR), divide-exactly heuristic otherwise.
        return cached_pick_block("quant_matmul", dim, candidates)
else:  # pragma: no cover - environment dependent
    pl = pltpu = None

    def pick_block(dim, candidates=(512, 256, 128, 64, 32, 16, 8)):
        return None


# Contraction tile: the staged (bm, bc) activations, their f32 quantization
# temporaries and the (bc, bn) weights, double-buffered, stay near 10 MiB.
_CONTRACT_BLOCKS = (1024, 512, 256, 128)
# What one program may stage, against the chip's 16 MiB scoped-VMEM limit.
_VMEM_BUDGET = 12 * 1024 * 1024


def _parse_matmul_eq(eq: str):
    """Prove ``eq`` is a pure matmul: returns ``(oa, ob, a_rest, b_rest)``
    with orientations in {"lead", "trail"} (contracted labels at the front
    or back of the operand, same order in both), or ``None``."""
    if "->" not in eq or "." in eq:
        return None
    lhs, out = eq.split("->")
    if "," not in lhs:
        return None
    a, b = lhs.split(",")
    contracted = "".join(c for c in a if c in b)
    if not contracted or any(c in out for c in contracted):
        return None  # no contraction, or shared batch labels: not this kernel
    if "".join(c for c in b if c in a) != contracted:
        return None  # contracted labels must appear in the same order
    a_rest = "".join(c for c in a if c not in contracted)
    b_rest = "".join(c for c in b if c not in contracted)
    if a_rest + b_rest != out or not a_rest or not b_rest:
        return None
    if a.startswith(contracted):
        oa = "lead"
    elif a.endswith(contracted):
        oa = "trail"
    else:
        return None
    if b.startswith(contracted):
        ob = "lead"
    elif b.endswith(contracted):
        ob = "trail"
    else:
        return None
    return oa, ob, len(a_rest), len(b_rest)


def _tile(dim: int, unit: int, pick) -> int:
    """A tile of ``dim`` that Mosaic lowers: a multiple of ``unit`` (8
    sublanes / 128 lanes) dividing it exactly, else the whole axis."""
    blk = pick(dim)
    return blk if blk is not None and blk % unit == 0 else dim


def _plan(eq: str, a, b, out_dtype):
    """2D views + tiles for ``eq``:
    ``(oa, ob, M, N, C, bm, bn, bc, a_rest, b_rest)`` (the output shape is
    ``a_rest + b_rest``), or ``None`` when the equation is not a matmul or
    no tiling fits the VMEM budget."""
    parsed = _parse_matmul_eq(eq)
    if parsed is None:
        return None
    oa, ob, na, nb = parsed
    a_shape, b_shape = a.shape, b.shape
    a_rest = a_shape[:na] if oa == "trail" else a_shape[-na:]
    b_rest = b_shape[-nb:] if ob == "lead" else b_shape[:nb]
    c_dims = a_shape[na:] if oa == "trail" else a_shape[: len(a_shape) - na]
    M = int(functools.reduce(lambda x, y: x * y, a_rest, 1))
    N = int(functools.reduce(lambda x, y: x * y, b_rest, 1))
    C = int(functools.reduce(lambda x, y: x * y, c_dims, 1))
    if M == 0 or N == 0 or C == 0:
        return None
    # M is the sublane axis of the output tile, and the lane axis of a
    # leading-contracted (C, M) operand view.
    bm = _tile(M, 128 if oa == "lead" else 8, pick_block)
    bn = _tile(N, 128, pick_block)
    bc = _tile(C, 128, lambda d: _divisor(d, _CONTRACT_BLOCKS))
    a_item, b_item = jnp.dtype(a.dtype).itemsize, jnp.dtype(b.dtype).itemsize
    out_item = jnp.dtype(out_dtype).itemsize

    def staged(bn):
        return (
            2 * bm * bc * a_item
            + 2 * bc * bn * b_item
            + 2 * bm * bn * out_item
            + bm * bn * 4  # accumulator scratch
            + bm * bc * 9  # in-kernel quantization temporaries (2 x f32 + int8)
        )

    if bm == M and bn % 128 == 0:
        # One row tile: every weight byte is read once, from HBM, and the
        # (bc, bn) tile is the DMA that reads it. As wide as the budget
        # holds: 16 grid steps of 3.7 MB for a 4096 x 14336 matrix, not 112
        # of 0.5 MB whose fixed cost a step is half their transfer time.
        wider = [n for n in range(bn, N + 1, 128) if N % n == 0 and staged(n) <= _VMEM_BUDGET]
        bn = max(wider, default=bn)
    if staged(bn) > _VMEM_BUDGET:
        return None
    return oa, ob, M, N, C, bm, bn, bc, tuple(a_rest), tuple(b_rest)


def _views(oa, ob, a, b, M, N, C):
    a2 = a.reshape(M, C) if oa == "trail" else a.reshape(C, M)
    b2 = b.reshape(C, N) if ob == "lead" else b.reshape(N, C)
    return a2, b2


def _view_is_free(w_shape, n_out_axes: int, dtype) -> bool:
    """Is the ``(C, N)`` view of a leading-contracted weight the same bytes?
    The chip tiles an array's last two axes (sublanes x 128 lanes; 32
    sublanes for int8): the columns must be the weight's own last axis, and
    contracted axes merge freely only in whole sublane tiles. ``(h, k, d)``
    with k = 128 is ``(h * k, d)`` as it lies; ``(d, h, k)`` as ``(d, h * k)``
    is a copy."""
    contracted = w_shape[: len(w_shape) - n_out_axes]
    return n_out_axes == 1 and (
        len(contracted) == 1 or contracted[-1] % min_tile_rows(dtype) == 0
    )


def _specs(oa, ob, bm, bn, bc):
    # (Every index map takes the grid indices, then whatever rides scalar
    # prefetch: nothing, or the int8 kernel's layer index.)
    if oa == "trail":
        a_spec = pl.BlockSpec((bm, bc), lambda i, j, c, *_: (i, c))
    else:
        a_spec = pl.BlockSpec((bc, bm), lambda i, j, c, *_: (c, i))
    if ob == "lead":
        b_spec = pl.BlockSpec((bc, bn), lambda i, j, c, *_: (c, j))
    else:
        b_spec = pl.BlockSpec((bn, bc), lambda i, j, c, *_: (j, c))
    return a_spec, b_spec


def _dot_dims(oa, ob):
    ca = 1 if oa == "trail" else 0
    cb = 0 if ob == "lead" else 1
    return (((ca,), (cb,)), ((), ()))


def _accumulate(acc_ref, part):
    @pl.when(pl.program_id(2) == 0)
    def _first():
        acc_ref[...] = part

    @pl.when(pl.program_id(2) != 0)
    def _rest():
        acc_ref[...] += part


def _int8_kernel(layer_ref, a_ref, sx_ref, b_ref, ws_ref, o_ref, acc_ref, *, dims):
    del layer_ref  # only the weight's block index map reads it
    # `quantize_act`'s rounding verbatim on one (bm, bc) tile against the
    # row scale, then an exact integer dot; only the scale divide (TPU
    # reciprocal semantics) can differ from the fallback, by 1 ulp.
    sx = sx_ref[...]  # (bm, 1)
    xf = a_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / sx), -127, 127).astype(jnp.int8)
    _accumulate(
        acc_ref,
        jax.lax.dot_general(q, b_ref[...], dims, preferred_element_type=jnp.int32),
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * (sx * ws_ref[...])
        ).astype(o_ref.dtype)


def _scaled_kernel(a_ref, b_ref, s_ref, o_ref, acc_ref, *, dims):
    _accumulate(
        acc_ref,
        jax.lax.dot_general(
            a_ref[...], b_ref[...], dims, preferred_element_type=jnp.float32
        ),
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * s_ref[0, 0]).astype(o_ref.dtype)


def _tiled_call(name, kernel, plan, in_specs, out_dtype, acc_dtype, interpret, n_prefetch=0):
    _, _, M, N, C, bm, bn, bc, _, _ = plan
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(M // bm, N // bn, C // bc),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, c, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        **tuned_call_kwargs(name, interpret, ("parallel", "parallel", "arbitrary")),
    )


def int8_matmul_fused(
    eq: str,
    x: jax.Array,
    wq: jax.Array,
    w_scale: jax.Array,
    layer: jax.Array | int | None = None,
    *,
    interpret: bool = False,
) -> jax.Array | None:
    """Fused `ops.int8.int8_einsum`: quantize rows of ``x``, int8 dot with
    ``wq``, rescale by ``row scale × w_scale``. Requires x contracted on its
    trailing axes (per-row groups = rows of the 2D view) and w on its
    leading axes — true for every int8 forward equation. With ``layer``,
    ``wq`` is a stack ``(L, ...)`` of such weights, read in place at that
    (traced) index; ``w_scale`` is the one layer's either way. ``None`` when
    the equation/shapes aren't supported (caller falls back)."""
    w_shape = wq.shape if layer is None else wq.shape[1:]
    plan = _plan(eq, x, jax.ShapeDtypeStruct(w_shape, wq.dtype), x.dtype)
    if plan is None:
        return None
    oa, ob, M, N, C, bm, bn, bc, a_rest, b_rest = plan
    if oa != "trail" or ob != "lead":
        return None
    if layer is not None and not _view_is_free(w_shape, len(b_rest), wq.dtype):
        return None  # (L, C, N) would be a relayout of the whole stack, every call
    x2 = x.reshape(M, C)
    a_spec, w_spec = _specs(oa, ob, bm, bn, bc)
    if layer is None:
        w_view = wq.reshape(C, N)  # a lone matrix: the operand it always was
    else:
        w_view = wq.reshape(-1, C, N)
        w_spec = pl.BlockSpec((None, bc, bn), lambda i, j, c, ly: (ly[0], c, j))
    layer = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
    # `quantize_act`'s per-row scale, over the whole contraction.
    amax = jnp.max(jnp.abs(x2.astype(jnp.float32)), axis=1, keepdims=True)
    sx = jnp.maximum(amax, 1e-12) / 127.0
    # w_scale has w's rank: contracted axes are size 1 (quantizer keepdims)
    # and so is any output axis that shares one scale (per-head weights
    # quantize per head_dim channel) — broadcast to the per-column vector.
    ws2 = jnp.broadcast_to(
        w_scale.astype(jnp.float32), (1,) * (len(w_shape) - len(b_rest)) + b_rest
    ).reshape(1, N)
    out = _tiled_call(
        "int8_matmul",
        functools.partial(_int8_kernel, dims=_dot_dims(oa, ob)),
        plan,
        [
            a_spec,
            pl.BlockSpec((bm, 1), lambda i, j, c, ly: (i, 0)),
            w_spec,
            pl.BlockSpec((1, bn), lambda i, j, c, ly: (0, j)),
        ],
        x.dtype,
        jnp.int32,
        interpret,
        n_prefetch=1,
    )(layer, x2, sx, w_view, ws2)
    return out.reshape(a_rest + b_rest)


def scaled_matmul(
    eq: str,
    qa: jax.Array,
    qb: jax.Array,
    scale: jax.Array,
    out_dtype,
    *,
    interpret: bool = False,
) -> jax.Array | None:
    """``(einsum(eq, qa, qb, preferred_element_type=f32) * scale).astype``
    as one kernel — the fp8 forward/backward contraction without the
    materialized upcast. ``scale`` is the scalar product of the per-tensor
    scales. ``None`` when unsupported."""
    plan = _plan(eq, qa, qb, out_dtype)
    if plan is None:
        return None
    oa, ob, M, N, C, bm, bn, bc, a_rest, b_rest = plan
    a2, b2 = _views(oa, ob, qa, qb, M, N, C)
    s2 = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    a_spec, b_spec = _specs(oa, ob, bm, bn, bc)
    out = _tiled_call(
        "scaled_matmul",
        functools.partial(_scaled_kernel, dims=_dot_dims(oa, ob)),
        plan,
        [a_spec, b_spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_dtype,
        jnp.float32,
        interpret,
    )(a2, b2, s2)
    return out.reshape(a_rest + b_rest)


def maybe_int8_matmul(
    eq: str,
    x: jax.Array,
    wq: jax.Array,
    w_scale: jax.Array,
    layer: jax.Array | int | None = None,
) -> jax.Array | None:
    """Dispatch entry for `ops.int8.int8_einsum` (``layer``: ``wq`` is a
    layer stack, read in place there)."""
    mode = kernel_mode("int8_matmul")
    if mode is None:
        return None
    return int8_matmul_fused(eq, x, wq, w_scale, layer, interpret=mode == "interpret")


def maybe_scaled_matmul(
    eq: str, qa: jax.Array, qb: jax.Array, scale: jax.Array, out_dtype
) -> jax.Array | None:
    """Dispatch entry for the fp8 forward/backward contractions."""
    mode = kernel_mode("fp8_matmul")
    if mode is None:
        return None
    return scaled_matmul(eq, qa, qb, scale, out_dtype, interpret=mode == "interpret")
