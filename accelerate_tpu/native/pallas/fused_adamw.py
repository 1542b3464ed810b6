"""Single-pass fused AdamW update.

The fallback (`parallel.host_offload._adamw_slice` under XLA) is a chain of
a dozen elementwise ops; XLA fuses most of them but still materializes the
bias-corrected intermediates and walks param/grad/moments more than once.
This kernel is the whole update — moment EMAs, bias correction, the
weight-decay term, and the learning-rate step — in one pass per block, with
the moment buffers aliased in place (``input_output_aliases``): the
host-offloaded tier's per-layer device-side update becomes one
read-modify-write over the layer slice.

The math replicates `_adamw_slice` literally (same op order, same dtypes,
``jnp`` namespace). Parity is to a few ulps, not bitwise: the divides and
sqrt lower with TPU semantics (reciprocal / rsqrt refinement) inside the
kernel. The disk tier's numpy-namespace call never dispatches here.

Leaves are viewed as (rows, lanes) over their flattened size and walked in
(block_rows, lanes) slabs — whole (8, 128) tiles, which is what Mosaic
lowers. A leaf whose size has no such view, or is too small to be worth a
kernel launch, falls back per leaf — mixing kernel and fallback leaves
within one tree step is fine, each leaf's update is independent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .dispatch import kernel_mode, pallas_available, register_kernel

register_kernel(
    "fused_adamw", "one-pass AdamW step with in-place moment buffers"
)

if pallas_available():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ...ops.autotune import cached_pick_block, tuned_call_kwargs

    def pick_block(dim, candidates=(512, 256, 128, 64, 32, 16, 8)):
        # Persisted autotune table first (ATX_BLOCK_FUSED_ADAMW /
        # $ATX_AUTOTUNE_DIR), divide-exactly heuristic otherwise.
        return cached_pick_block("fused_adamw", dim, candidates)
else:  # pragma: no cover - environment dependent
    pl = pltpu = None

    def pick_block(dim, candidates=(512, 256, 128, 64, 32, 16, 8)):
        return None

# Below this many elements the launch overhead beats the fusion win
# (norms, biases, tiny heads) — those leaves take the XLA fallback.
_MIN_SIZE = 1024
_LANES = (1024, 512, 256, 128)
# 128 rows x 1024 lanes x f32 = 512 KiB per operand slab; seven operands,
# double-buffered, stay under half of the 16 MiB scoped VMEM.
_BLOCK_ROWS = (128, 64, 32, 16, 8)


def _adamw_kernel(
    s_ref, g_ref, mu_ref, nu_ref, p_ref, u_ref, mu_out, nu_out,
    *, b1, b2, eps, weight_decay, has_grad_scale,
):
    # `_adamw_slice` verbatim, one (block_rows, lanes) slab at a time.
    mu = mu_ref[...]
    nu = nu_ref[...]
    g32 = g_ref[...].astype(mu.dtype)
    if has_grad_scale:
        g32 = g32 * s_ref[0, 2].astype(mu.dtype)
    new_mu = b1 * mu + (1.0 - b1) * g32
    new_nu = b2 * nu + (1.0 - b2) * jnp.square(g32)
    # s_ref[0, 0] / [0, 3] are the bias corrections 1 - b**count, taken
    # outside: Mosaic has no scalar pow ("failed to legalize 'math.powf'").
    mu_hat = new_mu / s_ref[0, 0].astype(new_mu.dtype)
    nu_hat = new_nu / s_ref[0, 3].astype(new_mu.dtype)
    step = mu_hat / (jnp.sqrt(nu_hat) + eps) + weight_decay * p_ref[...].astype(
        new_mu.dtype
    )
    u_ref[...] = -s_ref[0, 1].astype(new_mu.dtype) * step
    mu_out[...] = new_mu
    nu_out[...] = new_nu


def _plan(size: int):
    """``(rows, lanes, block_rows)`` for a flat leaf of ``size`` elements, or
    ``None``: lanes a multiple of 128 dividing the size, block rows a
    multiple of 8 dividing the rows (or all of them when fewer than 8)."""
    if size < _MIN_SIZE:
        return None
    lanes = pick_block(size, _LANES)
    if lanes is None or lanes % 128 != 0:
        return None
    rows = size // lanes
    block_rows = rows if rows < 8 else next(
        (b for b in _BLOCK_ROWS if rows % b == 0), None
    )
    if block_rows is None:
        return None
    return rows, lanes, block_rows


def fused_adamw_update(
    g, mu, nu, p, count, lr_t, b1, b2, eps, weight_decay,
    grad_scale=None, *, interpret: bool = False,
):
    """One AdamW step for one leaf: returns ``(update, new_mu, new_nu)``
    exactly like `_adamw_slice`, or ``None`` when the leaf's size doesn't
    tile (caller falls back)."""
    size = int(mu.size)
    plan = _plan(size)
    if plan is None or g.shape != mu.shape or nu.shape != mu.shape or p.shape != mu.shape:
        return None
    # b1/b2/eps/weight_decay are baked into the kernel body; the optimizer
    # passes them as Python floats. A traced value here (someone jitting over
    # the hyperparams) can't be closed over — fall back.
    if not all(isinstance(hp, (int, float)) for hp in (b1, b2, eps, weight_decay)):
        return None
    rows, lanes, block_rows = plan
    c = jnp.asarray(count).astype(mu.dtype).reshape(())
    scalars = jnp.stack(
        [
            (1.0 - b1**c).astype(jnp.float32),
            jnp.asarray(lr_t).astype(jnp.float32).reshape(()),
            (
                jnp.asarray(grad_scale).astype(jnp.float32).reshape(())
                if grad_scale is not None
                else jnp.zeros((), jnp.float32)
            ),
            (1.0 - b2**c).astype(jnp.float32),
        ]
    ).reshape(1, 4)
    view = lambda a: a.reshape(rows, lanes)
    row_spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    kernel = functools.partial(
        _adamw_kernel,
        b1=b1,
        b2=b2,
        eps=eps,
        weight_decay=weight_decay,
        has_grad_scale=grad_scale is not None,
    )
    u, new_mu, new_nu = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        # count / lr / grad-scale are scalars: SMEM, whole array.
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [row_spec] * 4,
        out_specs=[row_spec] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), mu.dtype),
            jax.ShapeDtypeStruct((rows, lanes), mu.dtype),
            jax.ShapeDtypeStruct((rows, lanes), nu.dtype),
        ],
        # Moments update in place; the scalars/g/p operands stay read-only.
        input_output_aliases={2: 1, 3: 2},
        **tuned_call_kwargs("fused_adamw", interpret, ("arbitrary",)),
    )(scalars, view(g), view(mu), view(nu), view(p))
    return u.reshape(mu.shape), new_mu.reshape(mu.shape), new_nu.reshape(mu.shape)


def maybe_fused_adamw(
    g, mu, nu, p, count, lr_t, b1, b2, eps, weight_decay, grad_scale=None
):
    """Dispatch entry for `parallel.host_offload._adamw_slice`."""
    mode = kernel_mode("fused_adamw")
    if mode is None:
        return None
    return fused_adamw_update(
        g, mu, nu, p, count, lr_t, b1, b2, eps, weight_decay, grad_scale,
        interpret=mode == "interpret",
    )
