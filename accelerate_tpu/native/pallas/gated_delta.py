"""The gated delta rule's two state kernels (`ops/gated_delta.py` has the
mathematics and the XLA lowerings both replace).

`gdn_decode` is one token for every decoding row: it reads and writes the
layer-stacked state ``(L, B, H, d_k, d_v)`` float32 where it lies (input
aliased to output, the layer picked by scalar prefetch, as `int8_matmul`
and `flash_decode` pick theirs) and walks only the rows that are decoding:
the grid's steps are laid out from the mask, so the state of every other
row is neither read nor written. A step moves one row's H matrices in and
out (2 x 2.2 MB at 30 x 96 x 192) and does a few passes of the vector unit
over them: the call is bound by those bytes.

`gdn_chunk` is the chunk-to-chunk part of the chunkwise form for a prefill
chunk: the state stays in VMEM while the chunks of a row go by, each
``v' = u - w S``, ``o = qg S + p v'``, ``S <- last S + kdt v'`` on operands
`ops.gated_delta.chunk_prepare` made for all chunks at once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .dispatch import kernel_mode, pallas_available, register_kernel

register_kernel("gdn_decode", "gated delta rule, one token a row, the state stack updated in place")
register_kernel("gdn_chunk", "gated delta rule, chunk-to-chunk state pass of a prefill chunk")

if pallas_available():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ...ops.autotune import tuned_call_kwargs
else:  # pragma: no cover - environment dependent
    pl = pltpu = None

_VMEM_LIMIT = 64 * 2**20
# A step's state block (one row's heads), in and out, double-buffered, stays under this.
_STATE_VMEM_BUDGET = 24 * 2**20
_CHUNK_HEADS = 8  # heads a `gdn_chunk` step handles at most
# The state is float32 and so are the products that read and write it: at the
# MXU's default a float32 operand is rounded to bf16 first.
_CHUNK_DOT_PRECISION = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ gdn_decode
def slots_touched(decoding, n_rows: int, *, in_place: bool):
    """Rows whose state one decode call reads and writes (``decoding``: the
    (B,) mask, numpy or traced): the decoding ones under the kernel, which
    visits one row when none decodes; every row under the XLA lowering, which
    selects over the whole layer."""
    if not in_place:
        return n_rows
    return jnp.maximum(jnp.sum(decoding.astype(jnp.int32)), 1)


def decode_supported(state: jax.Array, *, compiled: bool = False) -> bool:
    """A layer-stacked ``(L, B, H, d_k, d_v)`` float32 state. ``compiled``
    adds Mosaic's tiling of the block's last two axes (d_k whole sublane
    groups) and the VMEM one row's heads take."""
    if state.ndim != 5 or state.dtype != jnp.float32:
        return False
    if compiled:
        H, dk, dv = state.shape[2:]
        if dk % 8 or 4 * H * dk * dv * 4 > _STATE_VMEM_BUDGET:
            return False
    return True


def _decode_kernel(rows_ref, n_ref, layer_ref, qt_ref, kt_ref, v_ref, a_ref, b_ref, s_ref,
                   o_ref, s_out_ref):
    del rows_ref, layer_ref  # only the block index maps read them
    i = pl.program_id(0)
    heads = s_ref.shape[0]

    def update(live: bool):
        for h in range(heads):
            S = s_ref[h]  # (d_k, d_v)
            if not live:
                s_out_ref[h] = S
                continue
            k = kt_ref[:, h : h + 1]  # (d_k, 1): the head's key down the sublanes
            q = qt_ref[:, h : h + 1]
            alpha, beta = a_ref[h : h + 1, :], b_ref[h : h + 1, :]  # (1, d_v), one value
            seen = jnp.sum(k * S, axis=0, keepdims=True)  # k^T S
            write = beta * (v_ref[h : h + 1, :] - alpha * seen)
            S = alpha * S + k * write
            s_out_ref[h] = S
            o_ref[h : h + 1, :] = jnp.sum(q * S, axis=0, keepdims=True)

    # Steps past the last decoding row stay on its blocks: nothing is fetched
    # and what the row's own step left in the output buffers is what is written.
    pl.when(i < n_ref[0])(lambda: update(True))
    # No row decodes at all: the one row visited keeps its state.
    pl.when(jnp.logical_and(i == 0, n_ref[0] == 0))(lambda: update(False))


def gdn_decode(q, k, v, alpha, beta, state, layer, decoding=None, *, interpret: bool = False):
    """q, k: (B, H, d_k); v: (B, H, d_v); alpha, beta: (B, H); state: the
    stack (L, B, H, d_k, d_v) float32, updated at layer ``layer`` for the rows
    where ``decoding`` (B,) holds (all when None). Returns (o (B, H, d_v)
    float32, zero for the other rows; the stack)."""
    f32 = jnp.float32
    L, B, H, dk, dv = state.shape
    if decoding is None:
        decoding = jnp.ones((B,), bool)
    n = jnp.sum(decoding.astype(jnp.int32))
    # The decoding rows first, in order; the steps after them repeat the last.
    order = jnp.argsort(jnp.logical_not(decoding), stable=True).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(B, dtype=jnp.int32), jnp.maximum(n - 1, 0))]
    wide = lambda a: jnp.broadcast_to(a.astype(f32)[..., None], (B, H, dv))
    by_row = lambda i, rows, n, layer: (rows[i], 0, 0)
    vec = lambda shape: pl.BlockSpec((None,) + shape, by_row)
    s_spec = pl.BlockSpec(
        (None, None, H, dk, dv), lambda i, rows, n, layer: (layer[0], rows[i], 0, 0, 0)
    )
    out, state = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[vec((dk, H)), vec((dk, H)), vec((H, dv)), vec((H, dv)), vec((H, dv)), s_spec],
            out_specs=[vec((H, dv)), s_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32), jax.ShapeDtypeStruct(state.shape, f32)],
        # Operand 8 (after the three prefetched scalars and five vectors) is the stack.
        input_output_aliases={8: 1},
        **tuned_call_kwargs("gdn_decode", interpret, ("arbitrary",), _VMEM_LIMIT),
    )(
        rows, n.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.swapaxes(q.astype(f32), 1, 2), jnp.swapaxes(k.astype(f32), 1, 2),
        v.astype(f32), wide(alpha), wide(beta), state,
    )
    return jnp.where(decoding[:, None, None], out, 0.0), state


def maybe_gdn_decode(q, k, v, alpha, beta, state, layer, decoding=None):
    """Dispatch entry: (o, stack) from the kernel where `gdn_decode` may run
    and the stack's shape is its, else ``None`` (the caller updates one layer
    sliced out of the stack with `ops.gated_delta.recurrent_step`)."""
    mode = kernel_mode("gdn_decode")
    if mode is None or not decode_supported(state, compiled=mode == "compiled"):
        return None
    return gdn_decode(q, k, v, alpha, beta, state, layer, decoding, interpret=mode == "interpret")


# ------------------------------------------------------------------- gdn_chunk
def _chunk_heads(H: int) -> int:
    return max(c for c in range(1, _CHUNK_HEADS + 1) if H % c == 0)


def chunk_supported(parts: dict[str, jax.Array], *, compiled: bool = False) -> bool:
    if parts["u"].ndim != 5:
        return False
    if compiled:
        C, dk = parts["w"].shape[-2:]
        if C % 8 or dk % 8:
            return False
    return True


def _chunk_kernel(qg_ref, kdt_ref, w_ref, u_ref, p_ref, last_ref, s0_ref, o_ref, s_out_ref, s_acc):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        s_acc[...] = s0_ref[...]

    dot = functools.partial(
        jnp.dot, preferred_element_type=jnp.float32, precision=_CHUNK_DOT_PRECISION
    )
    for h in range(s_acc.shape[0]):
        S = s_acc[h]
        fresh = u_ref[h] - dot(w_ref[h], S)
        o_ref[h] = dot(qg_ref[h], S) + dot(p_ref[h], fresh)
        s_acc[h] = last_ref[h] * S + dot(kdt_ref[h], fresh)

    @pl.when(n == pl.num_programs(2) - 1)
    def _finish():
        s_out_ref[...] = s_acc[...]


def gdn_chunk(parts: dict[str, jax.Array], state: jax.Array, *, interpret: bool = False):
    """``parts``: `ops.gated_delta.chunk_prepare`'s operands, (B, H, N, ...)
    float32; state (B, H, d_k, d_v) float32. Returns (o (B, H, N, C, d_v),
    new state): `ops.gated_delta.chunk_scan`."""
    B, H, N, C, dv = parts["u"].shape
    dk = parts["w"].shape[-1]
    hb = _chunk_heads(H)
    chunk = lambda *tail: pl.BlockSpec((None, hb, None) + tail, lambda b, h, n: (b, h, n, 0, 0))
    s_spec = pl.BlockSpec((None, hb, dk, dv), lambda b, h, n: (b, h, 0, 0))
    return pl.pallas_call(
        _chunk_kernel,
        grid=(B, H // hb, N),
        in_specs=[chunk(C, dk), chunk(dk, C), chunk(C, dk), chunk(C, dv), chunk(C, C), chunk(1, 1), s_spec],
        out_specs=[chunk(C, dv), s_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, N, C, dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        **tuned_call_kwargs("gdn_chunk", interpret, ("parallel", "parallel", "arbitrary"), _VMEM_LIMIT),
    )(*(parts[n] for n in ("qg", "kdt", "w", "u", "p", "last")), state.astype(jnp.float32))


def maybe_gdn_chunk(parts: dict[str, jax.Array], state: jax.Array):
    """Dispatch entry: the kernel's (o, state) where `gdn_chunk` may run,
    else ``None`` (the caller runs `ops.gated_delta.chunk_scan`)."""
    mode = kernel_mode("gdn_chunk")
    if mode is None or not chunk_supported(parts, compiled=mode == "compiled"):
        return None
    return gdn_chunk(parts, state, interpret=mode == "interpret")
