"""Grouped gated-FFN experts over rows sorted by expert.

The dropless expert layer (`ops/moe.py:moe_dropless`) sorts its
``rows x top_k`` assignments by expert and pads each expert's group to a
whole number of row tiles, so that every tile of ``tile_rows`` rows belongs
to one expert. This kernel walks the tiles: for tile ``t`` it computes

    (act(x_t @ W_gate[e]) * (x_t @ W_up[e])) @ W_down[e],    e = tile_expert[t]

reading the expert's three matrices from the weight stacks where the model
keeps them. The stacks are whole ``(L, E, D, F)`` / ``(L, E, F, D)``
operands; the layer index and the tile -> expert table ride scalar prefetch
and the block index maps pick ``(layer, expert)``, so no layer and no expert
is ever sliced out of a stack (a slice of one layer's experts would move its
bytes a second time). Consecutive tiles of one expert keep the same block
index, so its weights are fetched once; the tiles past ``n_tiles`` (the
static bound on tiles is larger than what a step routes) repeat the last
expert in use, fetch nothing and compute nothing.

The feed-forward width is walked in chunks of ``f_chunk`` columns with an
f32 accumulator, so the staged weight blocks fit VMEM at any width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .dispatch import pallas_available, register_kernel

register_kernel(
    "moe_experts",
    "grouped gated-FFN experts over expert-sorted rows, weight stacks read in place",
)

if pallas_available():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ...ops.autotune import tuned_call_kwargs
else:  # pragma: no cover - environment dependent
    pl = pltpu = None

# Weight blocks staged at once (gate + up + down chunk, double-buffered) stay
# under this; the call raises Mosaic's scoped-VMEM limit to hold them.
_WEIGHT_VMEM_BUDGET = 48 * 2**20
_VMEM_LIMIT = 100 * 2**20

def min_tile_rows(dtype) -> int:
    """Smallest row tile Mosaic lays out for ``dtype`` (sublanes x packing)."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def pick_f_chunk(d_model: int, d_ff: int, itemsize: int) -> int:
    """Largest lane-aligned divisor of ``d_ff`` whose three double-buffered
    weight blocks fit the budget; ``d_ff`` itself when it fits."""
    def staged(chunk):
        return 2 * 3 * d_model * chunk * itemsize

    if staged(d_ff) <= _WEIGHT_VMEM_BUDGET:
        return d_ff
    fitting = [
        c for c in range(128, d_ff, 128) if d_ff % c == 0 and staged(c) <= _WEIGHT_VMEM_BUDGET
    ]
    return max(fitting) if fitting else d_ff


def supported(w_gate: jax.Array, tile_rows: int, dtype, *, compiled: bool = False) -> bool:
    """Weight stacks ``(L, E, D, F)`` for rows of ``dtype`` in tiles of
    ``tile_rows``. ``compiled`` adds Mosaic's tiling: lane-aligned D and F
    chunk, a row tile of whole sublane groups, staged weights in budget."""
    if w_gate.ndim != 4:
        return False
    if compiled:
        D, F = w_gate.shape[2:]
        chunk = pick_f_chunk(D, F, w_gate.dtype.itemsize)
        if D % 128 or chunk % 128 or tile_rows % min_tile_rows(dtype):
            return False
        if 2 * 3 * D * chunk * w_gate.dtype.itemsize > _WEIGHT_VMEM_BUDGET:
            return False
    return True


def _experts_kernel(
    tile_expert_ref, n_tiles_ref, layer_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_s,
    *, activation, n_chunks: int,
):
    del tile_expert_ref, layer_ref  # only the block index maps read them
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(t < n_tiles_ref[0])
    def _tile():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        hidden = (activation(gate) * up).astype(x.dtype)
        acc_s[...] += jnp.dot(hidden, wd_ref[...], preferred_element_type=jnp.float32)

    @pl.when(f == n_chunks - 1)
    def _finish():
        o_ref[...] = acc_s[...].astype(o_ref.dtype)


def moe_experts(
    x: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    tile_expert: jax.Array,
    n_tiles: jax.Array,
    layer: jax.Array | int = 0,
    *,
    tile_rows: int,
    activation=jax.nn.relu,
    interpret: bool = False,
) -> jax.Array:
    """x: (R, D) rows sorted by expert, every expert's group padded to whole
    tiles of ``tile_rows``; w_gate / w_up: (L, E, D, F) and w_down:
    (L, E, F, D), read in place at layer ``layer``; ``activation`` the gate's
    (a callable on f32); tile_expert: (R /
    tile_rows,) int32, the expert of each tile (tiles past ``n_tiles``
    repeat the last one in use); n_tiles: () int32. Returns (R, D) in x's
    dtype; rows of tiles past ``n_tiles`` are not written."""
    R, D = x.shape
    F = w_gate.shape[3]
    chunk = pick_f_chunk(D, F, w_gate.dtype.itemsize)
    n_chunks = F // chunk
    n_row_tiles = R // tile_rows
    n_tiles = jnp.asarray(n_tiles, jnp.int32).reshape(1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def chunk_of(t, f, n):
        # A tile past the last one in use stays on the last chunk fetched.
        return jnp.where(t < n[0], f, n_chunks - 1)

    rows = pl.BlockSpec((tile_rows, D), lambda t, f, te, n, ly: (t, 0))
    w_in = pl.BlockSpec(
        (None, None, D, chunk), lambda t, f, te, n, ly: (ly[0], te[t], 0, chunk_of(t, f, n))
    )
    w_out = pl.BlockSpec(
        (None, None, chunk, D), lambda t, f, te, n, ly: (ly[0], te[t], chunk_of(t, f, n), 0)
    )
    return pl.pallas_call(
        functools.partial(_experts_kernel, activation=activation, n_chunks=n_chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_row_tiles, n_chunks),
            in_specs=[rows, w_in, w_in, w_out],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((tile_rows, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        **tuned_call_kwargs("moe_experts", interpret, ("parallel", "arbitrary"), _VMEM_LIMIT),
    )(tile_expert.astype(jnp.int32), n_tiles, layer, x, w_gate, w_up, w_down)
