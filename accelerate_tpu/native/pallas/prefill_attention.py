"""Flash attention of a prefill chunk over the slot KV cache.

A chunk of ``S > 1`` new query rows, already written into the cache at rows
``[start, start + S)``, attends to layer ``layer`` of the layer-stacked
(L, B, T, K*h) cache where it lies: the whole stacks are the operands, the
layer index and the per-row cursor ride scalar prefetch, and the block index
maps pick (layer, row, block, kv head), as `decode_attention.flash_decode`
does for one query row. No layer is sliced out and no (S, T) scores exist.

Key row ``j`` is seen from the query at position ``p = start + r`` iff
``j <= p``. A query tile visits the K/V blocks up to the one that holds its
last position and no further: `decode_attention.live_steps` lays the grid out
from the cursors (a tile of ``bq`` queries is one of its "rows", of length
``start + (tile + 1) * bq``), so a block past the cursor costs no copy and no
step, and the cost of a chunk follows the cursor, not the slot's length. Only
the blocks that cross the diagonal are masked.

The query heads of one kv head share its K/V block: the GQA group is folded
into the query tile's rows (``group * bq`` of them), so a block of K and V is
read once a kv head and each product is (group * bq, h) x (h, bk). Online
softmax in float32 with running max / normalizer / accumulator in VMEM
scratch, operands in the cache's dtype, ``p`` cast to it for the second
product: the arithmetic of `ops/flash_attention.py:_fwd_kernel`.

Parity vs `models.layers.dot_product_attention` is to tolerance, not bitwise
(per-block partials merged in f32 against one full-row softmax of scores the
oracle's einsum hands back in the operands' dtype).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .decode_attention import live_steps
from .dispatch import kernel_mode, pallas_available, register_kernel

register_kernel(
    "prefill_attn",
    "a prefill chunk's flash attention over the slot KV cache, up to the cursor",
)

# Query rows of one product (the GQA group times the query tile, at most) and
# rows of a K/V block: what `perf/flash_prefill_live_rows.py` measured fastest
# on a v5e at the serve cells' shapes (PERF.md section 6, PR 35).
_PRODUCT_ROWS = 2048
_QUERY_TILES = (256, 128, 64, 32, 16)
_BLOCK_ROWS = (512, 256, 128, 64, 32, 16, 8)
# Steps the grid may have: their (row, block) tables ride scalar prefetch.
_MAX_STEPS = 4096
_LANES = 128

if pallas_available():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ...ops.flash_attention import _NEG_INF, pick_block, tuned_call_kwargs
else:  # pragma: no cover - environment dependent
    pl = pltpu = None


def pick_tiles(S: int, T: int, group: int) -> tuple[int, int] | None:
    """``(bq, bk)``: the query tile (the largest that divides ``S`` with
    ``group * bq`` within `_PRODUCT_ROWS`) and the K/V block (the largest that
    divides ``T``); None where no tile divides (the kernel never pads)."""
    if pl is None:
        return None
    most = max(_PRODUCT_ROWS // group, _QUERY_TILES[-1])
    bq = next((c for c in _QUERY_TILES if c <= most and S % c == 0), None)
    bk = pick_block(T, _BLOCK_ROWS) if T >= _BLOCK_ROWS[-1] else None
    if bq is None or bk is None:
        return None
    return bq, bk


def supported(
    q: jax.Array,
    k: jax.Array,
    *,
    compiled: bool = False,
    quantized: bool = False,
    window: int | None = None,
) -> bool:
    """Shape support: more than one query row per sequence, a layer-stacked
    (L, B, T, K*h) cache of float rows (an int8 cache, ``quantized``, and a
    sliding ``window`` are declined) whose last axis holds whole
    GQA-divisible heads, ``S`` a multiple of a query tile and ``T`` of a
    block. ``compiled`` adds what Mosaic's tiling asks: a head is a whole
    number of lane tiles (or the only one), a block a whole number of them
    too."""
    if quantized or window is not None or q.ndim != 4 or k.ndim != 4:
        return False
    B, S, H, h = q.shape
    T, K = k.shape[2], k.shape[3] // h
    if S < 2 or k.shape[1] != B or k.shape[3] != K * h or K == 0 or H % K != 0:
        return False
    if h > _LANES and h % _LANES != 0:
        return False
    if not (jnp.issubdtype(k.dtype, jnp.floating) and jnp.issubdtype(q.dtype, jnp.floating)):
        return False
    tiles = pick_tiles(S, T, H // K)
    if tiles is None:
        return False
    bq, bk = tiles
    if B * (S // bq) * (T // bk) > _MAX_STEPS:
        return False
    if compiled:
        if h % _LANES != 0 and H != 1:
            return False
        if bk % _LANES != 0 and bk != T:
            return False
    return True


def _across(x, n: int):
    """The lane-replicated (rows, 128) ``x`` as (rows, n)."""
    return x[:, :n] if n <= _LANES else pltpu.repeat(x, n // _LANES, 1)


def _prefill_kernel(
    start_ref,
    layer_ref,
    row_ref,
    block_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    q_s,
    m_s,
    l_s,
    acc_s,
    *,
    scale: float,
    bq: int,
    bk: int,
    nq: int,
    cache_len: int,
):
    """One live K/V block of one query tile of one kv head. Scratch holds the
    tile's queries with the group folded into rows (row ``g * bq + r`` is
    query ``r`` of the kv head's query head ``g``) and the softmax state.

    The running max is kept replicated over 128 lanes and the normalizer as
    lane-wise partial sums (reduced over lanes once, at the end): as (rows, 1)
    columns their relayouts, not the products, set a step's time (PERF.md
    section 6, PR 35: 0.75 -> 0.38 ms a layer)."""
    del layer_ref  # only the block index maps read it
    i = pl.program_id(1)
    t = block_ref[i]
    tile = row_ref[i]
    first = start_ref[tile // nq] + (tile % nq) * bq  # position of the tile's first query
    h = q_s.shape[1]
    group = q_s.shape[0] // bq
    w = l_s.shape[1]  # min(bk, 128)

    @pl.when(t == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        for g in range(group):
            q_s[g * bq : (g + 1) * bq, :] = q_ref[:, g * h : (g + 1) * h]

    def block(diagonal: bool):
        k = k_ref[...].astype(q_s.dtype)  # (bk, h)
        v = v_ref[...].astype(q_s.dtype)
        s = scale * jax.lax.dot_general(
            q_s[...], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (group * bq, bk) f32
        if diagonal:
            rows = first + (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) & (bq - 1))
            cols = t * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_s[...]  # (rows, 128), every lane the same
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _across(m_new, bk))
        alpha = jnp.exp(m_prev - m_new)
        lanes = p[:, :w]
        for c in range(1, bk // w):
            lanes = lanes + p[:, c * w : (c + 1) * w]
        l_s[...] = l_s[...] * alpha[:, :w] + lanes
        acc_s[...] = acc_s[...] * _across(alpha, h) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_s[...] = m_new

    # Block 0 holds key 0, which every query sees: no row of the state is
    # ever all masked. Only a block that reaches past the tile's first
    # position needs the mask.
    diagonal = (t + 1) * bk - 1 > first
    pl.when(diagonal)(functools.partial(block, True))
    pl.when(jnp.logical_not(diagonal))(functools.partial(block, False))

    # The tile's last block, as `live_steps` counts them.
    @pl.when(t == (jnp.minimum(first + bq, cache_len) + bk - 1) // bk - 1)
    def _finish():
        out = acc_s[...] / jnp.maximum(jnp.sum(l_s[...], axis=-1, keepdims=True), 1e-30)
        for g in range(group):
            o_ref[:, g * h : (g + 1) * h] = out[g * bq : (g + 1) * bq].astype(o_ref.dtype)


def flash_prefill(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    start: jax.Array | int,
    layer: jax.Array | int = 0,
    *,
    scale: float | None = None,
    tiles: tuple[int, int] | None = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, S, H, h), the chunk's queries; k/v: the layer-stacked
    (L, B, T, K*h) cache buffers, the chunk's own rows already written at
    ``[start, start + S)``, read in place at layer ``layer``; start: () or
    (B,) cursors. ``tiles`` overrides `pick_tiles` (powers of two that divide
    S and T; the timing script's). Returns (B, S, H, h) in q's dtype."""
    B, S, H, h = q.shape
    T, K = k.shape[2], k.shape[3] // h
    group = H // K
    tiles = tiles or pick_tiles(S, T, group)
    if tiles is None:
        raise ValueError(f"no tiles divide a chunk of {S} rows against a cache of {T}")
    bq, bk = tiles
    nq = S // bq
    scale = scale if scale is not None else float(1.0 / (h**0.5))

    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (B,))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    # A query tile is a row of `live_steps`: its blocks end at the one that
    # holds its last position, tile after tile and row after row.
    ends = start[:, None] + (jnp.arange(nq, dtype=jnp.int32) + 1) * bq
    n_steps, row, block = live_steps(ends.reshape(-1), T, bk)

    # Head kk's queries are lanes [kk * group * h, (kk + 1) * group * h) of a
    # row of q as (B, S, H * h), its K/V lanes [kk * h, (kk + 1) * h).
    q_spec = pl.BlockSpec(
        (None, bq, group * h),
        lambda kk, i, start, layer, row, block: (row[i] // nq, row[i] % nq, kk),
    )
    kv_spec = pl.BlockSpec(
        (None, None, bk, h),
        lambda kk, i, start, layer, row, block: (layer[0], row[i] // nq, block[i], kk),
    )
    kernel = functools.partial(
        _prefill_kernel, scale=scale, bq=bq, bk=bk, nq=nq, cache_len=T
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(K, n_steps),  # the live blocks only: a dead one costs no step
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((group * bq, h), q.dtype),
                pltpu.VMEM((group * bq, _LANES), jnp.float32),
                pltpu.VMEM((group * bq, min(bk, _LANES)), jnp.float32),
                pltpu.VMEM((group * bq, h), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, S, H * h), q.dtype),
        **tuned_call_kwargs("flash_prefill", interpret, ("arbitrary", "arbitrary")),
    )(start, layer, row, block, q.reshape(B, S, H * h), k, v)
    return out.reshape(B, S, H, h)


def maybe_flash_prefill(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    start: jax.Array | int,
    layer: jax.Array | int = 0,
    *,
    quantized: bool = False,
    window: int | None = None,
) -> jax.Array | None:
    """Dispatch entry: the kernel output when `prefill_attn` is enabled and
    the shapes are supported, else ``None`` (caller slices layer ``layer``
    out of the stack and runs the exact reference lowering)."""
    mode = kernel_mode("prefill_attn")
    if mode is None or not supported(
        q, k, compiled=mode == "compiled", quantized=quantized, window=window
    ):
        return None
    return flash_prefill(q, k, v, start, layer, interpret=mode == "interpret")
