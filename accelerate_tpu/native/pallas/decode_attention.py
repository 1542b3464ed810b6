"""Flash-decode attention over the slot KV cache.

Single query row per sequence (decode: T_new == 1) attending over the whole
cached prefix, split-K over the cache length with an online-softmax merge —
the FlashDecoding / PagedAttention-style kernel reduced to our static-shape
slot cache. The grid is one step a *live* block of one batch row: a block is
whole cache rows (every kv head of a token is one contiguous piece of the
stack), a row's blocks follow one another carrying running max / normalizer
/ accumulator in VMEM scratch, and the host-shipped length cursor masks the
tail of the last one so the padded slot tail never enters the softmax.

The kernel reads the cache where the model keeps it: the whole layer-stacked
(L, B, T, K*h) buffer is the operand, the layer index rides scalar prefetch
beside the cursors, and the block index map picks (layer, row, block). No
layer is sliced out of the stack and nothing is reshaped on the way in.

It moves only the rows the cursors say are live. `live_steps` lays the
steps out from the cursors, ``cdiv(length, blk)`` blocks a row and one for an
empty slot, and the grid's bound is their number (a traced scalar): a block
past a cursor costs no copy and no step, and the next row's first block is
fetched while this row's last is computed. `rows_fetched` is the same
arithmetic on the host, for the engine's ``kv_rows_fetched_*`` counters.

Inside a step the kv heads are taken up to `_HEADS_A_PRODUCT` at a time: their
query heads sit block-diagonally in one (heads * group, heads * h) operand
(built once a row), so one product against the block's lanes gives every
head's scores and one more every head's values. The off-diagonal terms are
exact zeros, the mathematics a head is unchanged, and a block costs two
products instead of two a head.

The int8-KV variant dequantizes inside the kernel (``k * scale`` per cache
block): the fallback lowering materializes the full bf16 dequant copy of the
cache before a single attention flop, this kernel reads the int8 bytes once.

Parity vs `models.layers.dot_product_attention` is to tolerance, not bitwise:
the oracle computes one full-row softmax, this kernel merges per-block
partials (both in f32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .dispatch import kernel_mode, pallas_available, register_kernel

register_kernel(
    "decode_attn",
    "single-query flash-decode over the slot KV cache (bf16 + int8-dequant)",
)

# One K (or V) block is whole cache rows: as many as this many bytes hold,
# so that a live block's copy hides a grid step's fixed cost, but no more than
# an eighth of a slot, so that the one block an empty or short slot costs
# stays a small part of a short cache (and never under 128 rows).
_BLOCK_BYTES = 512 * 1024
_BLOCK_ROWS = (1024, 512, 256, 128, 64, 32, 16, 8)
# kv heads whose scores (and values) one product gives: the query operand
# holds them block-diagonally, so its zeros grow with the square of this.
_HEADS_A_PRODUCT = 8

if pallas_available():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ...ops.autotune import cached_pick_block, tuned_call_kwargs
    from ...ops.flash_attention import _NEG_INF

    def pick_block(dim: int, row_bytes: int) -> int | None:
        """Rows of a block for a cache of ``dim`` rows of ``row_bytes`` (all
        kv heads of one token): the largest tile of at most `_BLOCK_BYTES`
        and an eighth of ``dim`` that divides ``dim``. Persisted autotune
        table first (ATX_BLOCK_DECODE_ATTENTION / $ATX_AUTOTUNE_DIR)."""
        most = max(min(_BLOCK_BYTES // row_bytes, dim // 8), 128)
        candidates = tuple(c for c in _BLOCK_ROWS if c <= most)
        return cached_pick_block("decode_attention", dim, candidates, dtype=f"rows_of_{row_bytes}")
else:  # pragma: no cover - environment dependent
    pl = pltpu = None
    _NEG_INF = -1e30

    def pick_block(dim: int, row_bytes: int) -> int | None:
        return None


def _live_blocks(xp, lengths, cache_len: int, blk: int):
    """Blocks a row costs: up to the one that holds its row ``length - 1``,
    an empty slot's one (``xp``: numpy on the host, jax.numpy in a trace)."""
    return xp.maximum(-(-xp.minimum(lengths, cache_len) // blk), 1)


def live_steps(lengths: jax.Array, cache_len: int, blk: int):
    """The kernel's grid, one step a live block: ``(n, row, block)`` with
    step ``i < n`` on block ``block[i]`` of batch row ``row[i]``, rows in
    order and each row's blocks in order up to the one that holds its row
    ``length - 1`` (an empty slot's block 0, so every row is visited).
    ``row`` and ``block`` have the static size of every block of every row.
    Masked sums over (B, B) and (steps, B), no scan and no gather: XLA leaves
    them inside a layer loop, where they are a few small fusions a layer."""
    B = lengths.shape[0]
    blocks = _live_blocks(jnp, lengths, cache_len, blk)  # (B,)
    rows = jnp.arange(B, dtype=jnp.int32)
    first = jnp.where(rows[None, :] < rows[:, None], blocks[None, :], 0).sum(axis=1)  # a row's first step
    step = jnp.arange(B * (cache_len // blk), dtype=jnp.int32)
    begun = step[:, None] >= first[None, :]  # (steps, B); a row has a step, so `first` rises
    row = begun.sum(axis=1).astype(jnp.int32) - 1
    block = step - jnp.where(begun, first[None, :], 0).max(axis=1)
    return (first[-1] + blocks[-1]).astype(jnp.int32), row, block.astype(jnp.int32)


def rows_fetched(lengths, cache_len: int, row_bytes: int) -> int:
    """Rows of one layer's K (as many again of V) that one call copies out of
    the stack for rows at these ``lengths`` (what `flash_decode` is handed:
    cursor + 1, an empty slot's 1): `live_steps`' blocks, on the host. Every
    row where the shape is not the kernel's."""
    blk = pick_block(cache_len, row_bytes)
    if blk is None:
        return int(np.size(lengths) * cache_len)
    return int(_live_blocks(np, np.asarray(lengths), cache_len, blk).sum() * blk)


def _decode_kernel(
    len_ref,
    layer_ref,
    row_ref,
    block_ref,
    q_ref,
    k_ref,
    ks_ref,
    v_ref,
    vs_ref,
    o_ref,
    m_s,
    l_s,
    acc_s,
    qd_s,
    *,
    scale: float,
    blk: int,
):
    """One live block of one batch row (`live_steps`). Scratch holds, for
    each set of `_HEADS_A_PRODUCT` kv heads, ``gp`` rows a head (its query
    heads, padded to a sublane tile): the block-diagonal queries ``qd_s`` and
    the accumulator, (sets, heads * gp, heads * h), and max / normalizer."""
    del layer_ref  # only the block index maps read it
    i = pl.program_id(0)
    t = block_ref[i]
    length = len_ref[row_ref[i]]
    group, h = q_ref.shape[1:]
    sets, rows, lanes = acc_s.shape
    heads, gp = lanes // h, rows * h // lanes
    diagonal = [(slice(kk * gp, kk * gp + group), slice(kk * h, (kk + 1) * h)) for kk in range(heads)]

    @pl.when(t == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        qd_s[...] = jnp.zeros_like(qd_s)
        for c in range(sets):
            for kk, (own_rows, own_lanes) in enumerate(diagonal):
                qd_s[c, own_rows, own_lanes] = q_ref[c * heads + kk].astype(jnp.float32)

    def by_head(ref, c):
        """A set's per-token scales, (heads, 1, blk) -> (heads * gp, blk)."""
        return jnp.concatenate(
            [
                jnp.broadcast_to(ref[c * heads + kk].astype(jnp.float32), (gp, blk))
                for kk in range(heads)
            ],
            axis=0,
        )

    # An empty row's one block (length 0: nothing is attended) is not computed.
    @pl.when(t * blk < length)
    def _block():
        cols = t * blk + jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 1)
        for c in range(sets):
            own = slice(c * lanes, (c + 1) * lanes)
            s = jax.lax.dot_general(
                qd_s[c],
                k_ref[:, own].astype(jnp.float32),  # (blk, heads * h)
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (heads * gp, blk): row kk * gp + g is query head g of kv head kk
            if ks_ref is not None:
                # Per-token dequant scale applied to the score column it belongs
                # to: (q . k_q) * s == q . (k_q * s), on the scores not the block.
                s = s * by_head(ks_ref, c)
            s = s * scale
            s = jnp.where(cols < length, s, _NEG_INF)

            m_prev = m_s[c]  # (heads * gp, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)

            l_s[c] = l_s[c] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = p
            if vs_ref is not None:
                pv = p * by_head(vs_ref, c)
            # Every head's probabilities against every head's values: a head's
            # own lanes of its own rows are the ones read at the end.
            acc_s[c] = acc_s[c] * alpha + jax.lax.dot_general(
                pv,
                v_ref[:, own].astype(jnp.float32),  # (blk, heads * h)
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_s[c] = m_new

    @pl.when((t + 1) * blk >= length)  # the row's last live block
    def _finish():
        for c in range(sets):
            for kk, (own_rows, own_lanes) in enumerate(diagonal):
                o_ref[c * heads + kk] = (
                    acc_s[c, own_rows, own_lanes] / jnp.maximum(l_s[c, own_rows], 1e-30)
                ).astype(o_ref.dtype)


def supported(q: jax.Array, k: jax.Array, *, compiled: bool = False, quantized: bool = False) -> bool:
    """Shape support: one query token per row, a layer-stacked (L, B, T, K*h)
    cache whose last axis holds whole GQA-divisible heads, and a cache length
    some tile divides exactly (the kernel never pads).
    ``compiled`` adds what Mosaic's (8, 128) tiling asks of the blocks: the
    per-head slice of the flattened (K*h) axis is a whole number of lane
    tiles, and the int8 scale rows (``quantized``) are lane-aligned too."""
    if q.ndim != 4 or k.ndim != 4 or q.shape[1] != 1:
        return False
    B, _, H, h = q.shape
    T, K = k.shape[2], k.shape[3] // h
    if k.shape[1] != B or k.shape[3] != K * h or K == 0 or H % K != 0:
        return False
    blk = pick_block(T, _row_bytes(k))
    if blk is None:
        return False
    if compiled:
        if h % 128 != 0 and K != 1:
            return False
        if blk % 8 != 0 and blk != T:
            return False
        if quantized and blk % 128 != 0 and blk != T:
            return False
    return True


def _row_bytes(k) -> int:
    return k.shape[3] * jnp.dtype(k.dtype).itemsize


def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    layer: jax.Array | int = 0,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, 1, H, h); k/v: the layer-stacked (L, B, T, K*h) cache buffers
    (bf16/f32, or int8 with per-(token, head) ``*_scale`` of shape
    (L, B, T, K)), read in place at layer ``layer``; lengths: () or (B,)
    valid-prefix cursors. Returns (B, 1, H, h) in q's dtype."""
    B, S, H, h = q.shape
    if S != 1:
        raise ValueError(f"flash_decode is single-query only, got T_new={S}")
    T, K = k.shape[2], k.shape[3] // h
    group = H // K
    blk = pick_block(T, _row_bytes(k))
    if blk is None:
        raise ValueError(f"no block tile divides cache length {T}")
    scale = scale if scale is not None else float(1.0 / (h**0.5))

    qt = q.reshape(B, K, group, h)  # head = kk * group + g, the oracle's layout
    # Cursors, layer index and the grid's steps ride scalar prefetch (SMEM):
    # the block index maps read which row and block a step is on.
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1), (B,))
    lengths = jnp.minimum(lengths, T)  # the kernel ends a row at the block that holds its last
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    n_steps, row, block = live_steps(lengths, T, blk)
    heads = max(c for c in range(1, _HEADS_A_PRODUCT + 1) if K % c == 0)
    sets, gp = K // heads, -(-group // 8) * 8

    q_spec = pl.BlockSpec((None, K, group, h), lambda i, lens, layer, row, block: (row[i], 0, 0, 0))
    # Whole cache rows: head kk is lanes [kk*h, (kk+1)*h) of the block.
    kv_spec = pl.BlockSpec(
        (None, None, blk, K * h), lambda i, lens, layer, row, block: (layer[0], row[i], block[i], 0)
    )
    # Scales as lane-dense rows: layer (B, T, K) -> (B, K, 1, T), block
    # (K, 1, blk). Slice and transpose move 1/h of one layer's cache bytes.
    scale_spec = pl.BlockSpec(
        (None, K, 1, blk), lambda i, lens, layer, row, block: (row[i], 0, 0, block[i])
    )

    def scale_rows(stacked):
        one = jax.lax.dynamic_index_in_dim(stacked, layer[0], 0, keepdims=False)
        return one.transpose(0, 2, 1)[:, :, None, :]

    operands = [qt, k]
    in_specs = [q_spec, kv_spec]
    if k_scale is not None:
        operands.append(scale_rows(k_scale))
        in_specs.append(scale_spec)
    operands.append(v)
    in_specs.append(kv_spec)
    if v_scale is not None:
        operands.append(scale_rows(v_scale))
        in_specs.append(scale_spec)

    kernel = functools.partial(
        _kernel_with_optionals,
        has_ks=k_scale is not None,
        has_vs=v_scale is not None,
        scale=scale,
        blk=blk,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_steps,),  # the live blocks only: a dead one costs no step
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((sets, heads * gp, 1), jnp.float32),
                pltpu.VMEM((sets, heads * gp, 1), jnp.float32),
                pltpu.VMEM((sets, heads * gp, heads * h), jnp.float32),
                pltpu.VMEM((sets, heads * gp, heads * h), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, group, h), q.dtype),
        **tuned_call_kwargs("flash_decode", interpret, ("arbitrary",)),
    )(lengths, layer, row, block, *operands)
    return out.reshape(B, 1, H, h)


def _kernel_with_optionals(
    len_ref, layer_ref, row_ref, block_ref, q_ref, k_ref, *rest, has_ks, has_vs, **kw
):
    """Unpack the optional scale operands into the fixed-arity kernel."""
    rest = list(rest)
    ks_ref = rest.pop(0) if has_ks else None
    v_ref = rest.pop(0)
    vs_ref = rest.pop(0) if has_vs else None
    o_ref, m_s, l_s, acc_s, qd_s = rest
    _decode_kernel(
        len_ref, layer_ref, row_ref, block_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
        m_s, l_s, acc_s, qd_s, **kw,
    )


def maybe_flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    layer: jax.Array | int = 0,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array | None:
    """Dispatch entry: the kernel output when `decode_attn` is enabled and
    the shapes are supported, else ``None`` (caller slices layer ``layer``
    out of the stack and runs the exact reference lowering). ``k_scale`` /
    ``v_scale`` are the stacked scales of an int8 cache, whose dequant then
    fuses into the kernel."""
    mode = kernel_mode("decode_attn")
    if mode is None or not supported(
        q, k, compiled=mode == "compiled", quantized=k_scale is not None
    ):
        return None
    return flash_decode(
        q, k, v, lengths, layer, k_scale=k_scale, v_scale=v_scale, scale=scale,
        interpret=mode == "interpret",
    )
