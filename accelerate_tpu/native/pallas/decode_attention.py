"""Flash-decode attention over the slot KV cache.

Single query row per sequence (decode: T_new == 1) attending over the whole
cached prefix, split-K over the cache length with an online-softmax merge —
the FlashDecoding / PagedAttention-style kernel reduced to our static-shape
slot cache. Each (batch, kv-head) program walks the cache-length axis in
blocks, carrying running max / normalizer / accumulator in VMEM scratch, and
masks by the host-shipped length cursor so the padded slot tail never enters
the softmax.

The kernel reads the cache where the model keeps it: the whole layer-stacked
(L, B, T, K*h) buffer is the operand, the layer index rides scalar prefetch
beside the cursors, and the block index map picks (layer, row, block, head).
No layer is sliced out of the stack and nothing is reshaped on the way in,
so a decode step moves only the rows it attends to.

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

from .dispatch import kernel_mode, pallas_available, register_kernel

register_kernel(
    "decode_attn",
    "single-query flash-decode over the slot KV cache (bf16 + int8-dequant)",
)

if pallas_available():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ...ops.autotune import cached_pick_block, tuned_call_kwargs
    from ...ops.flash_attention import _NEG_INF

    def pick_block(dim, candidates=(512, 256, 128, 64, 32, 16, 8)):
        # Persisted autotune table first (ATX_BLOCK_DECODE_ATTENTION /
        # $ATX_AUTOTUNE_DIR), divide-exactly heuristic otherwise.
        return cached_pick_block("decode_attention", dim, candidates)
else:  # pragma: no cover - environment dependent
    pl = pltpu = None
    _NEG_INF = -1e30

    def pick_block(dim, candidates=(512, 256, 128, 64, 32, 16, 8)):
        return None


def _decode_kernel(
    len_ref,
    layer_ref,
    q_ref,
    k_ref,
    ks_ref,
    v_ref,
    vs_ref,
    o_ref,
    m_s,
    l_s,
    acc_s,
    *,
    scale: float,
    blk: int,
    n_blocks: int,
):
    """One (B, K) program; grid axis 2 walks the cache length (carried)."""
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    del layer_ref  # only the block index maps read it
    length = len_ref[pl.program_id(0)]

    # Blocks entirely past the cursor contribute nothing — skip the flops
    # (this is where short sequences in a long-max_len cache win).
    @pl.when(t * blk < length)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)  # (group, h)
        k = k_ref[...].astype(jnp.float32)  # (blk, h)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (group, blk)
        if ks_ref is not None:
            # Per-token dequant scale applied to the score column it belongs
            # to: (q . k_q) * s == q . (k_q * s), on (group, blk) not (blk, h).
            s = s * ks_ref[0, 0].astype(jnp.float32)  # (1, blk) row
        s = s * scale
        cols = t * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, _NEG_INF)

        m_prev = m_s[...]  # (group, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (group, blk)

        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = p
        if vs_ref is not None:
            pv = p * vs_ref[0, 0].astype(jnp.float32)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            pv,
            v_ref[...].astype(jnp.float32),  # (blk, h)
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[...] = m_new

    @pl.when(t == n_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


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
    blk = pick_block(T)
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
    blk = pick_block(T)
    if blk is None:
        raise ValueError(f"no block tile divides cache length {T}")
    n_blocks = T // blk
    scale = scale if scale is not None else float(1.0 / (h**0.5))

    qt = q.reshape(B, K, group, h)  # head = kk * group + g, the oracle's layout
    # The cursors and the layer index ride scalar prefetch (SMEM): the cursors
    # are read per program by batch row, the layer by every block index map.
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1), (B,))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    q_spec = pl.BlockSpec((1, 1, group, h), lambda b, kk, t, lens, layer: (b, kk, 0, 0))
    # Head kk is lane-block kk of the stack's last axis.
    kv_spec = pl.BlockSpec(
        (None, None, blk, h), lambda b, kk, t, lens, layer: (layer[0], b, t, kk)
    )
    # Scales as lane-dense rows: layer (B, T, K) -> (B, K, 1, T), block
    # (1, blk). Slice and transpose move 1/h of one layer's cache bytes.
    scale_spec = pl.BlockSpec((1, 1, 1, blk), lambda b, kk, t, lens, layer: (b, kk, 0, t))

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
        n_blocks=n_blocks,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K, n_blocks),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, h), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, group, h), q.dtype),
        **tuned_call_kwargs(
            "flash_decode", interpret, ("parallel", "parallel", "arbitrary")
        ),
    )(lengths, layer, *operands)
    return out.reshape(B, 1, H, h)


def _kernel_with_optionals(len_ref, layer_ref, q_ref, k_ref, *rest, has_ks, has_vs, **kw):
    """Unpack the optional scale operands into the fixed-arity kernel."""
    rest = list(rest)
    ks_ref = rest.pop(0) if has_ks else None
    v_ref = rest.pop(0)
    vs_ref = rest.pop(0) if has_vs else None
    o_ref, m_s, l_s, acc_s = rest
    _decode_kernel(
        len_ref, layer_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref, m_s, l_s, acc_s, **kw
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
