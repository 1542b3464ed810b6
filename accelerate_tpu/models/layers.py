"""Shared neural-net building blocks (pure functions over param pytrees).

The reference framework owns no model code — models come from `transformers`
and are rewritten by `Accelerator.prepare` (reference `accelerator.py:1421`).
A TPU-native framework must own its model family instead, because the sharding
plan, the scan-over-layers structure, and the attention kernels ARE the
performance story (SURVEY.md §7: MFU target requires fused attention + 2-D
sharding). These blocks follow the standard TPU recipe:

- params in fp32, compute in bf16 (cast at call boundaries);
- einsum-everything so XLA tiles straight onto the MXU;
- no python control flow on data — shapes static under jit.

Conventions: ``B`` batch, ``S`` sequence, ``D`` model dim, ``H`` heads,
``K`` kv-heads, ``h`` head dim, ``F`` ff dim, ``L`` layers.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.fp8 import matmul_einsum  # noqa: F401  (re-export: every projection routes through it)

Params = Any


def truncated_normal_init(rng: jax.Array, shape: tuple[int, ...], stddev: float, dtype=jnp.float32) -> jax.Array:
    # Scale in f32, then cast: a numpy-scalar stddev is not weakly typed, so
    # `bf16_array * np.float64` would silently promote the result to f32.
    sample = jax.random.truncated_normal(rng, -2.0, 2.0, shape, jnp.float32)
    return (sample * stddev).astype(dtype)


def remat_policy(name: str):
    """Resolve a remat-policy name to a `jax.checkpoint` policy (shared by
    every model family's ``remat_policy`` config knob)."""
    if name == "nothing":
        return None  # jax.checkpoint default: save nothing, recompute all
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "block_outputs":
        return jax.checkpoint_policies.save_only_these_names("attn_out", "ffn_out")
    if name == "attn_and_outputs":
        # Additionally keep the rotated q/k/v so the backward skips the qkv
        # projections + rope recompute. The flash forward kernel itself still
        # re-runs (its lse residual is internal to the custom_vjp and can't be
        # kept by a name policy), so this trades ~64MB/layer for only the qkv
        # recompute — measured neutral at bench scale; useful when qkv is a
        # larger fraction (big d_model, short S).
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "ffn_out", "q_rope", "k_rope", "v_proj"
        )
    raise ValueError(
        f"Unknown remat_policy {name!r}; expected 'nothing', 'dots', "
        "'block_outputs', or 'attn_and_outputs'"
    )


# --------------------------------------------------------------------- norms
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32 regardless of input dtype (normalization is
    numerically fragile in bf16; standard TPU practice)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-12) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ------------------------------------------------------------------ kv cache
def cache_positions(start: jax.Array, t_new: int, batch: int) -> jax.Array:
    """(B, T_new) logical positions for tokens appended at ``start``.

    ``start`` is the cache length cursor: a scalar (every row appends at the
    same offset — the plain decode contract) or shape (B,) (per-row offsets —
    speculative decoding commits a different number of tokens per row, so
    rows advance independently). Plain Python ints are accepted (caches
    built with host-side int lengths) and normalized here."""
    start = jnp.asarray(start, jnp.int32)
    offs = jnp.arange(t_new, dtype=jnp.int32)[None, :]
    pos = (start[:, None] if start.ndim == 1 else start) + offs
    return jnp.broadcast_to(pos, (batch, t_new))


def cache_write(buf: jax.Array, new: jax.Array, start: jax.Array) -> jax.Array:
    """Write ``new`` (B, T, ...) into ``buf`` (B, S, ...) at offset ``start``
    along the sequence dim.

    Scalar ``start`` keeps the one-``dynamic_update_slice`` decode fast path;
    a (B,) ``start`` vmaps the update over rows (per-row write offsets lower
    to one scatter — the enabling primitive for per-row speculative commit
    lengths). Plain Python int ``start`` is normalized to a jnp scalar."""
    start = jnp.asarray(start, jnp.int32)
    new = new.astype(buf.dtype)
    zeros = (0,) * (buf.ndim - 2)
    if start.ndim == 0:
        return jax.lax.dynamic_update_slice(buf, new, (0, start) + zeros)
    return jax.vmap(
        lambda b, n, s: jax.lax.dynamic_update_slice(b, n, (s,) + zeros)
    )(buf, new, start)


def _row_cursors(start: jax.Array, batch: int) -> jax.Array:
    """A scalar or (B,) cursor as (B,) int32."""
    return jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (batch,))


def ring_positions(start: jax.Array, ring_len: int, batch: int) -> jax.Array:
    """(B, W) the position each row of a ring buffer of ``ring_len`` rows
    holds when the cursor stands at ``start`` (scalar or (B,)): position p
    lives in row ``p mod W``, so row r holds the largest p < start with
    ``p mod W == r``; -1 where no such position has been written yet."""
    start = _row_cursors(start, batch)
    r = jnp.arange(ring_len, dtype=jnp.int32)
    last = start[:, None] - 1
    held = last - (last - r[None, :]) % ring_len
    return jnp.where(held >= 0, held, -1)


def _ring_write_stacked(
    all_buf: jax.Array, i: jax.Array, rows: jax.Array, start: jax.Array, valid: jax.Array | None
) -> jax.Array:
    """`cache_write_stacked` for several rows into a ring: row t of ``rows``
    is position ``start + t`` and lands in ring row ``(start + t) mod W``;
    only the first ``valid`` rows are real (scalar or (B,); all when None),
    and of those the last W survive. Written as a gather and a select over
    layer ``i``'s rows, not a scatter: a chunk that wraps is two runs of
    rows, and rows past ``valid`` (a bucket's pad tail) must not land at
    all, because in a ring they would land on rows still in the window."""
    B, T = rows.shape[:2]
    W = all_buf.shape[2]
    start = _row_cursors(start, B)
    n = _row_cursors(T if valid is None else valid, B)
    r = jnp.arange(W, dtype=jnp.int32)
    last = (start + n - 1)[:, None]
    # The newest real row that lands in ring row r; negative where none does.
    j = (n - 1)[:, None] - (last - r[None, :]) % W
    tail = (1,) * (rows.ndim - 2)
    new = jnp.take_along_axis(rows, jnp.clip(j, 0, T - 1).reshape((B, W) + tail), axis=1)
    old = jax.lax.dynamic_index_in_dim(all_buf, i, 0, keepdims=False)
    merged = jnp.where((j >= 0).reshape((B, W) + tail), new, old)
    return jax.lax.dynamic_update_index_in_dim(all_buf, merged, i, 0)


def cache_write_stacked(
    all_buf: jax.Array,
    i: jax.Array,
    rows: jax.Array,
    start: jax.Array,
    *,
    ring: bool = False,
    valid: jax.Array | None = None,
) -> jax.Array:
    """Write ``rows`` (B, T, ...) into layer ``i`` of a layer-stacked cache
    buffer (L, B, S, ...) at offset ``start`` and return the updated stack.
    Only the new rows move, whatever the cursor's rank: a scalar ``start`` is
    one ``dynamic_update_slice`` at ``(i, 0, start)``; a (B,) ``start`` (the
    engine's per-slot cursors, speculative decoding's per-row commits) is one
    scatter of B x T rows into ``[i, b, start_b + t]``. A scattered row that
    would land past the end of the buffer is dropped. Shared by every
    family's cache path, which carries the stack through its layer scan.

    ``ring`` makes the buffer a ring of S rows (a sliding-window layer keeps
    only its window): position p lives in row ``p mod S``. One row (a decode
    step) is the same write at ``start mod S``; several rows may wrap and
    carry ``valid`` (`_ring_write_stacked`)."""
    start = jnp.asarray(start, jnp.int32)
    rows = rows.astype(all_buf.dtype)
    if ring:
        if rows.shape[1] > 1:
            return _ring_write_stacked(all_buf, i, rows, start, valid)
        start = start % all_buf.shape[2]
    if start.ndim == 0:
        idx = (i, 0, start) + (0,) * (all_buf.ndim - 3)
        return jax.lax.dynamic_update_slice(all_buf, rows[None], idx)
    B, T = rows.shape[:2]
    pos = start[:, None] + jnp.arange(T, dtype=jnp.int32)
    return all_buf.at[i, jnp.arange(B)[:, None], pos].set(
        rows, mode="drop", unique_indices=True, indices_are_sorted=True
    )


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B, T, K, h) -> int8 values + per-(token, head) scales."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def dequant_kv(vals: jax.Array, scales: jax.Array, dtype) -> jax.Array:
    """Inverse of `quantize_kv` — the ONE place the dequant arithmetic lives
    outside the flash-decode kernel."""
    return vals.astype(dtype) * scales[..., None].astype(dtype)


def cache_append(
    kv: dict[str, jax.Array],
    i: jax.Array,
    k: jax.Array,
    v: jax.Array,
    start: jax.Array,
    *,
    ring: bool = False,
    valid: jax.Array | None = None,
) -> dict[str, jax.Array]:
    """Write one layer's new keys and values (B, T, K, h) into the stacked
    cache leaves ``kv`` (a family cache without its ``length`` cursor: ``k``
    / ``v`` of shape (L, B, S, K*h) and, for an int8 cache, ``k_scale`` /
    ``v_scale`` of shape (L, B, S, K)) at layer ``i``, offset ``start``.

    A family whose layers are of two kinds keeps one such set of leaves for
    each kind, ``i`` the layer's index within its kind; ``ring`` leaves hold
    only a window's rows (`cache_write_stacked`), and a chunk written into
    them says how many of its rows are real (``valid``)."""
    B, T = k.shape[:2]
    if "k_scale" in kv:
        k, k_scale = quantize_kv(k)
        v, v_scale = quantize_kv(v)
        new = {"k_scale": k_scale, "v_scale": v_scale}
    else:
        new = {}
    new["k"], new["v"] = k.reshape(B, T, -1), v.reshape(B, T, -1)
    return {
        name: cache_write_stacked(buf, i, new[name], start, ring=ring, valid=valid)
        for name, buf in kv.items()
    }


STATE_PREFIX = "state"


def is_state_leaf(name: str) -> bool:
    """Whether a family cache's leaf ``name`` is a recurrent state: laid out
    ``(L, B, ...)`` with no row axis, one value a slot whatever the cursor
    (a delta-rule state matrix, a convolution's last inputs). A family marks
    such a leaf by its name, ``state`` or ``state_<what>``; every other
    non-``length`` leaf is rows, ``(L, B, T, ...)``. Told apart by name, not
    by extent: a ``(L, B, 30, 96, 192)`` state is no ring of 30 rows."""
    return name == STATE_PREFIX or name.startswith(STATE_PREFIX + "_")


def cache_slot_view(kv: Any, slot: jax.Array) -> Any:
    """Slice one slot row (batch axis 1) out of every layer-stacked KV leaf.

    ``kv`` is a family cache dict WITHOUT its ``length`` cursor (leaves are
    (L, B, T, ...) layer-stacked buffers — k/v and, for int8 caches, their
    scales). ``slot`` is a traced int32 index, so one jitted caller serves
    every slot without recompiling. The result is a batch-1 cache view the
    family ``forward_with_cache`` runs on directly; pair with
    `cache_slot_write` to fold the updated row back. This is the primitive
    the serving engine's bucketed prefill rides: prefill computes on a
    single slot's row while the other slots' entries stay untouched."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1), kv
    )


def cache_slot_write(kv: Any, row: Any, slot: jax.Array) -> Any:
    """Write a batch-1 cache view (from `cache_slot_view`, after a forward
    updated it) back into slot ``slot`` of the full slot-batched cache."""
    return jax.tree.map(
        lambda a, r: jax.lax.dynamic_update_slice_in_dim(
            a, r.astype(a.dtype), slot, axis=1
        ),
        kv,
        row,
    )


def cache_slot_copy(
    dst: Any,
    src: Any,
    dst_slot: jax.Array,
    src_slot: jax.Array,
    start: jax.Array,
    length: int,
) -> Any:
    """Copy ``length`` committed KV positions from row ``src_slot`` of
    ``src`` into row ``dst_slot`` of ``dst`` at the same sequence offset
    ``start``, for every layer-stacked (L, B, T, ...) leaf of two family
    caches (``length`` cursors excluded, like `cache_slot_view`).

    The positions are preserved (source offset == destination offset)
    because committed KV has its rotary/positional encoding baked in — KV
    for token t at position p is only reusable AT position p. ``length`` is
    a static chunk size drawn from the serving engine's prefill bucket set
    while ``dst_slot``/``src_slot``/``start`` are traced int32, so one
    jitted caller compiles at most once per bucket whatever slots and
    cursors traffic produces — the primitive behind the prefix cache's
    device-to-device hit copies and promotions (serving/prefix_cache.py).
    ``dst`` and ``src`` may have different batch (row-pool) sizes."""
    dst_slot = jnp.asarray(dst_slot, jnp.int32)
    src_slot = jnp.asarray(src_slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)

    def one(d: jax.Array, s: jax.Array) -> jax.Array:
        tail = (0,) * (s.ndim - 3)
        seg = jax.lax.dynamic_slice(
            s, (0, src_slot, start) + tail, (s.shape[0], 1, length) + s.shape[3:]
        )
        return jax.lax.dynamic_update_slice(
            d, seg.astype(d.dtype), (0, dst_slot, start) + tail
        )

    return jax.tree.map(one, dst, src)


# ---------------------------------------------------------------------- rope
@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Rotary-frequency rescaling (HF ``rope_scaling``), hashable so configs
    carrying it stay valid jit static args / lru_cache keys.

    ``rope_type``:
      - ``"llama3"`` — Llama-3.1+ wavelength-banded rescale: low-frequency
        (long-wavelength) components are slowed by ``factor``, high-frequency
        ones kept, with a smooth ramp between the two bands (reference
        semantics: transformers ``modeling_rope_utils._compute_llama3_parameters``).
      - ``"linear"`` — position interpolation: every frequency divided by
        ``factor``.
    """

    rope_type: str
    factor: float
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192


def rope_frequencies(
    head_dim: int,
    max_len: int,
    theta: float = 10000.0,
    scaling: RopeScaling | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Precomputed cos/sin tables, shape (max_len, head_dim/2), fp32.

    Tables are built host-side in fp64 (they're tiny and computed once per
    trace), so the scaled frequencies match transformers' fp32 tables to
    rounding."""
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    if scaling is not None:
        if scaling.rope_type == "linear":
            inv_freq = inv_freq / scaling.factor
        elif scaling.rope_type == "llama3":
            old_len = scaling.original_max_position_embeddings
            low_wavelen = old_len / scaling.low_freq_factor
            high_wavelen = old_len / scaling.high_freq_factor
            wavelen = 2.0 * np.pi / inv_freq
            smooth = (old_len / wavelen - scaling.low_freq_factor) / (
                scaling.high_freq_factor - scaling.low_freq_factor
            )
            smoothed = ((1.0 - smooth) / scaling.factor + smooth) * inv_freq
            inv_freq = np.where(
                wavelen > low_wavelen,
                inv_freq / scaling.factor,
                np.where(wavelen < high_wavelen, inv_freq, smoothed),
            )
        else:
            raise ValueError(
                f"Unimplemented rope_type {scaling.rope_type!r}; supported: "
                "'llama3', 'linear'."
            )
    t = np.arange(max_len, dtype=np.float64)
    freqs = np.outer(t, inv_freq)
    return np.cos(freqs).astype(np.float32), np.sin(freqs).astype(np.float32)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array) -> jax.Array:
    """Rotary position embedding, rotate-half pairing (llama/GPT-NeoX:
    dimension i pairs with i + h/2). x: (B, S, H, h); positions: (B, S)."""
    dtype = x.dtype
    cos = cos[positions][:, :, None, :]  # (B, S, 1, h/2)
    sin = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def apply_rope_interleaved(
    x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array
) -> jax.Array:
    """Rotary position embedding, interleaved pairing (GPT-J
    ``rotate_every_two``: dimension 2i pairs with 2i+1). Same cos/sin tables
    as `apply_rope` — only the pairing differs, so checkpoints trained with
    one convention silently produce wrong logits under the other."""
    dtype = x.dtype
    cos = cos[positions][:, :, None, :]  # (B, S, 1, h/2)
    sin = sin[positions][:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(xf.shape).astype(dtype)


# ----------------------------------------------------------------- attention
def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mask: jax.Array | None = None,
    bias: jax.Array | None = None,
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Reference (non-fused) attention. q: (B, S, H, h), k/v: (B, T, K, h)
    with grouped-query broadcast when K < H. fp32 softmax. ``bias`` is an
    additive (H, S, T) logit bias (T5-style relative position bias).

    The fused path lives in `ops/flash_attention.py` (Pallas) and the
    sequence-parallel path in `ops/ring_attention.py`; this function is the
    numerical oracle both are tested against.
    """
    B, S, H, h = q.shape
    T, K = k.shape[1], k.shape[2]
    if K != H:
        if H % K != 0:
            raise ValueError(f"num_heads {H} not divisible by num_kv_heads {K}")
        group = H // K
        q = q.reshape(B, S, K, group, h)
        logits = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32)
    else:
        logits = jnp.einsum("bskh,btkh->bkst", q, k).astype(jnp.float32)
        logits = logits[:, :, None]  # group dim of 1
        group = 1
        q = q.reshape(B, S, K, group, h)
    scale = scale if scale is not None else 1.0 / np.sqrt(h)
    logits = logits * scale

    if bias is not None:
        # (H, S, T) -> (1, K, group, S, T) matching the logits layout
        logits = logits + bias.astype(jnp.float32).reshape(1, K, group, S, T)

    if causal:
        causal_mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        logits = jnp.where(causal_mask[None, None, None], logits, -1e30)
    if mask is not None:
        # mask: (B, T) padding mask or (B, S, T) full mask
        if mask.ndim == 2:
            mask = mask[:, None, :]
        logits = jnp.where(mask[:, None, None].astype(bool), logits, -1e30)

    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, H, h)


def position_masked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    *,
    window: int | None = None,
    q_block: int | None = None,
) -> jax.Array:
    """`dot_product_attention` of q (B, S, H, h) over k / v (B, T, K, h) whose
    visibility is given by positions, not by row order: key j is visible from
    query i iff ``0 <= k_pos[j] <= q_pos[i]`` and, with ``window``,
    ``q_pos[i] - k_pos[j] < window``. q_pos (B, S), k_pos (B, T) or (T,); a
    negative key position marks a row that holds nothing. This is what a
    prefill chunk runs against a cache: the rows of a full-length buffer are
    their own positions, those of a ring are `ring_positions`.

    ``q_block`` computes the queries in blocks of that many rows (when it
    divides S), so that the fp32 scores of a long chunk against a long cache
    are never whole in memory: 28 heads x 1024 queries x 16,384 keys are
    1.9 GB, a block of 256 queries a quarter of that."""
    B, S = q.shape[:2]
    k_pos = jnp.broadcast_to(k_pos, (B, k.shape[1]))

    def block(qb, pb):
        seen = (k_pos[:, None, :] <= pb[:, :, None]) & (k_pos[:, None, :] >= 0)
        if window is not None:
            seen = seen & (pb[:, :, None] - k_pos[:, None, :] < window)
        return dot_product_attention(qb, k, v, mask=seen)

    if q_block is None or S <= q_block or S % q_block:
        return block(q, q_pos)
    n = S // q_block
    out = jax.lax.map(
        lambda qp: block(*qp),
        (
            q.reshape(B, n, q_block, *q.shape[2:]).swapaxes(0, 1),
            q_pos.reshape(B, n, q_block).swapaxes(0, 1),
        ),
    )
    return out.swapaxes(0, 1).reshape(q.shape)


_ATTENTION_PATHS: contextvars.ContextVar[list[str] | None] = contextvars.ContextVar(
    "atx_cached_attention_paths", default=None
)
_LAYERS_A_TRACE: contextvars.ContextVar[int] = contextvars.ContextVar(
    "atx_layers_a_trace", default=1
)


@contextlib.contextmanager
def record_attention_paths():
    """Collect which lowering every layer's attention over a stacked cache
    took in the program traced inside the block, an entry a layer:
    ``"in_place"`` (a kernel reads the stacked cache where it lies:
    `flash_decode` for one query row, `flash_prefill` for a chunk) or
    ``"sliced"`` (one layer sliced out of the stack for
    `dot_product_attention`). Trace-time bookkeeping only: the serving
    engine wraps the traces of its decode and prefill programs in it, so a
    silent fall to the sliced lowering shows in
    ``Engine.stats['decode_in_place']`` / ``['prefill_attn_*']``."""
    paths: list[str] = []
    token = _ATTENTION_PATHS.set(paths)
    try:
        yield paths
    finally:
        _ATTENTION_PATHS.reset(token)


@contextlib.contextmanager
def traced_once_for(layers: int):
    """Round a `lax.scan` whose body, traced once, runs ``layers`` layers'
    attention for every call it makes: what is noted inside the block counts
    ``layers`` times. The scan's caller says so because only it knows; a
    loop that says nothing has its traced calls counted one each."""
    token = _LAYERS_A_TRACE.set(layers)
    try:
        yield
    finally:
        _LAYERS_A_TRACE.reset(token)


def note_attention_path(path: str) -> None:
    """Tell `record_attention_paths` that an attention over a layer-stacked
    cache took ``path``. `cached_attention` reports its own; a call site
    that slices a layer out by hand (a ring's chunk) reports here."""
    paths = _ATTENTION_PATHS.get()
    if paths is not None:
        paths.extend([path] * _LAYERS_A_TRACE.get())


_STEP_COUNTS: contextvars.ContextVar[dict[str, jax.Array] | None] = contextvars.ContextVar(
    "atx_step_counts", default=None
)


@contextlib.contextmanager
def record_step_counts():
    """Collect the exact counts a family's cached forward reports while it
    is traced inside the block (`report_step_counts`): name -> int32 scalar
    of the trace, summed over the reports. The serving engine wraps its
    decode program's trace in it and returns what was collected beside the
    step's tokens, so the counts ride the one fetch a step already makes."""
    counts: dict[str, jax.Array] = {}
    token = _STEP_COUNTS.set(counts)
    try:
        yield counts
    finally:
        _STEP_COUNTS.reset(token)


def report_step_counts(counts: dict[str, jax.Array]) -> None:
    """Hand `record_step_counts` what this forward counted (e.g. the expert
    layer's `ops.moe.MOE_COUNTS`, summed over its layers). Values must belong
    to the trace that is recording: report after the layer scan, from its
    carry. A no-op when nothing records."""
    into = _STEP_COUNTS.get()
    if into is not None:
        for name, value in counts.items():
            into[name] = into[name] + value if name in into else value


_HEAD_ROWS: contextvars.ContextVar[list[int] | None] = contextvars.ContextVar(
    "atx_head_rows", default=None
)


@contextlib.contextmanager
def record_head_rows():
    """Collect, an entry a call, the rows `head_logits` hands the vocabulary
    head when the cache names the row it wants (``cache['logits_at']``); a
    call without the key projects every row and notes nothing. Trace-time
    bookkeeping only: the serving engine wraps its prefill programs' traces
    in it for ``Engine.stats['prefill_head_rows']``."""
    rows: list[int] = []
    token = _HEAD_ROWS.set(rows)
    try:
        yield rows
    finally:
        _HEAD_ROWS.reset(token)


def head_logits(x: jax.Array, cache: dict[str, jax.Array], head) -> jax.Array:
    """Logits of the last layer's hidden states ``x`` (B, S, D) through
    ``head``, the family's final norm and vocabulary projection.

    Without ``cache['logits_at']``, every row's: (B, S, vocab). With it, a
    (B,) int32 row index into the chunk, only that row's, gathered before
    the norm: (B, 1, vocab). A negative index wants no row (its logits are
    zeros), and where no row of the batch wants one the head does not run:
    `jax.lax.cond` returns zeros of the same shape and dtype, in the same
    program. The serving engine's prefill chunks set the key, since only a
    prompt's last chunk is sampled, and at one row."""
    at = cache.get("logits_at")
    if at is None:
        return head(x)
    wanted = (at >= 0)[:, None, None]
    rows = x[jnp.arange(x.shape[0]), jnp.maximum(at, 0)][:, None]
    noted = _HEAD_ROWS.get()
    if noted is not None:
        noted.append(x.shape[0])
    out = jax.eval_shape(head, rows)
    return jax.lax.cond(
        jnp.any(wanted),
        lambda r: jnp.where(wanted, head(r), 0),
        lambda r: jnp.zeros(out.shape, out.dtype),
        rows,
    )


def cached_attention(
    q: jax.Array,
    kv: dict[str, jax.Array],
    i: jax.Array,
    *,
    mask: jax.Array | None = None,
    lengths: jax.Array | None = None,
    window: int | None = None,
    start: jax.Array | None = None,
    q_block: int | None = None,
) -> jax.Array:
    """Attention of ``q`` (B, T_new, H, h) over layer ``i`` of the stacked
    cache leaves ``kv`` (see `cache_append`), the new rows already written.

    One cache layout, one entry for every family, and the lowering chosen by
    what the shapes show. With the kernel's name enabled
    (`native/pallas/dispatch.py`) and its ``supported()`` saying yes, a
    kernel reads the stack in place, handed the whole buffers and the layer
    index:

    - a decode step (one query token, cursor-masked by ``lengths``, no
      sliding window): `flash_decode`, int8 dequant included;
    - a prefill chunk (more than one query row, written at the cursor
      ``start`` of a full-length leaf, no sliding window): `flash_prefill`,
      which visits the rows up to the cursor and no further.

    Everything else (an int8 cache's chunk, a window, a few speculative
    rows, a length no block divides, kernels off, the CPU) slices layer
    ``i`` out of the stack and runs the reference `dot_product_attention`
    with the full cache ``mask``; a chunk that hands no ``mask`` states its
    visibility by ``start`` alone (key row ``j`` is seen from the query at
    ``start + r`` iff ``j <= start + r``), computed ``q_block`` queries at a
    time (`position_masked_attention`).

    A ring of W rows (a window layer's leaves, `cache_write_stacked`) is
    read the same way after the step's row is written: every row it holds is
    inside the window, the order of rows inside a softmax does not matter
    (rotary is applied before the write), so ``lengths`` is
    ``min(cursor + 1, W)``, ``mask`` its (B, 1, W) counterpart, ``i`` the
    layer's index among the window layers, and ``window`` stays None. A
    ring's chunk is not this function's: its rows are not in position
    order."""
    out = None
    if lengths is not None and window is None:
        from ..native.pallas.decode_attention import maybe_flash_decode

        out = maybe_flash_decode(
            q, kv["k"], kv["v"], lengths, i,
            k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"),
        )
    elif lengths is None and start is not None:
        from ..native.pallas.prefill_attention import maybe_flash_prefill

        out = maybe_flash_prefill(
            q, kv["k"], kv["v"], start, i, quantized="k_scale" in kv, window=window
        )
    note_attention_path("sliced" if out is None else "in_place")
    if out is not None:
        return out
    layer = {
        name: jax.lax.dynamic_index_in_dim(buf, i, 0, keepdims=False)
        for name, buf in kv.items()
    }
    heads = layer["k"].shape[:2] + (-1, q.shape[-1])  # (B, S, K*h) -> (B, S, K, h)
    k, v = layer["k"].reshape(heads), layer["v"].reshape(heads)
    if "k_scale" in layer:
        # Dequant stays elementwise on the sliced layer: HBM reads int8.
        k = dequant_kv(k, layer["k_scale"], q.dtype)
        v = dequant_kv(v, layer["v_scale"], q.dtype)
    k, v = k.astype(q.dtype), v.astype(q.dtype)
    if mask is None:
        return position_masked_attention(
            q, k, v, cache_positions(start, q.shape[1], q.shape[0]),
            jnp.arange(k.shape[1], dtype=jnp.int32), q_block=q_block,
        )
    return dot_product_attention(q, k, v, mask=mask)


# ------------------------------------------------------------------ attention block
@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


def init_attention(
    rng: jax.Array, spec: AttentionSpec, dtype=jnp.float32, *, bias: bool = False
) -> Params:
    """``bias=True`` adds per-head q/k/v biases and an output bias (BERT /
    GPT-2 / ViT convention; llama-family attention is bias-free)."""
    kq, kk, kv, ko = jax.random.split(rng, 4)
    std = 1.0 / np.sqrt(spec.d_model)
    params = {
        "wq": truncated_normal_init(kq, (spec.d_model, spec.num_heads, spec.head_dim), std, dtype),
        "wk": truncated_normal_init(kk, (spec.d_model, spec.num_kv_heads, spec.head_dim), std, dtype),
        "wv": truncated_normal_init(kv, (spec.d_model, spec.num_kv_heads, spec.head_dim), std, dtype),
        "wo": truncated_normal_init(ko, (spec.num_heads, spec.head_dim, spec.d_model), std, dtype),
    }
    if bias:
        params["bq"] = jnp.zeros((spec.num_heads, spec.head_dim), dtype)
        params["bk"] = jnp.zeros((spec.num_kv_heads, spec.head_dim), dtype)
        params["bv"] = jnp.zeros((spec.num_kv_heads, spec.head_dim), dtype)
        params["bo"] = jnp.zeros((spec.d_model,), dtype)
    return params


def attention_qkv(params: Params, x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    q = matmul_einsum("bsd,dhk->bshk", x, params["wq"])
    k = matmul_einsum("bsd,dhk->bshk", x, params["wk"])
    v = matmul_einsum("bsd,dhk->bshk", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    return q, k, v


def attention_out(params: Params, attn: jax.Array) -> jax.Array:
    out = matmul_einsum("bshk,hkd->bsd", attn, params["wo"])
    if "bo" in params:
        out = out + params["bo"].astype(out.dtype)
    return out


# ------------------------------------------------------------------------ mlp
def init_swiglu(rng: jax.Array, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    kg, ku, kd = jax.random.split(rng, 3)
    std_in = 1.0 / np.sqrt(d_model)
    std_out = 1.0 / np.sqrt(d_ff)
    return {
        "w_gate": truncated_normal_init(kg, (d_model, d_ff), std_in, dtype),
        "w_up": truncated_normal_init(ku, (d_model, d_ff), std_in, dtype),
        "w_down": truncated_normal_init(kd, (d_ff, d_model), std_out, dtype),
    }


def gated_mlp(params: Params, x: jax.Array, activation=jax.nn.silu) -> jax.Array:
    """Gated MLP over {w_gate, w_up, w_down}: swiglu with silu (llama),
    gated-gelu with gelu (T5 v1.1)."""
    gate = matmul_einsum("bsd,df->bsf", x, params["w_gate"])
    up = matmul_einsum("bsd,df->bsf", x, params["w_up"])
    hidden = activation(gate) * up
    return matmul_einsum("bsf,fd->bsd", hidden, params["w_down"])


def swiglu(params: Params, x: jax.Array) -> jax.Array:
    return gated_mlp(params, x, jax.nn.silu)


def init_mlp_gelu(rng: jax.Array, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ki, ko = jax.random.split(rng)
    return {
        "w_in": truncated_normal_init(ki, (d_model, d_ff), 1.0 / np.sqrt(d_model), dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": truncated_normal_init(ko, (d_ff, d_model), 1.0 / np.sqrt(d_ff), dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def activation_fn(name: str):
    """HF ``ACT2FN`` names -> jax callables for the variants the zoo's
    checkpoints actually ship. ``gelu_fast`` is ``gelu_new`` with the tanh
    argument factored differently — algebraically identical."""
    try:
        return {
            "gelu_new": partial(jax.nn.gelu, approximate=True),
            "gelu_fast": partial(jax.nn.gelu, approximate=True),
            "gelu": partial(jax.nn.gelu, approximate=False),
            "relu": jax.nn.relu,
            "silu": jax.nn.silu,
        }[name]
    except KeyError:
        raise ValueError(
            f"Unimplemented activation {name!r}; implemented: gelu_new, "
            "gelu_fast, gelu, relu, silu."
        ) from None


def mlp_gelu(
    params: Params, x: jax.Array, *, approximate: bool = True, act=None
) -> jax.Array:
    """``approximate=True`` is GPT-2's tanh "gelu_new"; BERT/ViT use the
    exact erf gelu (transformers ``ACT2FN["gelu"]``) — the two differ by up
    to ~3e-3 at real activation scales, so the variant must match the
    checkpoint's or logit parity quietly breaks. ``act`` (a callable)
    overrides entirely (OPT's relu MLP rides the same param layout)."""
    h = matmul_einsum("bsd,df->bsf", x, params["w_in"]) + params["b_in"].astype(x.dtype)
    h = act(h) if act is not None else jax.nn.gelu(h, approximate=approximate)
    return matmul_einsum("bsf,fd->bsd", h, params["w_out"]) + params["b_out"].astype(x.dtype)


# ----------------------------------------------------------------------- loss
def chunked_lm_loss(
    x: jax.Array,
    head: jax.Array,
    labels: jax.Array,
    *,
    mask: jax.Array | None = None,
    z_loss: float = 0.0,
    chunk_size: int = 512,
) -> jax.Array:
    """Next-token cross entropy WITHOUT materializing the full (B, S, V)
    logits: the sequence is scanned in chunks, each chunk's
    projection+softmax is `jax.checkpoint`ed so the backward recomputes it
    chunk-by-chunk. At (8, 2048, 32k) the fp32 logit tail is ~2 GB of
    residuals; chunking caps it at chunk_size/S of that. Numerically
    identical (fp32 reductions, same masking/z-loss) to
    ``cross_entropy_loss(einsum(x, head), labels, ...)``.

    x: (B, S, D) trunk output aligned with labels (B, S); S must be a
    multiple of ``chunk_size`` (pick a divisor — S is static under jit).
    """
    B, S, D = x.shape
    if S % chunk_size != 0:
        raise ValueError(f"chunk_size {chunk_size} must divide sequence length {S}")
    n_chunks = S // chunk_size
    xc = x.reshape(B, n_chunks, chunk_size, D).swapaxes(0, 1)
    lc = labels.reshape(B, n_chunks, chunk_size).swapaxes(0, 1)
    if mask is None:
        mc = jnp.ones((n_chunks, B, chunk_size), jnp.float32)
    else:
        mc = mask.reshape(B, n_chunks, chunk_size).swapaxes(0, 1).astype(jnp.float32)

    @jax.checkpoint
    def chunk_sums(x_chunk, label_chunk, mask_chunk):
        logits = jnp.einsum("bsd,dv->bsv", x_chunk, head.astype(x_chunk.dtype))
        logits = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        label_logits = jnp.take_along_axis(logits, label_chunk[..., None], axis=-1)[..., 0]
        losses = logz - label_logits
        if z_loss > 0.0:
            losses = losses + z_loss * jnp.square(logz)
        return jnp.sum(losses * mask_chunk), jnp.sum(mask_chunk)

    def scan_body(carry, inputs):
        loss_sum, count = carry
        s, c = chunk_sums(*inputs)
        return (loss_sum + s, count + c), None

    (loss_sum, count), _ = jax.lax.scan(
        scan_body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (xc, lc, mc)
    )
    return loss_sum / jnp.maximum(count, 1.0)


def shifted_labels_and_mask(
    tokens: jax.Array, attn_mask: jax.Array | None
) -> tuple[jax.Array, jax.Array]:
    """Next-token labels/mask at FULL sequence length for the chunked loss:
    position i predicts token i+1; the final position is masked out instead
    of sliced off (chunking needs chunk_size | S)."""
    B, S = tokens.shape
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    loss_mask = jnp.ones((B, S), jnp.float32).at[:, -1].set(0.0)
    if attn_mask is not None:
        shifted = jnp.concatenate(
            [attn_mask[:, 1:], jnp.zeros((B, 1), attn_mask.dtype)], axis=1
        )
        loss_mask = loss_mask * shifted.astype(jnp.float32)
    return labels, loss_mask


def chunked_lm_loss_from_batch(
    x: jax.Array,
    head: jax.Array,
    tokens: jax.Array,
    labels: jax.Array | None,
    attn_mask: jax.Array | None,
    *,
    z_loss: float,
    chunk_size: int,
) -> jax.Array:
    """The shared chunked-loss entry for decoder families: resolves the
    shifted-labels default, then runs `chunked_lm_loss`."""
    if labels is None:
        labels, loss_mask = shifted_labels_and_mask(tokens, attn_mask)
    else:
        loss_mask = attn_mask
    return chunked_lm_loss(
        x, head, labels, mask=loss_mask, z_loss=z_loss, chunk_size=chunk_size
    )


def cross_entropy_loss(
    logits: jax.Array,
    labels: jax.Array,
    *,
    mask: jax.Array | None = None,
    z_loss: float = 0.0,
) -> jax.Array:
    """Token-level cross entropy in fp32 with optional z-loss regularizer
    (keeps the softmax normalizer bounded — stabilizes long bf16 runs)."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    losses = logz - label_logits
    if z_loss > 0.0:
        losses = losses + z_loss * jnp.square(logz)
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(losses)
