"""Fused flash attention (Pallas, TPU).

The reference has no attention kernels at all — fused attention arrives via
torch SDPA / Megatron CUDA kernels (SURVEY.md §2.2: "fused softmax" listed as
a native dependency to replace). Here it is a first-class TPU kernel:

- forward: online-softmax with BOTH Q and KV blocked through the grid —
  VMEM use is O(block²), independent of sequence length, so the kernel
  compiles at the long-context lengths flash attention exists for. The
  softmax running state (m, l, acc) lives in VMEM scratch carried across
  the innermost (KV) grid axis;
- backward: custom VJP with two Pallas kernels (dq accumulated over KV
  blocks, dk/dv accumulated over Q blocks), same blocked-grid structure,
  using the saved logsumexp + delta trick;
- GQA: query heads map onto kv heads via index maps (no kv replication in
  HBM); backward folds group gradients outside the kernel;
- causal masking by block skipping (upper-triangle blocks are visited but
  skipped with `pl.when` — no FLOPs, no VMEM traffic beyond the prefetch).

Layouts follow the framework convention (B, S, H, h); kernels run in
(B, H, S, h). Falls back to the XLA reference implementation
(`models/layers.py:dot_product_attention`) for shapes the kernel does not
support (tiny S, explicit padding masks) so callers can use one entry point.
Runs in interpreter mode automatically on CPU (tests/CI).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# 512 empirically: ~3-7x faster than 128 on v5e at S=2048 (loop/semaphore
# overhead amortizes; the (512, 512) f32 s-matrix stays well under VMEM).
DEFAULT_BLOCK = 512
# Staged-K+V byte budget for the resident-KV kernels: below this the whole
# KV sequence stays in VMEM per (B, H) program (fastest — no KV re-fetch per
# Q block, measured ~8% whole-model MFU at S=2048); above it the blocked
# kernels keep VMEM O(block^2) so arbitrarily long sequences compile.
_RESIDENT_KV_BUDGET = 4 * 1024 * 1024


def _use_resident(S: int, h: int, dtype) -> bool:
    # The blocked-KV path with its adaptive 1024 block measured 1.5-1.6x
    # FASTER than the resident kernels from S=4096 up on v5e (equal-token
    # sweeps: 31 vs 50 ms at 4k, 41 vs 61 ms at 8k, fwd+bwd); resident
    # still wins at S=2048 (27 vs 33 ms). Keep resident below the
    # crossover, and only while the staged KV fits its VMEM budget.
    return S < 4096 and 2 * S * h * jnp.dtype(dtype).itemsize <= _RESIDENT_KV_BUDGET


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _call_kwargs(name, interpret):
    """(B, H, Q-blocks) parallel, KV-blocks sequential (the scratch carry)."""
    return tuned_call_kwargs(
        name, interpret, ("parallel", "parallel", "parallel", "arbitrary")
    )


def _block_live(q_start, block_q, k_start, *, causal, valid, window=None, block_k=None):
    """Should this (Q-block, KV-block) tile be computed at all?"""
    live = (q_start + block_q - 1 >= k_start) if causal else (k_start < valid)
    if window is not None:
        # Sliding window: key c visible from row r iff r - c < window. The
        # tile is dead when even its newest key is out of every row's band.
        bk = block_k if block_k is not None else block_q
        live = jnp.logical_and(live, q_start - (k_start + bk - 1) < window)
    return live


def _mask_scores(s, q_start, k_start, *, causal, valid, window=None):
    """Apply causal / window / padded-column masking to a (bq, bk) tile."""
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if causal:
        keep = rows >= cols
        if window is not None:
            keep = jnp.logical_and(keep, rows - cols < window)
        return jnp.where(keep, s, _NEG_INF)
    keep = cols < valid
    if window is not None:
        keep = jnp.logical_and(keep, rows - cols < window)
    return jnp.where(keep, s, _NEG_INF)


# ---------------------------------------------------- resident-KV kernels
# Original single-pass kernels: K/V for the whole sequence stay staged in
# VMEM while one Q block loops over them — fastest when they fit (short/
# medium S), used below _RESIDENT_KV_BUDGET bytes of staged KV.
def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block, causal, seq_len, valid, window=None):
    qi = pl.program_id(2)
    # Keep matmul operands in their native (bf16) dtype: the MXU runs bf16 x
    # bf16 -> f32 at full rate, while f32 x f32 passes take a multiple of the
    # time. Accumulation stays f32 via preferred_element_type.
    q = q_ref[0, 0]  # (bq, h)
    bq = q.shape[0]
    head_dim = q.shape[1]
    q_start = qi * bq
    n_blocks = seq_len // block
    # Causal: KV blocks strictly above the diagonal contribute nothing.
    hi = jnp.minimum((q_start + bq + block - 1) // block, n_blocks) if causal else n_blocks
    # Sliding window: KV blocks entirely below the band contribute nothing
    # either — the loop starts at the window's oldest live block.
    lo = jnp.maximum((q_start - (window - 1)) // block, 0) if window is not None else 0

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * block, block), :]  # (bk, h)
        v = v_ref[0, 0, pl.ds(j * block, block), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk) f32
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = rows >= cols
            if window is not None:
                keep = jnp.logical_and(keep, rows - cols < window)
            s = jnp.where(keep, s, _NEG_INF)
        elif valid < seq_len or window is not None:
            cols = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = cols < valid
            if window is not None:
                rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                keep = jnp.logical_and(keep, rows - cols < window)
            s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p is cast to the kv dtype for the MXU (standard flash practice;
        # p in [0,1] so bf16's relative precision is adequate).
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l, acc

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, head_dim), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l_safe)).astype(jnp.float32)  # (bq, 1)



def _fwd_resident(q, k, v, *, scale, block, causal, interpret, valid, window=None):
    B, H, S, h = q.shape
    K = k.shape[1]
    group = H // K
    grid = (B, H, S // block)
    kernel = functools.partial(
        _fwd_kernel_resident, scale=scale, block=block, causal=causal,
        seq_len=S, valid=valid, window=window,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block, h), lambda b, hh, qi: (b, hh, qi, 0)),
            pl.BlockSpec((1, 1, S, h), lambda b, hh, qi: (b, hh // group, 0, 0)),
            pl.BlockSpec((1, 1, S, h), lambda b, hh, qi: (b, hh // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block, h), lambda b, hh, qi: (b, hh, qi, 0)),
            pl.BlockSpec((1, 1, block, 1), lambda b, hh, qi: (b, hh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, h), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        **tuned_call_kwargs("flash_fwd_resident", interpret),
    )(q, k, v)
    return o, lse



def _dq_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, block, causal, seq_len, valid, window=None):
    qi = pl.program_id(2)
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]  # (bq, 1)
    delta = delta_ref[0, 0]
    bq, head_dim = q.shape
    q_start = qi * bq
    n_blocks = seq_len // block
    hi = jnp.minimum((q_start + bq + block - 1) // block, n_blocks) if causal else n_blocks
    lo = jnp.maximum((q_start - (window - 1)) // block, 0) if window is not None else 0

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(j * block, block), :]
        v = v_ref[0, 0, pl.ds(j * block, block), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal or valid < seq_len or window is not None:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = (rows >= cols) if causal else (cols < valid)
            if window is not None:
                keep = jnp.logical_and(keep, rows - cols < window)
            s = jnp.where(keep, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta)).astype(k.dtype)
        return dq + scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(lo, hi, body, jnp.zeros((bq, head_dim), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)



def _dkv_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, block, causal, seq_len, valid, window=None):
    j = pl.program_id(2)
    k = k_ref[0, 0]  # (bk, h)
    v = v_ref[0, 0]
    bk, head_dim = k.shape
    k_start = j * bk
    n_blocks = seq_len // block
    lo = (k_start // block) if causal else 0
    # Window: q rows past k_start+bk-1+window-1 see none of this k block.
    hi = (
        jnp.minimum((k_start + bk - 1 + window) // block + 1, n_blocks)
        if window is not None
        else n_blocks
    )

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * block, block), :]
        do = do_ref[0, 0, pl.ds(i * block, block), :]
        lse = lse_ref[0, 0, pl.ds(i * block, block), :]  # (bq, 1)
        delta = delta_ref[0, 0, pl.ds(i * block, block), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal or valid < seq_len or window is not None:
            rows = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = (rows >= cols) if causal else (cols < valid)
            if window is not None:
                keep = jnp.logical_and(keep, rows - cols < window)
            s = jnp.where(keep, s, _NEG_INF)
        p = jnp.exp(s - lse)  # (bq, bk) f32
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta)).astype(q.dtype)
        dk = dk + scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk, dv

    init = (
        jnp.zeros((bk, head_dim), jnp.float32),
        jnp.zeros((bk, head_dim), jnp.float32),
    )
    dk, dv = jax.lax.fori_loop(lo, hi, body, init)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)



def _bwd_resident(scale, block, causal, interpret, valid, residuals, g, window=None):
    q, k, v, o, lse = residuals
    B, H, S, h = q.shape
    K = k.shape[1]
    group = H // K
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)  # (B,H,S,1)

    grid = (B, H, S // block)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel_resident, scale=scale, block=block, causal=causal, seq_len=S, valid=valid, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block, h), lambda b, hh, qi: (b, hh, qi, 0)),
            pl.BlockSpec((1, 1, S, h), lambda b, hh, qi: (b, hh // group, 0, 0)),
            pl.BlockSpec((1, 1, S, h), lambda b, hh, qi: (b, hh // group, 0, 0)),
            pl.BlockSpec((1, 1, block, h), lambda b, hh, qi: (b, hh, qi, 0)),
            pl.BlockSpec((1, 1, block, 1), lambda b, hh, qi: (b, hh, qi, 0)),
            pl.BlockSpec((1, 1, block, 1), lambda b, hh, qi: (b, hh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block, h), lambda b, hh, qi: (b, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, h), q.dtype),
        **tuned_call_kwargs("flash_bwd_dq_resident", interpret),
    )(q, k, v, do, lse, delta)

    grid_kv = (B, H, S // block)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel_resident, scale=scale, block=block, causal=causal, seq_len=S, valid=valid, window=window),
        grid=grid_kv,
        in_specs=[
            pl.BlockSpec((1, 1, S, h), lambda b, hh, j: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, block, h), lambda b, hh, j: (b, hh // group, j, 0)),
            pl.BlockSpec((1, 1, block, h), lambda b, hh, j: (b, hh // group, j, 0)),
            pl.BlockSpec((1, 1, S, h), lambda b, hh, j: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, S, 1), lambda b, hh, j: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, S, 1), lambda b, hh, j: (b, hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block, h), lambda b, hh, j: (b, hh, j, 0)),
            pl.BlockSpec((1, 1, block, h), lambda b, hh, j: (b, hh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, h), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, h), q.dtype),
        ],
        **tuned_call_kwargs("flash_bwd_dkv_resident", interpret),
    )(q, k, v, do, lse, delta)

    if group > 1:
        # Fold query-head-group gradients onto the shared kv heads.
        dk = dk_h.reshape(B, K, group, S, h).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(B, K, group, S, h).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h.astype(k.dtype), dv_h.astype(v.dtype)
    return dq, dk, dv




# ------------------------------------------------------------------- forward
def _banded_grid(nq: int, block: int, causal: bool, window, group: int, clamp_hi: int | None = None):
    """Shared banded-KV/Q-grid setup for the windowed kernels: (n_eff,
    window_grid, index_map). `clamp_hi` picks the clamp edge — None for the
    fwd/dq KV axis (clamped at 0, offset qi - (n_eff-1) + i), or nq-1 for
    the dkv Q axis (offset ki + i). All three kernels reconstruct
    k_start/q_start from the SAME n_eff, so this must stay the single
    source of the band width."""
    if window is not None and causal:
        n_eff = min(nq, (window + block - 1) // block + 1)
        window_grid = n_eff < nq
    else:
        n_eff, window_grid = nq, False

    if clamp_hi is None:
        def index_map(b, hh, qi, ki):
            if window_grid:
                return (b, hh // group, jnp.maximum(qi - (n_eff - 1) + ki, 0), 0)
            return (b, hh // group, ki, 0)
    else:
        def index_map(b, hh, ki, qi):
            if window_grid:
                return (b, hh // group, jnp.minimum(ki + qi, clamp_hi), 0)
            return (b, hh // group, qi, 0)

    return n_eff, window_grid, index_map


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, scale, block_q, block_k, causal, valid, window=None, window_grid=False,
):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    q_start = qi * block_q
    if window_grid:
        # Banded grid: the KV-block axis only spans the window's live
        # diagonal band — ki indexes positions [qi - (nk-1), qi]. k_start
        # may be negative at the left edge; those tiles mask to nothing
        # (their fetch is clamped to block 0 by the index map).
        k_start = (qi - (nk - 1) + ki) * block_k
    else:
        k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal: blocks entirely above the diagonal contribute nothing;
    # a sliding window additionally kills blocks below the band.
    run = _block_live(
        q_start, block_q, k_start,
        causal=causal, valid=valid, window=window, block_k=block_k,
    )
    if window_grid:
        # Left-edge band positions before the sequence start do not exist;
        # without this the clamped fetch would re-read block 0 under a
        # shifted (wrong) mask and double-count its keys.
        run = jnp.logical_and(run, k_start >= 0)

    @pl.when(run)
    def _block():
        # Keep matmul operands in their native (bf16) dtype: the MXU runs
        # bf16 x bf16 -> f32 at full rate; accumulation stays f32 via
        # preferred_element_type.
        q = q_ref[0, 0]  # (bq, h)
        k = k_ref[0, 0]  # (bk, h)
        v = v_ref[0, 0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk) f32
        s = _mask_scores(
            s, q_start, k_start, causal=causal, valid=valid, window=window
        )
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p cast to the kv dtype for the MXU (standard flash practice; p in
        # [0,1] so bf16 relative precision is adequate).
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l_safe)).astype(jnp.float32)


def _fwd(q, k, v, *, scale, block, causal, interpret, valid, window=None):
    B, H, S, h = q.shape
    if _use_resident(S, h, k.dtype):
        return _fwd_resident(
            q, k, v, scale=scale, block=block, causal=causal,
            interpret=interpret, valid=valid, window=window,
        )
    K = k.shape[1]
    group = H // K
    nq = S // block
    # With a sliding window, the KV-grid axis spans only the live band —
    # dead tiles are never fetched or visited, so work (and DMA) scales
    # with O(S * window) instead of O(S^2).
    n_eff, window_grid, kv_index = _banded_grid(nq, block, causal, window, group)
    grid = (B, H, nq, n_eff)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block, block_k=block, causal=causal,
        valid=valid, window=window, window_grid=window_grid,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block, h), lambda b, hh, qi, ki: (b, hh, qi, 0)),
            pl.BlockSpec((1, 1, block, h), kv_index),
            pl.BlockSpec((1, 1, block, h), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block, h), lambda b, hh, qi, ki: (b, hh, qi, 0)),
            pl.BlockSpec((1, 1, block, 1), lambda b, hh, qi, ki: (b, hh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, h), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),   # m
            pltpu.VMEM((block, 1), jnp.float32),   # l
            pltpu.VMEM((block, h), jnp.float32),   # acc
        ],
        **_call_kwargs("flash_fwd", interpret),
    )(q, k, v)
    return o, lse


# ------------------------------------------------------------------ backward
def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
    *, scale, block_q, block_k, causal, valid, window=None, window_grid=False,
):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    q_start = qi * block_q
    if window_grid:
        k_start = (qi - (nk - 1) + ki) * block_k
    else:
        k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    run = _block_live(
        q_start, block_q, k_start,
        causal=causal, valid=valid, window=window, block_k=block_k,
    )
    if window_grid:
        run = jnp.logical_and(run, k_start >= 0)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = _mask_scores(
            s, q_start, k_start, causal=causal, valid=valid, window=window
        )
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_acc_ref[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, scale, block_q, block_k, causal, valid, window=None, window_grid=False,
    n_q_blocks=None,
):
    # Grid: (B, H, KV-blocks, Q-blocks) — Q is the innermost carried axis.
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    k_start = ki * block_k
    if window_grid:
        # Banded: causal+window means only q blocks [ki, ki + nq) touch
        # this k block; right-edge tiles past the sequence are dead (their
        # fetch is clamped to the last block by the index map).
        q_start = (ki + qi) * block_q
    else:
        q_start = qi * block_q

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    run = _block_live(
        q_start, block_q, k_start,
        causal=causal, valid=valid, window=window, block_k=block_k,
    )
    if window_grid:
        run = jnp.logical_and(run, ki + qi < n_q_blocks)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = _mask_scores(
            s, q_start, k_start, causal=causal, valid=valid, window=window
        )
        p = jnp.exp(s - lse)  # (bq, bk) f32
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc_ref[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def dq_call(q, k, v, do, lse, delta, *, scale, block, causal, interpret, valid, window=None):
    """dq for one (q, kv) pair via the blocked kernel. Shapes (B, H, S, h);
    exposed for ring attention's per-chunk backward."""
    B, H, S, h = q.shape
    group = H // k.shape[1]
    nq = S // block
    n_eff, window_grid, kv_index = _banded_grid(nq, block, causal, window, group)
    grid = (B, H, nq, n_eff)
    return pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, block_q=block, block_k=block, causal=causal,
            valid=valid, window=window, window_grid=window_grid,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block, h), lambda b, hh, qi, ki: (b, hh, qi, 0)),
            pl.BlockSpec((1, 1, block, h), kv_index),
            pl.BlockSpec((1, 1, block, h), kv_index),
            pl.BlockSpec((1, 1, block, h), lambda b, hh, qi, ki: (b, hh, qi, 0)),
            pl.BlockSpec((1, 1, block, 1), lambda b, hh, qi, ki: (b, hh, qi, 0)),
            pl.BlockSpec((1, 1, block, 1), lambda b, hh, qi, ki: (b, hh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block, h), lambda b, hh, qi, ki: (b, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, h), q.dtype),
        scratch_shapes=[pltpu.VMEM((block, h), jnp.float32)],
        **_call_kwargs("flash_bwd_dq", interpret),
    )(q, k, v, do, lse, delta)


def dkv_call(q, k, v, do, lse, delta, *, scale, block, causal, interpret, valid, window=None):
    """(dk, dv) for one (q, kv) pair via the blocked kernel — per expanded
    query head (no GQA fold; the caller folds groups). Shapes (B, H, S, h)."""
    B, H, S, h = q.shape
    group = H // k.shape[1]
    nq = S // block
    n_eff, window_grid, _q_index = _banded_grid(
        nq, block, causal, window, group=1, clamp_hi=nq - 1
    )
    q_index = _q_index
    grid_kv = (B, H, nq, n_eff)
    return pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, block_q=block, block_k=block, causal=causal,
            valid=valid, window=window, window_grid=window_grid, n_q_blocks=nq,
        ),
        grid=grid_kv,
        in_specs=[
            pl.BlockSpec((1, 1, block, h), q_index),
            pl.BlockSpec((1, 1, block, h), lambda b, hh, ki, qi: (b, hh // group, ki, 0)),
            pl.BlockSpec((1, 1, block, h), lambda b, hh, ki, qi: (b, hh // group, ki, 0)),
            pl.BlockSpec((1, 1, block, h), q_index),
            pl.BlockSpec((1, 1, block, 1), q_index),
            pl.BlockSpec((1, 1, block, 1), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block, h), lambda b, hh, ki, qi: (b, hh, ki, 0)),
            pl.BlockSpec((1, 1, block, h), lambda b, hh, ki, qi: (b, hh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, h), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, h), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, h), jnp.float32),
            pltpu.VMEM((block, h), jnp.float32),
        ],
        **_call_kwargs("flash_bwd_dkv", interpret),
    )(q, k, v, do, lse, delta)


def fold_gqa_groups(dk_h, dv_h, K, k_dtype, v_dtype):
    """Sum per-query-head kv grads onto the shared kv heads."""
    B, H, S, h = dk_h.shape
    group = H // K
    if group > 1:
        dk = dk_h.reshape(B, K, group, S, h).sum(axis=2).astype(k_dtype)
        dv = dv_h.reshape(B, K, group, S, h).sum(axis=2).astype(v_dtype)
        return dk, dv
    return dk_h.astype(k_dtype), dv_h.astype(v_dtype)


# ------------------------------------------------------- SPMD partitioning
# pallas_call lowers to an opaque custom-call: left to the partitioner it is
# replicated, with UNSHARDED operands on every chip — at pod scale a
# full-global-batch 30+ GiB allocation per device (tests/test_pod_aot.py).
# The kernels are embarrassingly parallel over batch and heads, so under the
# ambient mesh (`jax.sharding.set_mesh`; `Accelerator` traces its steps under
# it) they run per shard in a `shard_map`: batch over the mesh's batch axes,
# heads over `tensor` when that divides both H and the GQA K, sequence and
# head_dim whole within each shard. (`custom_partitioning`, which let the
# partitioner choose, cannot be used: the TPU runtime never registers its
# callbacks — "Custom emitter for CustomSPMDPartitioning not found" on a
# real 2x2 v5e, jax 0.9.0 / libtpu 0.0.34.)
def _shard_spec(B: int, H: int, K: int):
    """PartitionSpec over the leading (batch, heads) dims of the (B, H|K, S,
    *) kernel operands under the ambient mesh; None when there is nothing to
    shard over (no mesh, a one-device mesh, or already inside a shard_map,
    whose caller has done the partitioning)."""
    from jax.sharding import PartitionSpec

    from ..parallel.mesh import BATCH_AXES, TENSOR_AXIS, ambient_mesh

    mesh = ambient_mesh()
    if mesh.empty or mesh.manual_axes:
        return None
    sizes = mesh.shape
    batch = tuple(a for a in BATCH_AXES if sizes.get(a, 1) > 1)
    if B % math.prod(sizes[a] for a in batch):
        batch = ()  # e.g. a small eval batch on a large mesh: replicate it
    n_tensor = sizes.get(TENSOR_AXIS, 1)
    heads = TENSOR_AXIS if n_tensor > 1 and H % n_tensor == 0 and K % n_tensor == 0 else None
    if not batch and heads is None:
        return None
    return PartitionSpec(batch or None, heads)


def _per_shard(kernel, tensors, n_out: int):
    """``kernel(*tensors)`` over each batch/head shard of the ambient mesh."""
    spec = _shard_spec(tensors[0].shape[0], tensors[0].shape[1], tensors[1].shape[1])
    if spec is None:
        return kernel(*tensors)
    return jax.shard_map(
        kernel,
        in_specs=(spec,) * len(tensors),
        out_specs=(spec,) * n_out,
        check_vma=False,
    )(*tensors)


def _bwd_tensors(q, k, v, o, lse, g, *, scale, block, causal, interpret, valid, window):
    do = g
    if _use_resident(q.shape[2], q.shape[3], k.dtype):
        return _bwd_resident(
            scale, block, causal, interpret, valid, (q, k, v, o, lse), g,
            window=window,
        )
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )
    kwargs = dict(scale=scale, block=block, causal=causal, interpret=interpret,
                  valid=valid, window=window)
    dq = dq_call(q, k, v, do, lse, delta, **kwargs)
    dk_h, dv_h = dkv_call(q, k, v, do, lse, delta, **kwargs)
    dk, dv = fold_gqa_groups(dk_h, dv_h, k.shape[1], k.dtype, v.dtype)
    return dq, dk, dv


# --------------------------------------------------------------- entry point
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, block, causal, interpret, valid, window):
    return _flash_fwd(q, k, v, scale, block, causal, interpret, valid, window)[0]


def _flash_fwd(q, k, v, scale, block, causal, interpret, valid, window):
    kernel = functools.partial(
        _fwd, scale=scale, block=block, causal=causal, interpret=interpret,
        valid=valid, window=window,
    )
    o, lse = _per_shard(kernel, (q, k, v), n_out=2)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, block, causal, interpret, valid, window, residuals, g):
    kernel = functools.partial(
        _bwd_tensors, scale=scale, block=block, causal=causal,
        interpret=interpret, valid=valid, window=window,
    )
    return _per_shard(kernel, (*residuals, g), n_out=3)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_mask: jax.Array | None = None,
    block_size: int | None = None,
    scale: float | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> jax.Array:
    """Fused attention over (B, S, H, h) queries and (B, T, K, h) kv (GQA).

    ``window`` enables Mistral-style sliding-window attention IN the
    kernels (forward and backward): key c is visible from row r iff
    ``r - c < window``; band-dead tiles are neither fetched nor computed —
    the KV/Q grid axes span only the live diagonal band, so window-bounded
    contexts run at O(S * window) instead of O(S^2).

    Falls back to the XLA reference path when the shape is out of kernel
    territory (S not a multiple of the block, or an explicit padding mask —
    packed/padded batches route through the oracle until the kernel grows
    segment-id support)."""
    B, S, H, h = q.shape
    T, K = k.shape[1], k.shape[2]
    if H % K != 0:
        raise ValueError(f"num_heads {H} not divisible by num_kv_heads {K}")
    scale = scale if scale is not None else 1.0 / math.sqrt(h)
    if segment_mask is not None or S != T or S < 16:
        from ..models.layers import dot_product_attention

        if window is not None:
            # Queries are the last S of T absolute positions (the KV-cache
            # decode convention); anchoring at row index 0 would make the
            # band a no-op for single-token decode.
            rows = (T - S) + jnp.arange(S)[:, None]
            cols = jnp.arange(T)[None, :]
            band = jnp.broadcast_to((rows - cols < window), (B, S, T))
            segment_mask = (
                band
                if segment_mask is None
                else band
                & (segment_mask[:, None, :] if segment_mask.ndim == 2 else segment_mask).astype(bool)
            )
        return dot_product_attention(q, k, v, mask=segment_mask, causal=causal, scale=scale)
    interpret = _interpret_default() if interpret is None else interpret
    if block_size is None:
        # The persisted autotune table (ops/autotune.py) wins when it has
        # an entry for this (chip, seq, head_dim, dtype) — or when the
        # ATX_BLOCK_FLASH_ATTENTION override is set.
        from .autotune import default_cache

        cached = default_cache().get("flash_attention", (S, h), q.dtype)
        if cached is not None and cached > 0:
            block_size = int(cached)
        # Bigger blocks amortize the online-softmax bookkeeping across more
        # MXU work: 1024 measured 1.5x over 512 from S=4096 up on v5e
        # (75.6 vs 50.6 TF/s at 32k; 31 vs 46 ms at 4k); 2048 exceeds VMEM.
        # _use_resident already cuts over to the blocked path at 4096, so
        # 1024 here never reaches the resident kernels (which cannot
        # compile it). Guard: only when 1024 pads no more than 512 would
        # (S=4608 runs exact at 512; 1024 would add 11% dead work).
        elif S >= 4096 and _round_up(S, 1024) == _round_up(S, 512):
            block_size = 1024
        else:
            block_size = DEFAULT_BLOCK
        if cached is None:
            # Bank the heuristic so the table documents what actually ran
            # (and ATX603 can lint against it).
            default_cache().put("flash_attention", (S, h), q.dtype, block_size)
    block = min(block_size, _round_up(S, 128) if S < block_size else block_size)
    # Pad S up to a block multiple (e.g. the ubiquitous S-1 from next-token
    # shifting). Padded KV columns sit at positions >= S: under causal they
    # are masked for every real row by construction; non-causal kernels mask
    # cols >= valid explicitly. Padded Q rows are sliced away.
    padded = _round_up(S, block)
    if padded != S:
        pad = [(0, 0), (0, padded - S), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    # kernels run in (B, H, S, h)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _flash(qt, kt, vt, scale, block, causal, interpret, S, window)
    o = o.transpose(0, 2, 1, 3)
    return o[:, :S] if padded != S else o


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# Shared block/tuning helpers for the `native/pallas/` kernel tier: every
# tier kernel needs "largest tile that divides this dim" (grids must cover
# exactly — the tier kernels never pad, they fall back) and per-grid
# dimension semantics.

def pick_block(dim: int, candidates: tuple[int, ...] = (512, 256, 128, 64, 32, 16, 8)):
    """Largest candidate evenly dividing ``dim``; ``dim`` itself when smaller
    than every candidate; ``None`` when no candidate divides (caller falls
    back to the reference lowering)."""
    if dim <= 0:
        return None
    for c in candidates:
        if dim >= c and dim % c == 0:
            return c
    if dim < min(candidates):
        return dim
    return None


def tuned_call_kwargs(
    name: str,
    interpret: bool,
    semantics: tuple[str, ...] | None = None,
    vmem_limit_bytes: int | None = None,
):
    """`pallas_call` kwargs every kernel of the package shares: its ``name``
    (``metadata`` is what reaches a device trace: a v5e names an operation by
    its HLO text, and JAX writes the metadata into the custom call's
    ``frontend_attributes={kernel_metadata={...}}``), the per-grid
    dimension semantics and, for a kernel that stages more than Mosaic's
    default scoped VMEM, its limit (compiled mode only: the interpreter
    takes no compiler params)."""
    kwargs = {"name": name, "metadata": {"kernel": name}, "interpret": interpret}
    if semantics is not None and not interpret:
        params = {"dimension_semantics": tuple(semantics)}
        if vmem_limit_bytes is not None:
            params["vmem_limit_bytes"] = vmem_limit_bytes
        kwargs["compiler_params"] = pltpu.CompilerParams(**params)
    return kwargs
