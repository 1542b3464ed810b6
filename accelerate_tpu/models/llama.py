"""Llama-3-style decoder — the framework's flagship model family.

The reference delegates model code to `transformers` and shards it after the
fact (TP via `model.tensor_parallel(mesh)`, reference `accelerator.py:1545`;
FSDP wrapping :1555). Here the model is TPU-native from the start:

- **scan-over-layers**: all L transformer blocks' params are stacked along a
  leading layer axis and the body is one `lax.scan` — O(1) compile time in
  depth and a uniform sharding story;
- **remat**: optional `jax.checkpoint` on the block so activations are
  recomputed in backward (the activation-checkpointing analog of the
  reference FSDP plugin flag, `utils/dataclasses.py:1449`);
- **GQA + RoPE + SwiGLU + RMSNorm** in bf16-friendly form;
- attention is pluggable: "dot" (oracle), "flash" (Pallas kernel), "ring"
  (sequence-parallel ppermute) — see `ops/`.

The TP/FSDP sharding plan for this family is registered in `parallel/tp.py`
under the name ``"llama"``.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.int8 import at_layer, hoist_layer_stacks
from .layers import (
    AttentionSpec,
    apply_rope,
    attention_out,
    attention_qkv,
    cache_append,
    cache_positions,
    cache_write,
    cached_attention,
    cross_entropy_loss,
    dot_product_attention,
    init_attention,
    init_swiglu,
    rms_norm,
    remat_policy,
    RopeScaling,
    rope_frequencies,
    swiglu,
    traced_once_for,
    truncated_normal_init,
)

Params = Any


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    d_ff: int = 14336
    head_dim: int | None = None
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    # Rotary rescaling — Llama-3.1+ "llama3" banded rescale or "linear"
    # position interpolation; None = plain RoPE.
    rope_scaling: RopeScaling | None = None
    # Mistral-style sliding-window attention: position i attends to keys in
    # (i - sliding_window, i], uniformly across layers. None = full causal.
    sliding_window: int | None = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    remat: bool = False
    # What the checkpointed block may keep instead of recomputing:
    # "nothing" = full recompute (lowest memory); "dots" = keep every matmul
    # output (backward at ~2x forward FLOPs but O(10GB) of residuals at
    # bench scale); "block_outputs" = keep only the two residual-branch
    # outputs per layer (attention out-proj + FFN down-proj) — the best
    # recompute-FLOPs-avoided per byte (those are the highest-arithmetic-
    # intensity matmuls) at ~64MB/layer for the bench shape.
    remat_policy: str = "block_outputs"
    attention_impl: str = "dot"  # "dot" | "flash" | "ring" | "ulysses"
    z_loss: float = 0.0
    # Compute the LM loss in sequence chunks of this size (must divide S)
    # without materializing the full (B, S, V) logits — the fp32 logit tail
    # is the single biggest activation at long S / large vocab
    # (layers.chunked_lm_loss). None = unchunked.
    loss_chunk_size: int | None = None
    # Qwen2-style q/k/v biases (the only block-level deviation Qwen2 makes
    # from llama); o_proj stays bias-free there, so only bq/bk/bv are added.
    attn_bias: bool = False
    # Mixture-of-Experts: n_experts > 0 replaces every block's FFN with a
    # top-k routed expert layer (ops/moe.py); expert weights shard over the
    # `expert` mesh axis via the "llama" plan.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    moe_z_weight: float = 1e-3

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.num_heads

    @property
    def attention_spec(self) -> AttentionSpec:
        return AttentionSpec(self.d_model, self.num_heads, self.num_kv_heads, self.resolved_head_dim)

    @classmethod
    def tiny(cls, **overrides: Any) -> "LlamaConfig":
        """A toy config for tests/CI (fits the 8-device CPU mesh)."""
        defaults = dict(
            vocab_size=256, d_model=64, n_layers=2, num_heads=4, num_kv_heads=2,
            d_ff=128, max_seq_len=128, rope_theta=10000.0,
        )
        defaults.update(overrides)
        return cls(**defaults)

    @classmethod
    def llama3_8b(cls, **overrides: Any) -> "LlamaConfig":
        return cls(**{**dict(
            vocab_size=128256, d_model=4096, n_layers=32, num_heads=32,
            num_kv_heads=8, d_ff=14336, max_seq_len=8192,
        ), **overrides})

    @classmethod
    def llama3_70b(cls, **overrides: Any) -> "LlamaConfig":
        return cls(**{**dict(
            vocab_size=128256, d_model=8192, n_layers=80, num_heads=64,
            num_kv_heads=8, d_ff=28672, max_seq_len=8192,
        ), **overrides})

    def param_count(self) -> int:
        h = self.resolved_head_dim
        attn = self.d_model * h * (2 * self.num_heads + 2 * self.num_kv_heads)
        if self.attn_bias:
            attn += h * (self.num_heads + 2 * self.num_kv_heads)
        if self.n_experts:
            ffn = self.n_experts * 3 * self.d_model * self.d_ff + self.d_model * self.n_experts
        else:
            ffn = 3 * self.d_model * self.d_ff
        block = attn + ffn + 2 * self.d_model
        embed = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        return self.n_layers * block + embed + self.d_model

    def flops_per_token(self) -> float:
        """Approximate training FLOPs/token (6N + attention term)."""
        return 6.0 * self.param_count() + 12.0 * self.n_layers * self.d_model * self.max_seq_len


def init_block(rng: jax.Array, config: LlamaConfig, dtype=jnp.float32) -> Params:
    ka, km = jax.random.split(rng)
    attn = init_attention(ka, config.attention_spec, dtype, bias=config.attn_bias)
    if config.attn_bias:
        del attn["bo"]  # Qwen2 convention: q/k/v biased, o_proj is not
    block = {
        "attn_norm": jnp.zeros((config.d_model,), dtype),
        "attn": attn,
        "mlp_norm": jnp.zeros((config.d_model,), dtype),
    }
    if config.n_experts:
        from ..ops.moe import init_moe

        block["moe"] = init_moe(km, config.d_model, config.d_ff, config.n_experts, dtype)
    else:
        block["mlp"] = init_swiglu(km, config.d_model, config.d_ff, dtype)
    return block


def init(rng: jax.Array, config: LlamaConfig, dtype=jnp.float32) -> Params:
    """Initialize params. Layer params are stacked: every leaf under
    ``blocks`` has a leading ``n_layers`` axis (scan-over-layers layout)."""
    k_embed, k_blocks, k_out = jax.random.split(rng, 3)
    block_keys = jax.random.split(k_blocks, config.n_layers)
    blocks = jax.vmap(lambda k: init_block(k, config, dtype))(block_keys)
    params = {
        "embed": truncated_normal_init(k_embed, (config.vocab_size, config.d_model), 1.0, dtype),
        "blocks": blocks,
        "final_norm": jnp.zeros((config.d_model,), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = truncated_normal_init(
            k_out, (config.d_model, config.vocab_size), 1.0 / np.sqrt(config.d_model), dtype
        )
    return params


_remat_policy = remat_policy  # shared impl in layers.py


def _rope_tables(config: LlamaConfig) -> tuple[jax.Array, jax.Array]:
    cos_np, sin_np = rope_frequencies(
        config.resolved_head_dim,
        config.max_seq_len,
        config.rope_theta,
        scaling=config.rope_scaling,
    )
    return jnp.asarray(cos_np), jnp.asarray(sin_np)


def _window_mask(
    mask: jax.Array | None, positions: jax.Array, seq_len: int, window: int
) -> jax.Array:
    """Fold the sliding-window band into the (optional) user mask: key j is
    visible from query position p iff ``p - j < window`` (HF Mistral
    semantics — the window includes the current token; causality is applied
    separately by the attention op). Returns a (B, S, T) boolean mask."""
    j = jnp.arange(seq_len, dtype=jnp.int32)
    win = (positions[:, :, None] - j[None, None, :]) < window
    if mask is None:
        return win
    if mask.ndim == 2:
        mask = mask[:, None, :]
    return mask.astype(bool) & win


def _attention(config: LlamaConfig, q, k, v, mask):
    if config.attention_impl == "flash":
        from ..ops.flash_attention import flash_attention

        # window only when no mask arrived: a non-None mask means the band
        # (if any) is already folded in by the caller (see forward) — the
        # kernel's row-index band must not be applied on top.
        if config.sliding_window is not None and mask is not None:
            # Folded-band cases (explicit positions / user masks) run the
            # unfused oracle — at windowed long contexts that is exactly
            # the O(S^2) blowup the kernel exists to avoid; say so.
            import warnings

            warnings.warn(
                "sliding_window with an explicit mask or non-default "
                "positions runs the unfused O(S^2) attention path (the "
                "fused band kernel needs default contiguous positions and "
                "no extra mask).",
                stacklevel=3,
            )
        return flash_attention(
            q, k, v, causal=True, segment_mask=mask,
            window=config.sliding_window if mask is None else None,
        )
    if config.attention_impl in ("ring", "ulysses"):
        if mask is not None and mask.ndim != 2:
            hint = (
                " (with sliding_window, a folded 3-D band mask reaches here "
                "whenever positions are non-default — packed/shifted "
                "sequences band by position, which the ring/ulysses chunk "
                "plumbing cannot express)"
                if config.sliding_window is not None
                else ""
            )
            raise NotImplementedError(
                f"attention_impl={config.attention_impl!r} supports (B, S) "
                "key-padding masks only; full (B, S, T) masks need 'flash' "
                f"or 'dot'.{hint}"
            )
        if config.attention_impl == "ring":
            from ..ops.ring_attention import ring_attention

            # Window rides the per-step chunk masks (einsum path; band-dead
            # ring steps skip their FLOPs).
            return ring_attention(
                q, k, v, causal=True, kv_mask=mask,
                window=config.sliding_window,
            )
        if mask is not None:
            # Masked ulysses falls back to the O(S^2)-per-device oracle over
            # the gathered sequence — exactly what long context cannot
            # afford; ring handles masks chunked at O(S^2/n).
            raise NotImplementedError(
                "attention_impl='ulysses' with a padding mask would "
                "materialize full-sequence attention per device; use "
                "attention_impl='ring' for padded long-context batches."
            )
        from ..ops.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, causal=True, window=config.sliding_window)
    if config.attention_impl != "dot":
        raise ValueError(
            f"Unknown attention_impl {config.attention_impl!r}; expected "
            "'dot', 'flash', 'ring', or 'ulysses'"
        )
    return dot_product_attention(q, k, v, mask=mask, causal=True)


def block_forward(
    block: Params,
    x: jax.Array,
    *,
    config: LlamaConfig,
    cos: jax.Array,
    sin: jax.Array,
    positions: jax.Array,
    mask: jax.Array | None,
) -> jax.Array:
    from jax.ad_checkpoint import checkpoint_name

    from ..parallel.mesh import constrain_batch

    # Re-pin the residual stream's batch sharding every layer: inside the
    # scan the partitioner otherwise drifts (mesh.constrain_batch docstring).
    x = constrain_batch(x)
    h = rms_norm(x, block["attn_norm"], config.norm_eps)
    q, k, v = attention_qkv(block["attn"], h)
    q = checkpoint_name(apply_rope(q, cos, sin, positions), "q_rope")
    k = checkpoint_name(apply_rope(k, cos, sin, positions), "k_rope")
    v = checkpoint_name(v, "v_proj")
    attn = _attention(config, q, k, v, mask)
    x = x + checkpoint_name(attention_out(block["attn"], attn), "attn_out")
    h = rms_norm(x, block["mlp_norm"], config.norm_eps)
    ffn_out, aux = _ffn(block, h, config)
    x = x + checkpoint_name(ffn_out, "ffn_out")
    return x, aux


def _maybe_dequantize(block: Params, dtype: Any) -> Params:
    """Transparent weight-only int8 support (utils/quantization.py): when a
    block carries quantized leaves, dequantize them to the compute dtype here
    — per layer, inside the scan — so HBM holds int8 while matmuls see the
    compute dtype.

    Inside an `ops.int8.int8_compute()` context the quantized nodes pass
    through UNTOUCHED: every projection routes through `matmul_einsum`,
    which contracts them int8×int8→int32 on the int8 MXU (~2× the bf16
    rate — the compute-bound prefill/verify win; `ops/int8.py`)."""
    from ..ops.int8 import int8_compute_enabled
    from ..utils.quantization import dequantize_pytree, has_quantized

    if has_quantized(block):
        if int8_compute_enabled():
            return block
        return dequantize_pytree(block, dtype)
    return block


def _ffn(block: Params, h: jax.Array, config: LlamaConfig):
    """Dense swiglu or routed MoE; returns (out, aux-losses-or-None)."""
    if config.n_experts:
        from ..ops.moe import moe_forward

        return moe_forward(
            block["moe"],
            h,
            top_k=config.moe_top_k,
            capacity_factor=config.moe_capacity_factor,
        )
    return swiglu(block["mlp"], h), None


def forward(
    params: Params,
    tokens: jax.Array,
    config: LlamaConfig,
    *,
    positions: jax.Array | None = None,
    mask: jax.Array | None = None,
    return_aux: bool = False,
    return_hidden: bool = False,
) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, vocab).

    With ``return_aux`` (MoE training) returns ``(logits, aux)`` where aux
    holds the per-layer-averaged router losses. ``return_hidden`` skips the
    logits head and returns the final-norm hidden states instead (the
    chunked-loss path projects them chunk-by-chunk)."""
    B, S = tokens.shape
    if S > config.max_seq_len:
        # RoPE table gathers clamp out-of-range positions under jit, which
        # would silently degrade instead of failing.
        raise ValueError(f"sequence length {S} exceeds max_seq_len={config.max_seq_len}")
    default_positions = positions is None
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cos, sin = _rope_tables(config)
    _kernel_band = default_positions and (
        (config.attention_impl in ("flash", "ulysses") and mask is None)
        # ring combines its per-step band with (B, S) padding masks natively.
        or config.attention_impl == "ring"
    )
    if config.sliding_window is not None and not _kernel_band:
        # flash/ulysses apply the band in-kernel (tile skipping) — but only
        # for the unmasked default-positions case; explicit positions
        # (packed/shifted sequences) band by POSITION, which the kernel's
        # row-index band cannot express, and user masks force the oracle
        # anyway, so every other combination folds into ONE materialized
        # mask (_attention then passes no window — the band must not be
        # applied twice with different anchors).
        mask = _window_mask(mask, positions, S, config.sliding_window)

    x = params["embed"][tokens]

    body = partial(
        block_forward, config=config, cos=cos, sin=sin, positions=positions, mask=mask
    )
    if config.remat:
        body = jax.checkpoint(body, policy=_remat_policy(config.remat_policy))

    def scan_body(carry, block):
        new_x, aux = body(_maybe_dequantize(block, carry.dtype), carry)
        return new_x, aux

    x, aux_stacked = jax.lax.scan(scan_body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    aux = (
        jax.tree.map(lambda a: jnp.mean(a, axis=0), aux_stacked)
        if aux_stacked is not None
        else {}
    )
    if return_hidden:
        return (x, aux) if return_aux else x
    logits = jnp.einsum("bsd,dv->bsv", x, _lm_head(params, config).astype(x.dtype))
    if not return_aux:
        return logits
    return logits, aux


# ---------------------------------------------------------------- KV cache
def init_cache(
    config: LlamaConfig, batch_size: int, max_len: int, dtype=jnp.bfloat16
) -> dict[str, jax.Array]:
    """Decode-time KV cache, layer-stacked to match the scan layout.

    ``dtype=jnp.int8`` stores K/V quantized with per-(token, head) scales —
    half the HBM bytes per decode step, which IS the decode roofline once
    the context is long (at 32k the cache outweighs a 443M model's weights
    ~2:1). Dequantization fuses into the attention matmuls; accuracy is the
    standard per-token-scale int8 KV trade (logit drift ~1e-2, tested)."""
    kv_heads, head_dim = config.num_kv_heads, config.resolved_head_dim
    # Heads are flattened into the last axis: (L, B, T, K*h) is the shape the
    # flash-decode kernel's blocks index, so a decode step reads the stack in
    # place (`layers.cached_attention`); head kk is lanes [kk*h, (kk+1)*h).
    shape = (config.n_layers, batch_size, max_len, kv_heads * head_dim)
    if dtype == jnp.int8:
        scale_shape = shape[:-1] + (kv_heads,)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(scale_shape, jnp.bfloat16),
            "v_scale": jnp.zeros(scale_shape, jnp.bfloat16),
            "length": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def forward_with_cache(
    params: Params,
    tokens: jax.Array,
    cache: dict[str, jax.Array],
    config: LlamaConfig,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Incremental forward: append ``tokens`` (B, T_new) at ``cache['length']``
    and attend against everything cached so far. Returns (logits, new_cache).

    Serves both prefill (T_new = prompt length) and decode (T_new = 1); the
    same jitted function handles either with static T_new.

    ``cache['length']`` may be a scalar (all rows share one cursor — the
    plain decode contract) or shape (B,) (per-row cursors: speculative
    decoding commits a different number of tokens per row, `speculative.py`).
    Positions, masks, and the KV writes are all per-row in the latter case.
    """
    B, T_new = tokens.shape
    max_len = cache["k"].shape[2]
    start = cache["length"]
    positions = cache_positions(start, T_new, B)
    cos, sin = _rope_tables(config)

    # (B, T_new, max_len) attention mask: cached positions < start+1+i.
    cache_pos = jnp.arange(max_len, dtype=jnp.int32)
    mask = cache_pos[None, None, :] <= positions[:, :, None]
    if config.sliding_window is not None:
        # The cache is still a full ring-free buffer; the window is applied
        # as a mask so positions older than (p - window) are invisible.
        mask = mask & (
            cache_pos[None, None, :] > positions[:, :, None] - config.sliding_window
        )

    x = params["embed"][tokens]
    # Decode steps (T_new == 1) may take the Pallas flash-decode kernel:
    # valid prefix per row after the write is positions[:, 0] + 1 (works for
    # the scalar cursor and the per-row speculative cursors alike). A chunk
    # (T_new > 1) may take the flash-prefill kernel by its cursor ``start``;
    # sliding-window configs always run the masked reference attention.
    decode_lengths = positions[:, 0] + 1 if T_new == 1 else None

    # The layer-stacked cache rides the scan CARRY at every length: a step
    # writes its new rows into the donated buffers and attends against them
    # there (`layers.cache_append`, `layers.cached_attention`). As xs/ys the
    # scan would restack the whole cache every step. Int8 weight stacks the
    # `int8_matmul` kernel will read stay out of the xs too, closed over
    # whole: the kernel indexes them by ``i`` (`ops.int8.hoist_layer_stacks`).
    blocks, stacks = hoist_layer_stacks(params["blocks"])

    def scan_body(carry, block):
        x, kv, i = carry
        block = _maybe_dequantize(at_layer(block, stacks, i), x.dtype)
        h = rms_norm(x, block["attn_norm"], config.norm_eps)
        q, k, v = attention_qkv(block["attn"], h)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        kv = cache_append(kv, i, k, v, start)
        attn = cached_attention(
            q, kv, i, mask=mask, lengths=decode_lengths, window=config.sliding_window, start=start
        )
        x = x + attention_out(block["attn"], attn)
        h = rms_norm(x, block["mlp_norm"], config.norm_eps)
        ffn_out, _ = _ffn(block, h, config)  # aux unused at inference
        return (x + ffn_out, kv, i + 1), None

    kv = {name: buf for name, buf in cache.items() if name != "length"}
    with traced_once_for(config.n_layers):
        (x, kv, _), _ = jax.lax.scan(scan_body, (x, kv, jnp.zeros((), jnp.int32)), blocks)
    new_cache = dict(kv, length=start + T_new)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, _lm_head(params, config).astype(x.dtype))
    return logits, new_cache


@functools.lru_cache(maxsize=16)
def _generator(config: LlamaConfig, generation_config: Any, jit_loop: bool):
    from ..generation import GenerationConfig, Generator, cache_dtype

    gcfg = generation_config or GenerationConfig()
    kv_dtype = cache_dtype(gcfg)
    return Generator(
        lambda p, t, c: forward_with_cache(p, t, c, config),
        lambda b, m: init_cache(config, b, m, dtype=kv_dtype),
        gcfg,
        jit_loop=jit_loop,
    )


def _lm_head(params: Params, config: LlamaConfig) -> jax.Array:
    return params["embed"].T if config.tie_embeddings else params["lm_head"]


def _add_moe_aux(loss: jax.Array, aux: dict, config: LlamaConfig) -> jax.Array:
    return (
        loss
        + config.moe_aux_weight * aux["moe_load_balance"]
        + config.moe_z_weight * aux["moe_z_loss"]
    )


def _chunked_loss_fn(
    params: Params, batch: dict[str, jax.Array], config: LlamaConfig
) -> jax.Array:
    """`loss_fn` with `layers.chunked_lm_loss`: the trunk runs at full S and
    only the logits projection + softmax are chunked. The shifted-labels
    default keeps S intact by masking out the final position instead of
    slicing (chunking needs chunk_size | S)."""
    from .layers import chunked_lm_loss_from_batch

    tokens = batch["input_ids"]
    attn_mask = batch.get("attention_mask")
    moe = config.n_experts > 0
    out = forward(
        params, tokens, config, mask=attn_mask, return_aux=moe, return_hidden=True
    )
    x, aux = out if moe else (out, {})
    loss = chunked_lm_loss_from_batch(
        x, _lm_head(params, config), tokens, batch.get("labels"), attn_mask,
        z_loss=config.z_loss, chunk_size=config.loss_chunk_size,
    )
    return _add_moe_aux(loss, aux, config) if moe else loss


def generate(
    params: Params,
    prompt: jax.Array,
    config: LlamaConfig,
    *,
    generation_config: Any = None,
    rng: jax.Array | None = None,
    jit_loop: bool = True,
) -> jax.Array:
    """Autoregressive generation for this family. Jitted prefill/decode steps
    are cached per (model config, generation config), so repeated calls skip
    tracing (both configs are frozen dataclasses, hence hashable)."""
    gen = _generator(config, generation_config, jit_loop)
    total = prompt.shape[1] + gen.config.max_new_tokens
    if total > config.max_seq_len:
        # RoPE table gathers clamp out-of-range positions under jit, which
        # would silently degrade instead of failing.
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + max_new_tokens "
            f"({gen.config.max_new_tokens}) = {total} exceeds "
            f"max_seq_len={config.max_seq_len}"
        )
    return gen(params, prompt, rng=rng)


@functools.lru_cache(maxsize=16)
def _offloaded_block_step(config: LlamaConfig):
    """Jitted per-layer step for the offloaded path, cached per config so
    repeated streamed forwards reuse the compilation."""

    def step(block, x, cos, sin, positions, mask):
        x, _aux = block_forward(
            block, x, config=config, cos=cos, sin=sin, positions=positions, mask=mask
        )
        return x

    return jax.jit(step)


def forward_offloaded(
    params: Params,
    tokens: jax.Array,
    config: LlamaConfig,
    *,
    compute_dtype: Any = jnp.bfloat16,
) -> jax.Array:
    """Forward for over-HBM models: ``params['blocks']`` leaves may be
    host-resident numpy (see `big_modeling.offload_blocks`); each layer
    streams to the device one step ahead of compute
    (`big_modeling.streamed_scan`). Non-block params must fit on device.
    """
    from ..big_modeling import streamed_scan

    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cos, sin = _rope_tables(config)
    mask = (
        _window_mask(None, positions, S, config.sliding_window)
        if config.sliding_window is not None
        else None
    )
    embed = jnp.asarray(params["embed"]).astype(compute_dtype)
    x = embed[tokens]

    block_step = _offloaded_block_step(config)
    x = streamed_scan(
        lambda carry, block: block_step(block, carry, cos, sin, positions, mask),
        x, params["blocks"],
        dtype=compute_dtype,
    )
    x = rms_norm(x, jnp.asarray(params["final_norm"]), config.norm_eps)
    head = embed.T if config.tie_embeddings else jnp.asarray(params["lm_head"]).astype(compute_dtype)
    return jnp.einsum("bsd,dv->bsv", x, head)


@functools.lru_cache(maxsize=16)
def _offloaded_cache_step(config: LlamaConfig):
    """Jitted per-layer cache step for offloaded decode: one block's weights
    (staged from host/disk), that layer's KV cache slices, and the running
    hidden state."""

    def step(block, k_cache, v_cache, x, cos, sin, positions, mask, start):
        block = _maybe_dequantize(block, x.dtype)
        h = rms_norm(x, block["attn_norm"], config.norm_eps)
        q, k, v = attention_qkv(block["attn"], h)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        # One layer (B, S, K*h) of the stacked cache: heads flattened.
        k_cache = cache_write(k_cache, k.reshape(k.shape[:2] + (-1,)), start)
        v_cache = cache_write(v_cache, v.reshape(v.shape[:2] + (-1,)), start)
        heads = k_cache.shape[:2] + k.shape[2:]
        attn = dot_product_attention(
            q,
            k_cache.reshape(heads).astype(q.dtype),
            v_cache.reshape(heads).astype(q.dtype),
            mask=mask,
        )
        x = x + attention_out(block["attn"], attn)
        h = rms_norm(x, block["mlp_norm"], config.norm_eps)
        ffn_out, _ = _ffn(block, h, config)
        return x + ffn_out, k_cache, v_cache

    return jax.jit(step, donate_argnums=(1, 2))


def forward_with_cache_offloaded(
    params: Params,
    tokens: jax.Array,
    cache: dict[str, jax.Array],
    config: LlamaConfig,
    *,
    compute_dtype: Any = jnp.bfloat16,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """`forward_with_cache` for over-HBM (and over-host-RAM) models:
    ``params['blocks']`` leaves are host numpy arrays or disk memmaps
    (`big_modeling.offload_blocks` / disk offload via
    ``load_pretrained(offload_dir=...)``); each layer's weights stream to
    the device one step ahead of compute while the KV cache stays resident.
    The per-layer reads are what make a model larger than host RAM + HBM
    decodable — only one layer's weights are ever in flight (reference
    `disk_offload` + `OffloadedWeightsLoader`, `big_modeling.py:260`,
    `utils/offload.py:127`)."""
    if cache["k"].dtype == jnp.int8:
        raise NotImplementedError(
            "int8 KV caches are not implemented for the offloaded decode "
            "path (the streamed step would truncate float K/V into "
            "scale-free int8 and read them back as garbage); use "
            "forward_with_cache, or a bf16 cache here."
        )
    from ..big_modeling import streamed_scan

    B, T_new = tokens.shape
    start = cache["length"]
    positions = cache_positions(start, T_new, B)
    cos, sin = _rope_tables(config)
    max_len = cache["k"].shape[2]
    cache_pos = jnp.arange(max_len, dtype=jnp.int32)
    mask = cache_pos[None, None, :] <= positions[:, :, None]
    if config.sliding_window is not None:
        mask = mask & (
            cache_pos[None, None, :] > positions[:, :, None] - config.sliding_window
        )

    embed = jnp.asarray(params["embed"]).astype(compute_dtype)
    x = embed[tokens]
    step = _offloaded_cache_step(config)

    # Stream blocks while carrying per-layer cache slices alongside.
    n_layers = config.n_layers
    k_layers, v_layers = [], []

    def body(carry, block, _i=[0]):
        x = carry
        i = _i[0]
        _i[0] += 1
        x, k_i, v_i = step(
            block, cache["k"][i], cache["v"][i], x, cos, sin, positions, mask, start
        )
        k_layers.append(k_i)
        v_layers.append(v_i)
        return x

    x = streamed_scan(body, x, params["blocks"], dtype=compute_dtype)
    x = rms_norm(x, jnp.asarray(params["final_norm"]), config.norm_eps)
    head = (
        embed.T
        if config.tie_embeddings
        else jnp.asarray(params["lm_head"]).astype(compute_dtype)
    )
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    new_cache = {
        "k": jnp.stack(k_layers),
        "v": jnp.stack(v_layers),
        "length": start + T_new,
    }
    return logits, new_cache


def generate_offloaded(
    params: Params,
    prompt: jax.Array,
    config: LlamaConfig,
    *,
    max_new_tokens: int = 16,
    compute_dtype: Any = jnp.bfloat16,
) -> jax.Array:
    """Greedy decoding over host/disk-offloaded blocks. Every generated
    token streams the full stack once — throughput is storage-bandwidth /
    model-size, the same roofline as the reference's disk-offloaded
    OPT-30B `generate` (BASELINE's over-RAM configuration)."""
    B, S = prompt.shape
    total = S + max_new_tokens
    if total > config.max_seq_len:
        raise ValueError(
            f"prompt ({S}) + max_new_tokens ({max_new_tokens}) = {total} "
            f"exceeds max_seq_len={config.max_seq_len}"
        )
    cache = init_cache(config, B, total, dtype=compute_dtype)
    logits, cache = forward_with_cache_offloaded(
        params, prompt, cache, config, compute_dtype=compute_dtype
    )
    out = [prompt]
    last = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    for _ in range(max_new_tokens - 1):
        out.append(last)
        logits, cache = forward_with_cache_offloaded(
            params, last, cache, config, compute_dtype=compute_dtype
        )
        last = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    out.append(last)
    return jnp.concatenate(out, axis=1)


def loss_fn(
    params: Params,
    batch: dict[str, jax.Array],
    config: LlamaConfig,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Next-token prediction loss. batch: {"input_ids": (B, S)} with optional
    "labels" (shifted) and "attention_mask"."""
    if config.loss_chunk_size:
        return _chunked_loss_fn(params, batch, config)
    tokens = batch["input_ids"]
    labels = batch.get("labels")
    attn_mask = batch.get("attention_mask")
    moe = config.n_experts > 0
    out = forward(params, tokens, config, mask=attn_mask, return_aux=moe)
    logits, aux = out if moe else (out, {})
    if labels is None:
        # Run the forward at full S and drop the last logit instead of
        # slicing the tokens: keeps the sequence length at its (power-of-two,
        # block-aligned) value so matmul tiling and the flash kernel's block
        # path are preserved; one wasted position is noise.
        labels = tokens[:, 1:]
        loss_mask = attn_mask[:, 1:] if attn_mask is not None else None
        logits = logits[:, :-1]
    else:
        loss_mask = attn_mask
    loss = cross_entropy_loss(logits, labels, mask=loss_mask, z_loss=config.z_loss)
    return _add_moe_aux(loss, aux, config) if moe else loss
