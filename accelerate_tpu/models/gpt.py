"""GPT-style decoder family: GPT-2, GPT-NeoX, GPT-J, and OPT.

Widens the model zoo to the reference's breadth: the reference trains
GPT-class models through Megatron's `GPTTrainStep` (reference
`utils/megatron_lm.py:588`) and its published big-model-inference table is
GPT-J-6B / GPT-NeoX-20B / OPT-30B (reference
`benchmarks/big_model_inference/README.md:27-37`). Same TPU-native skeleton
as `models/llama.py` (scan-over-layers, optional remat, pluggable
attention), with the architecture selected by config knobs instead of four
near-identical modules — every variant therefore inherits the family's TP
plan (`parallel/tp.py` ``"gpt"``), quantize-on-load, offload, and
generation paths for free:

- ``positional``: learned absolute embeddings (``wpe``; GPT-2/OPT) or
  rotary (``rotary_dim`` for partial application, ``rotary_interleaved``
  for GPT-J's rotate-every-two pairing vs NeoX's rotate-half);
- ``parallel_residual``: NeoX computes attn and MLP from the SAME block
  input (two norms); ``shared_parallel_norm`` is GPT-J's single-norm
  version;
- ``activation``: gelu_new (GPT-2/GPT-J), gelu (NeoX), relu (OPT);
- bias layout: ``attn_bias`` (GPT-J is bias-free in attention),
  ``head_bias`` (GPT-J's untied lm_head carries one).

Pre-LN `layer_norm` (scale+bias), full multi-head attention (no GQA), and
biased MLPs are common to all four.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .layers import (
    AttentionSpec,
    activation_fn,
    apply_rope,
    apply_rope_interleaved,
    attention_out,
    attention_qkv,
    cache_append,
    cache_positions,
    cached_attention,
    cross_entropy_loss,
    dot_product_attention,
    head_logits,
    init_attention,
    init_mlp_gelu,
    layer_norm,
    mlp_gelu,
    remat_policy,
    rope_frequencies,
    traced_once_for,
    truncated_normal_init,
)

Params = Any


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    d_model: int = 768
    n_layers: int = 12
    num_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 1024
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    remat: bool = False
    remat_policy: str = "block_outputs"
    attention_impl: str = "dot"  # "dot" | "flash"
    z_loss: float = 0.0
    # Chunked LM loss (layers.chunked_lm_loss): compute the loss in sequence
    # chunks without materializing the (B, S, V) fp32 logits. None = off.
    loss_chunk_size: int | None = None
    # ------------------------------------------- variant knobs (GPT-2 dflt)
    # Which HF tensor layout this config ingests/exports as
    # (models/hf.py): "gpt2" | "gpt_neox" | "gptj" | "opt".
    hf_layout: str = "gpt2"
    positional: str = "learned"  # "learned" (wpe) | "rotary"
    # Partial rotary: rope applied to the first `rotary_dim` dims of each
    # head (GPT-NeoX rotary_pct, GPT-J rotary_dim); None = full head_dim.
    rotary_dim: int | None = None
    rotary_interleaved: bool = False  # GPT-J pairing; False = rotate-half
    rope_theta: float = 10000.0
    # NeoX: x + attn(ln1(x)) + mlp(ln2(x)) in one residual hop; GPT-J is the
    # same with the MLP reusing ln1's output (shared_parallel_norm — the
    # block then has no ln2 params at all).
    parallel_residual: bool = False
    shared_parallel_norm: bool = False
    activation: str = "gelu_new"  # "gelu_new" | "gelu" | "relu"
    attn_bias: bool = True  # GPT-J attention projections are bias-free
    head_bias: bool = False  # GPT-J's untied lm_head has a bias

    def __post_init__(self) -> None:
        if self.shared_parallel_norm and not self.parallel_residual:
            # init_block omits ln2 under shared_parallel_norm; the
            # sequential path reads it — fail at config time, not mid-trace.
            raise ValueError(
                "shared_parallel_norm=True requires parallel_residual=True "
                "(the shared norm IS the parallel layout's single norm)."
            )
        if self.positional not in ("learned", "rotary"):
            raise ValueError(
                f"positional={self.positional!r}; expected 'learned' or 'rotary'."
            )

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def resolved_rotary_dim(self) -> int:
        return self.rotary_dim if self.rotary_dim is not None else self.head_dim

    @property
    def attention_spec(self) -> AttentionSpec:
        return AttentionSpec(self.d_model, self.num_heads, self.num_heads, self.head_dim)

    @classmethod
    def tiny(cls, **overrides: Any) -> "GPTConfig":
        defaults = dict(
            vocab_size=256, d_model=64, n_layers=2, num_heads=4, d_ff=128, max_seq_len=128
        )
        defaults.update(overrides)
        return cls(**defaults)

    @classmethod
    def gpt2(cls, **overrides: Any) -> "GPTConfig":
        return cls(**overrides)

    @classmethod
    def gpt2_xl(cls, **overrides: Any) -> "GPTConfig":
        return cls(**{**dict(d_model=1600, n_layers=48, num_heads=25, d_ff=6400), **overrides})

    @classmethod
    def gptj_6b(cls, **overrides: Any) -> "GPTConfig":
        defaults = dict(
            vocab_size=50400, d_model=4096, n_layers=28, num_heads=16,
            d_ff=16384, max_seq_len=2048, hf_layout="gptj",
            positional="rotary", rotary_dim=64, rotary_interleaved=True,
            parallel_residual=True, shared_parallel_norm=True,
            attn_bias=False, tie_embeddings=False, head_bias=True,
        )
        defaults.update(overrides)
        return cls(**defaults)

    @classmethod
    def gpt_neox_20b(cls, **overrides: Any) -> "GPTConfig":
        defaults = dict(
            vocab_size=50432, d_model=6144, n_layers=44, num_heads=64,
            d_ff=24576, max_seq_len=2048, hf_layout="gpt_neox",
            positional="rotary", rotary_dim=24, parallel_residual=True,
            activation="gelu", tie_embeddings=False,
        )
        defaults.update(overrides)
        return cls(**defaults)

    @classmethod
    def opt_30b(cls, **overrides: Any) -> "GPTConfig":
        defaults = dict(
            vocab_size=50272, d_model=7168, n_layers=48, num_heads=56,
            d_ff=28672, max_seq_len=2048, hf_layout="opt",
            activation="relu", tie_embeddings=True,
        )
        defaults.update(overrides)
        return cls(**defaults)

    def param_count(self) -> int:
        attn = 4 * self.d_model * self.d_model
        if self.attn_bias:
            attn += 4 * self.d_model  # q/k/v/o biases
        ffn = 2 * self.d_model * self.d_ff + self.d_ff + self.d_model
        n_norms = 1 if self.shared_parallel_norm else 2
        block = attn + ffn + n_norms * 2 * self.d_model
        embed = self.vocab_size * self.d_model
        if self.positional == "learned":
            embed += self.max_seq_len * self.d_model
        head = 0 if self.tie_embeddings else self.d_model * self.vocab_size
        if self.head_bias and not self.tie_embeddings:
            head += self.vocab_size
        return self.n_layers * block + embed + 2 * self.d_model + head

    def flops_per_token(self) -> float:
        return 6.0 * self.param_count() + 12.0 * self.n_layers * self.d_model * self.max_seq_len


def init_block(rng: jax.Array, config: GPTConfig, dtype=jnp.float32) -> Params:
    ka, km = jax.random.split(rng)
    block = {
        "ln1_scale": jnp.ones((config.d_model,), dtype),
        "ln1_bias": jnp.zeros((config.d_model,), dtype),
        "attn": init_attention(ka, config.attention_spec, dtype, bias=config.attn_bias),
        "mlp": init_mlp_gelu(km, config.d_model, config.d_ff, dtype),
    }
    if not config.shared_parallel_norm:
        block["ln2_scale"] = jnp.ones((config.d_model,), dtype)
        block["ln2_bias"] = jnp.zeros((config.d_model,), dtype)
    return block


def init(rng: jax.Array, config: GPTConfig, dtype=jnp.float32) -> Params:
    """Layer params stacked along a leading ``n_layers`` axis (scan layout)."""
    k_tok, k_pos, k_blocks, k_head = jax.random.split(rng, 4)
    block_keys = jax.random.split(k_blocks, config.n_layers)
    blocks = jax.vmap(lambda k: init_block(k, config, dtype))(block_keys)
    params = {
        "wte": truncated_normal_init(k_tok, (config.vocab_size, config.d_model), 0.02, dtype),
        "blocks": blocks,
        "lnf_scale": jnp.ones((config.d_model,), dtype),
        "lnf_bias": jnp.zeros((config.d_model,), dtype),
    }
    if config.positional == "learned":
        params["wpe"] = truncated_normal_init(
            k_pos, (config.max_seq_len, config.d_model), 0.01, dtype
        )
    if not config.tie_embeddings:
        params["lm_head"] = truncated_normal_init(
            k_head, (config.d_model, config.vocab_size), 1.0 / np.sqrt(config.d_model), dtype
        )
        if config.head_bias:
            params["lm_head_bias"] = jnp.zeros((config.vocab_size,), dtype)
    return params


def _rope_tables(config: GPTConfig):
    """cos/sin tables over the ROTARY dims only (partial rotary leaves the
    tail of each head untouched). Rebuilt per call, NOT cached: under jit
    the `jnp.asarray` result is a trace-local constant, and caching it
    would leak the tracer into later traces (llama._rope_tables ditto)."""
    cos, sin = rope_frequencies(
        config.resolved_rotary_dim, config.max_seq_len, config.rope_theta
    )
    return jnp.asarray(cos), jnp.asarray(sin)


def _apply_rotary(x, cos, sin, positions, config: GPTConfig):
    rd = config.resolved_rotary_dim
    rope = apply_rope_interleaved if config.rotary_interleaved else apply_rope
    if rd == config.head_dim:
        return rope(x, cos, sin, positions)
    rot = rope(x[..., :rd], cos, sin, positions)
    return jnp.concatenate([rot, x[..., rd:]], axis=-1)


def _attention(config: GPTConfig, q, k, v, mask):
    if config.attention_impl == "flash":
        from ..ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True, segment_mask=mask)
    if config.attention_impl != "dot":
        raise ValueError(
            f"Unknown attention_impl {config.attention_impl!r}; expected 'dot' or 'flash'"
        )
    return dot_product_attention(q, k, v, mask=mask, causal=True)


def _mlp(config: GPTConfig, mlp_params: Params, h: jax.Array) -> jax.Array:
    return mlp_gelu(mlp_params, h, act=activation_fn(config.activation))


def block_forward(
    block: Params,
    x: jax.Array,
    *,
    config: GPTConfig,
    mask: jax.Array | None,
    cos: jax.Array | None = None,
    sin: jax.Array | None = None,
    positions: jax.Array | None = None,
) -> jax.Array:
    from jax.ad_checkpoint import checkpoint_name

    h1 = layer_norm(x, block["ln1_scale"], block["ln1_bias"], config.norm_eps)
    q, k, v = attention_qkv(block["attn"], h1)
    if config.positional == "rotary":
        q = checkpoint_name(_apply_rotary(q, cos, sin, positions, config), "q_rope")
        k = checkpoint_name(_apply_rotary(k, cos, sin, positions, config), "k_rope")
    attn = _attention(config, q, k, v, mask)
    attn_out = checkpoint_name(attention_out(block["attn"], attn), "attn_out")
    if config.parallel_residual:
        h2 = (
            h1
            if config.shared_parallel_norm
            else layer_norm(x, block["ln2_scale"], block["ln2_bias"], config.norm_eps)
        )
        return x + attn_out + checkpoint_name(_mlp(config, block["mlp"], h2), "ffn_out")
    x = x + attn_out
    h2 = layer_norm(x, block["ln2_scale"], block["ln2_bias"], config.norm_eps)
    return x + checkpoint_name(_mlp(config, block["mlp"], h2), "ffn_out")


def _lm_head(params: Params, config: GPTConfig) -> jax.Array:
    return params["wte"].T if config.tie_embeddings else params["lm_head"]


def _logits(params: Params, x: jax.Array, config: GPTConfig) -> jax.Array:
    head = _lm_head(params, config)
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    if "lm_head_bias" in params:
        logits = logits + params["lm_head_bias"].astype(logits.dtype)
    return logits


def forward(
    params: Params,
    tokens: jax.Array,
    config: GPTConfig,
    *,
    positions: jax.Array | None = None,
    mask: jax.Array | None = None,
    return_hidden: bool = False,
) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, vocab). ``return_hidden`` skips
    the logits head (the chunked-loss path projects chunk-by-chunk)."""
    B, S = tokens.shape
    if S > config.max_seq_len:
        # XLA gathers clamp out-of-range rows, which would silently hand
        # every position past the table its last row.
        raise ValueError(f"sequence length {S} exceeds max_seq_len={config.max_seq_len}")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["wte"][tokens]
    if config.positional == "learned":
        x = x + params["wpe"][positions]
        cos = sin = None
    else:
        cos, sin = _rope_tables(config)

    body = partial(
        block_forward, config=config, mask=mask, cos=cos, sin=sin, positions=positions
    )
    if config.remat:
        body = jax.checkpoint(body, policy=remat_policy(config.remat_policy))

    def scan_body(carry, block):
        return body(block, carry), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    x = layer_norm(x, params["lnf_scale"], params["lnf_bias"], config.norm_eps)
    if return_hidden:
        return x
    return _logits(params, x, config)


# ---------------------------------------------------------------- KV cache
def init_cache(
    config: GPTConfig, batch_size: int, max_len: int, dtype=jnp.bfloat16
) -> dict[str, jax.Array]:
    if dtype == jnp.int8:
        raise NotImplementedError(
            "int8 KV caches are implemented for the llama family "
            "(models/llama.py init_cache); the gpt cache path would "
            "silently misread scale-free int8 values."
        )
    # (L, B, T, H*h), heads flattened: the layout of `llama.init_cache`.
    shape = (config.n_layers, batch_size, max_len, config.num_heads * config.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def forward_with_cache(
    params: Params,
    tokens: jax.Array,
    cache: dict[str, jax.Array],
    config: GPTConfig,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Incremental forward (prefill or decode) against the KV cache.

    ``cache['length']`` is a scalar or per-row (B,) cursor — same contract
    as `llama.forward_with_cache` (per-row = speculative decoding), and so is
    ``cache['logits_at']`` (`layers.head_logits`)."""
    B, T_new = tokens.shape
    max_len = cache["k"].shape[2]
    start = cache["length"]
    positions = cache_positions(start, T_new, B)
    cache_pos = jnp.arange(max_len, dtype=jnp.int32)
    mask = cache_pos[None, None, :] <= positions[:, :, None]

    x = params["wte"][tokens]
    if config.positional == "learned":
        x = x + params["wpe"][positions]
        cos = sin = None
    else:
        cos, sin = _rope_tables(config)

    # Same single-query kernel dispatch as llama.forward_with_cache.
    decode_lengths = positions[:, 0] + 1 if T_new == 1 else None

    # Same cache layout as llama.forward_with_cache: the stacked cache rides
    # the scan carry, a step writes its new rows and attends in place.
    def scan_body(carry, block):
        x, kv, i = carry
        h1 = layer_norm(x, block["ln1_scale"], block["ln1_bias"], config.norm_eps)
        q, k, v = attention_qkv(block["attn"], h1)
        if config.positional == "rotary":
            q = _apply_rotary(q, cos, sin, positions, config)
            k = _apply_rotary(k, cos, sin, positions, config)
        kv = cache_append(kv, i, k, v, start)
        attn = cached_attention(q, kv, i, mask=mask, lengths=decode_lengths)
        attn_out = attention_out(block["attn"], attn)
        if config.parallel_residual:
            # The parallel-residual MLP branches off the block input, not the
            # post-attention sum: h1 is the pre-attention norm of the same x.
            h2 = (
                h1
                if config.shared_parallel_norm
                else layer_norm(x, block["ln2_scale"], block["ln2_bias"], config.norm_eps)
            )
            x = x + attn_out + _mlp(config, block["mlp"], h2)
        else:
            x = x + attn_out
            h2 = layer_norm(x, block["ln2_scale"], block["ln2_bias"], config.norm_eps)
            x = x + _mlp(config, block["mlp"], h2)
        return (x, kv, i + 1), None

    kv = {"k": cache["k"], "v": cache["v"]}
    with traced_once_for(config.n_layers):
        (x, kv, _), _ = jax.lax.scan(
            scan_body, (x, kv, jnp.zeros((), jnp.int32)), params["blocks"]
        )
    logits = head_logits(x, cache, lambda h: _logits(
        params, layer_norm(h, params["lnf_scale"], params["lnf_bias"], config.norm_eps), config
    ))
    return logits, dict(kv, length=start + T_new)


@functools.lru_cache(maxsize=16)
def _generator(config: GPTConfig, generation_config: Any, jit_loop: bool):
    from ..generation import GenerationConfig, Generator, cache_dtype

    gcfg = generation_config or GenerationConfig()
    kv_dtype = cache_dtype(gcfg)  # int8 request fails loudly in init_cache
    return Generator(
        lambda p, t, c: forward_with_cache(p, t, c, config),
        lambda b, m: init_cache(config, b, m, dtype=kv_dtype),
        gcfg,
        jit_loop=jit_loop,
    )


def generate(
    params: Params,
    prompt: jax.Array,
    config: GPTConfig,
    *,
    generation_config: Any = None,
    rng: jax.Array | None = None,
    jit_loop: bool = True,
) -> jax.Array:
    gen = _generator(config, generation_config, jit_loop)
    total = prompt.shape[1] + gen.config.max_new_tokens
    if total > config.max_seq_len:
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + max_new_tokens "
            f"({gen.config.max_new_tokens}) = {total} exceeds "
            f"max_seq_len={config.max_seq_len}"
        )
    return gen(params, prompt, rng=rng)


def loss_fn(
    params: Params,
    batch: dict[str, jax.Array],
    config: GPTConfig,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Next-token prediction. batch: {"input_ids": (B, S)} with optional
    "labels" and "attention_mask" (same contract as `llama.loss_fn`)."""
    tokens = batch["input_ids"]
    labels = batch.get("labels")
    attn_mask = batch.get("attention_mask")
    if config.loss_chunk_size:
        from .layers import chunked_lm_loss_from_batch

        x = forward(params, tokens, config, mask=attn_mask, return_hidden=True)
        return chunked_lm_loss_from_batch(
            x, _lm_head(params, config), tokens, labels, attn_mask,
            z_loss=config.z_loss, chunk_size=config.loss_chunk_size,
        )
    logits = forward(params, tokens, config, mask=attn_mask)
    if labels is None:
        labels = tokens[:, 1:]
        loss_mask = attn_mask[:, 1:] if attn_mask is not None else None
        logits = logits[:, :-1]
    else:
        loss_mask = attn_mask
    return cross_entropy_loss(logits, labels, mask=loss_mask, z_loss=config.z_loss)
