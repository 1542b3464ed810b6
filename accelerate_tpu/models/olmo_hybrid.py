"""Olmo-Hybrid-style decoder: gated-delta-rule linear attention in most
layers, full softmax attention in the rest.

What sets the family apart from `models/llama.py` (whose helpers it uses):

- **a layer pattern**: ``layer_types[l]`` is ``"linear_attention"`` or
  ``"full_attention"`` (published: three linear layers, then a full one).
  The pattern is static; the layer scan runs over its periods with one
  period's layers unrolled in the body, over two stacks of block weights
  (``linear`` and ``full``), as `models/smallthinker.py` scans its kinds;
- **the linear mixer** is Gated DeltaNet (`ops/gated_delta.py`): a width-4
  causal depthwise convolution and silu on the q, k and v projections, unit
  keys, a decay ``alpha = exp(-exp(A_log) softplus(a + dt_bias))`` and a
  write strength ``beta = 2 sigmoid(b)`` (the doubling is the published
  ``linear_allow_neg_eigval``) for each head, the rule's state read by the query,
  an rmsnorm over each head's output gated by ``silu(z)``;
- **the full mixer** is multi-head softmax attention with an rmsnorm over the
  whole q and the whole k projection (QK-norm) and no rotary term: position
  comes from the recurrent layers;
- **the block** is the Olmo family's reordered norm:
  ``h = x + rmsnorm(mixer(x))``, ``out = h + rmsnorm(mlp(h))``;
- **a cache with state leaves** (`init_cache`): ``k`` / ``v`` rows for the
  full layers, and for the linear layers ``state_gdn`` (L_linear, B, H, d_k,
  d_v) float32 and ``state_conv`` (L_linear, B, 3, channels), the last three
  inputs of the convolution. State leaves (`layers.is_state_leaf`) have no
  row axis: a cached forward is told how many of its new rows are real
  (``cache["valid"]``: a pad tail advances neither) and, on a decode step,
  which rows are decoding (``cache["decoding"]``: the others keep theirs).

`benchmarks/reference/olmo_hybrid.py` writes the same equations with no
kernel, cache, chunkwise form or scan; the tests hold this file to it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import gated_delta
from .layers import (
    AttentionSpec,
    attention_out,
    attention_qkv,
    cache_append,
    cache_positions,
    cached_attention,
    gated_mlp,
    head_logits,
    init_attention,
    init_swiglu,
    matmul_einsum,
    position_masked_attention,
    report_step_counts,
    rms_norm,
    traced_once_for,
    truncated_normal_init,
)

Params = Any
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    d_model: int = 3840
    d_ff: int = 11008
    n_layers: int = 32
    num_heads: int = 30
    num_kv_heads: int = 30
    head_dim: int = 128
    # One entry a layer; empty = three linear layers then a full one, repeated.
    layer_types: tuple[str, ...] = ()
    linear_heads: int = 30  # key heads = value heads
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_kernel: int = 4
    max_seq_len: int = 65536
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # A prefill chunk's queries attend in blocks of this many rows.
    attention_q_block: int = 256

    def __post_init__(self):
        if self.layer_types and len(self.layer_types) != self.n_layers:
            raise ValueError(f"layer_types has {len(self.layer_types)} entries for {self.n_layers} layers")
        unknown = set(self.kinds) - {LINEAR, FULL}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")

    @property
    def kinds(self) -> tuple[str, ...]:
        if self.layer_types:
            return self.layer_types
        return tuple(FULL if l % 4 == 3 else LINEAR for l in range(self.n_layers))

    @property
    def period(self) -> int:
        kinds = self.kinds
        for p in range(1, self.n_layers + 1):
            if self.n_layers % p == 0 and kinds == kinds[:p] * (self.n_layers // p):
                return p
        return self.n_layers

    @property
    def n_linear_layers(self) -> int:
        return sum(k == LINEAR for k in self.kinds)

    @property
    def attention_spec(self) -> AttentionSpec:
        return AttentionSpec(self.d_model, self.num_heads, self.num_kv_heads, self.head_dim)

    @property
    def conv_channels(self) -> int:
        return self.linear_heads * (2 * self.linear_key_dim + self.linear_value_dim)

    @classmethod
    def tiny(cls, **overrides: Any) -> "OlmoHybridConfig":
        """A toy config for tests: two periods of the published pattern."""
        defaults = dict(
            vocab_size=256, d_model=64, d_ff=96, n_layers=8, num_heads=4, num_kv_heads=4,
            head_dim=16, linear_heads=4, linear_key_dim=8, linear_value_dim=16,
            max_seq_len=256, attention_q_block=8,
        )
        defaults.update(overrides)
        return cls(**defaults)

    def param_count(self) -> int:
        D, H, dk, dv = self.d_model, self.linear_heads, self.linear_key_dim, self.linear_value_dim
        mlp = 3 * D * self.d_ff + 2 * D
        linear = D * (self.conv_channels + H * dv + 2 * H) + H * dv * D
        linear += self.conv_kernel * self.conv_channels + 2 * H + dv
        full = D * self.head_dim * (2 * self.num_heads + 2 * self.num_kv_heads)
        full += self.head_dim * (self.num_heads + self.num_kv_heads)
        embed = self.vocab_size * D * (1 if self.tie_embeddings else 2)
        n_lin = self.n_linear_layers
        return n_lin * (linear + mlp) + (self.n_layers - n_lin) * (full + mlp) + embed + D


def _init_shared(rng, config: OlmoHybridConfig, dtype) -> Params:
    return {
        "mixer_norm": jnp.zeros((config.d_model,), dtype),
        "mlp": init_swiglu(rng, config.d_model, config.d_ff, dtype),
        "mlp_norm": jnp.zeros((config.d_model,), dtype),
    }


def init_linear_block(rng: jax.Array, config: OlmoHybridConfig, dtype=jnp.float32) -> Params:
    kq, kg, ka, kc, ko, km, kd = jax.random.split(rng, 7)
    D, H, dv = config.d_model, config.linear_heads, config.linear_value_dim
    std = 1.0 / np.sqrt(D)
    # dt_bias: softplus^-1 of a step log-uniform on (1e-3, 1e-1); A on (1, 16):
    # the mixer family's usual start, decays near 1.
    dt = jnp.exp(jax.random.uniform(kd, (H,), minval=np.log(1e-3), maxval=np.log(1e-1)))
    return {
        "w_qkv": truncated_normal_init(kq, (D, config.conv_channels), std, dtype),
        "w_gate": truncated_normal_init(kg, (D, H * dv), std, dtype),
        "w_ab": truncated_normal_init(ka, (D, 2 * H), std, dtype),
        "conv": truncated_normal_init(
            kc, (config.conv_kernel, config.conv_channels), 1.0 / np.sqrt(config.conv_kernel), dtype
        ),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
        "out_norm": jnp.zeros((dv,), dtype),
        "w_out": truncated_normal_init(ko, (H * dv, D), 1.0 / np.sqrt(H * dv), dtype),
        **_init_shared(km, config, dtype),
    }


def init_full_block(rng: jax.Array, config: OlmoHybridConfig, dtype=jnp.float32) -> Params:
    ka, km = jax.random.split(rng)
    return {
        "attn": init_attention(ka, config.attention_spec, dtype),
        "q_norm": jnp.zeros((config.num_heads * config.head_dim,), dtype),
        "k_norm": jnp.zeros((config.num_kv_heads * config.head_dim,), dtype),
        **_init_shared(km, config, dtype),
    }


def init(rng: jax.Array, config: OlmoHybridConfig, dtype=jnp.float32) -> Params:
    """Initialize params. ``linear`` and ``full`` hold the blocks of each
    kind, every leaf with a leading axis of that kind's layers, in layer
    order. Layers are drawn one after another (`lax.map`)."""
    k_embed, k_lin, k_full, k_out = jax.random.split(rng, 4)
    n_lin = config.n_linear_layers
    params = {
        "embed": truncated_normal_init(k_embed, (config.vocab_size, config.d_model), 1.0, dtype),
        "linear": jax.lax.map(
            lambda k: init_linear_block(k, config, dtype), jax.random.split(k_lin, n_lin)
        ),
        "full": jax.lax.map(
            lambda k: init_full_block(k, config, dtype),
            jax.random.split(k_full, config.n_layers - n_lin),
        ),
        "final_norm": jnp.zeros((config.d_model,), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = truncated_normal_init(
            k_out, (config.d_model, config.vocab_size), 1.0 / np.sqrt(config.d_model), dtype
        )
    return params


def _lm_head(params: Params, config: OlmoHybridConfig) -> jax.Array:
    return params["embed"].T if config.tie_embeddings else params["lm_head"]


# ------------------------------------------------------------------- mixers
def _l2_normalize(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def _linear_inputs(block: Params, x: jax.Array, tail: jax.Array, config: OlmoHybridConfig):
    """x (B, T, D) and the convolution's tail (B, 3, channels) -> the rule's
    operands in float32: q, k (B, T, H, d_k), v (B, T, H, d_v), g and beta
    (B, T, H); and the projection the next tail is cut from."""
    B, T, _ = x.shape
    H, dk, dv = config.linear_heads, config.linear_key_dim, config.linear_value_dim
    proj = matmul_einsum("btd,dc->btc", x, block["w_qkv"])
    c = jax.nn.silu(gated_delta.causal_conv(proj, tail, block["conv"]))
    q, k, v = jnp.split(c, [H * dk, 2 * H * dk], axis=-1)
    q = _l2_normalize(q.reshape(B, T, H, dk)) * (dk**-0.5)
    k = _l2_normalize(k.reshape(B, T, H, dk))
    ab = matmul_einsum("btd,dc->btc", x, block["w_ab"]).astype(jnp.float32)
    a, b = ab[..., :H], ab[..., H:]
    g = -jnp.exp(block["A_log"].astype(jnp.float32)) * jax.nn.softplus(a + block["dt_bias"])
    beta = 2.0 * jax.nn.sigmoid(b)
    return (q, k, v.reshape(B, T, H, dv), g, beta), proj


def _linear_output(block: Params, x: jax.Array, o: jax.Array, config: OlmoHybridConfig):
    """The rule's output o (B, T, H, d_v) float32, normed head by head, gated
    by silu(z) and projected back to (B, T, D)."""
    B, T, H, dv = o.shape
    z = matmul_einsum("btd,dc->btc", x, block["w_gate"]).astype(jnp.float32).reshape(B, T, H, dv)
    y = rms_norm(o, block["out_norm"], config.norm_eps) * jax.nn.silu(z)
    return matmul_einsum("btc,cd->btd", y.reshape(B, T, H * dv).astype(x.dtype), block["w_out"])


def _qk_normed(block: Params, x: jax.Array, config: OlmoHybridConfig):
    q, k, v = attention_qkv(block["attn"], x)
    flat = lambda a, w: rms_norm(a.reshape(a.shape[:2] + (-1,)), w, config.norm_eps).reshape(a.shape)
    return flat(q, block["q_norm"]), flat(k, block["k_norm"]), v


def _finish_block(block: Params, x: jax.Array, mixed: jax.Array, config: OlmoHybridConfig):
    h = x + rms_norm(mixed, block["mixer_norm"], config.norm_eps)
    return h + rms_norm(gated_mlp(block["mlp"], h), block["mlp_norm"], config.norm_eps)


def _scan_periods(params, x, config, state, layer_fn):
    """Run every layer: a scan over the periods of the layer pattern with one
    period's layers unrolled in the body. ``layer_fn(kind, block, i, x,
    state) -> (x, state)`` gets the layer's block and its traced index ``i``
    among the layers of its kind. Each block is indexed out of its kind's
    whole stack, one layer at a time: handed a period's slice as the scan's
    ``xs``, XLA copies the period's weights before it reads them (1.5 GB of
    temporaries a decode step at the published widths, the weights moved
    three times)."""
    P, kinds = config.period, config.kinds
    per = {kind: sum(k == kind for k in kinds[:P]) for kind in (LINEAR, FULL)}
    stacks = {LINEAR: params["linear"], FULL: params["full"]}
    before = [sum(k == kinds[j] for k in kinds[:j]) for j in range(P)]

    def body(carry, _):
        x, state, p = carry
        for j in range(P):
            kind = kinds[j]
            i = p * per[kind] + before[j]
            block = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stacks[kind]
            )
            x, state = layer_fn(kind, block, i, x, state)
        return (x, state, p + 1), None

    with traced_once_for(config.n_layers // P):
        (x, state, _), _ = jax.lax.scan(
            body, (x, state, jnp.zeros((), jnp.int32)), None, length=config.n_layers // P
        )
    return x, state


def forward(params: Params, tokens: jax.Array, config: OlmoHybridConfig) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, vocab), cache-free."""
    B, S = tokens.shape
    if S > config.max_seq_len:
        raise ValueError(f"sequence length {S} exceeds max_seq_len={config.max_seq_len}")
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    H, dk, dv = config.linear_heads, config.linear_key_dim, config.linear_value_dim

    def layer_fn(kind, block, i, x, state):
        if kind == LINEAR:
            tail = jnp.zeros((B, config.conv_kernel - 1, config.conv_channels), x.dtype)
            operands, _ = _linear_inputs(block, x, tail, config)
            o, _ = gated_delta.chunk_gated_delta(*operands, jnp.zeros((B, H, dk, dv), jnp.float32))
            mixed = _linear_output(block, x, o, config)
        else:
            q, k, v = _qk_normed(block, x, config)
            attn = position_masked_attention(
                q, k, v, positions, positions, q_block=config.attention_q_block
            )
            mixed = attention_out(block["attn"], attn)
        return _finish_block(block, x, mixed, config), state

    x, _ = _scan_periods(params, params["embed"][tokens], config, (), layer_fn)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, _lm_head(params, config).astype(x.dtype))


# -------------------------------------------------------------------- cache
def init_cache(
    config: OlmoHybridConfig, batch_size: int, max_len: int, dtype=jnp.bfloat16
) -> dict[str, jax.Array]:
    """Decode-time cache: ``k`` / ``v`` (L_full, B, max_len, K*h) rows for the
    full-attention layers; for the linear layers the state leaves
    ``state_gdn`` (L_linear, B, H, d_k, d_v) float32 and ``state_conv``
    (L_linear, B, conv_kernel - 1, channels) in ``dtype``. A kind the model
    has no layer of has no leaves."""
    if dtype == jnp.int8:
        raise NotImplementedError("this family's cache is bf16 / fp32; int8 KV is not implemented")
    n_lin = config.n_linear_layers
    cache = {}
    if config.n_layers - n_lin:
        shape = (config.n_layers - n_lin, batch_size, max_len, config.num_kv_heads * config.head_dim)
        cache.update(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
    if n_lin:
        H, dk, dv = config.linear_heads, config.linear_key_dim, config.linear_value_dim
        cache["state_gdn"] = jnp.zeros((n_lin, batch_size, H, dk, dv), jnp.float32)
        cache["state_conv"] = jnp.zeros(
            (n_lin, batch_size, config.conv_kernel - 1, config.conv_channels), dtype
        )
    cache["length"] = jnp.zeros((), jnp.int32)
    return cache


def decode_state(operands, S, i, decoding):
    """One token a row through layer ``i`` of the state stack ``S``: the
    kernel in place where it may run, else one layer sliced out, updated and
    written back. Returns (o (B, 1, H, d_v), stack, rows touched)."""
    from ..native.pallas.gated_delta import maybe_gdn_decode, slots_touched

    q, k, v, g, beta = (a[:, 0] for a in operands)
    alpha = jnp.exp(g)
    done = maybe_gdn_decode(q, k, v, alpha, beta, S, i, decoding)
    live = jnp.ones((q.shape[0],), bool) if decoding is None else decoding
    in_place = done is not None
    if not in_place:
        old = jax.lax.dynamic_index_in_dim(S, i, 0, keepdims=False)
        o, new = gated_delta.recurrent_step(q, k, v, alpha, beta, old)
        new = jnp.where(live[:, None, None, None], new, old)
        o = jnp.where(live[:, None, None], o, 0.0)  # as the kernel leaves the rows it skips
        done = o, jax.lax.dynamic_update_index_in_dim(S, new, i, 0)
    touched = slots_touched(live, q.shape[0], in_place=in_place)
    return done[0][:, None], done[1], touched


def forward_with_cache(
    params: Params,
    tokens: jax.Array,
    cache: dict[str, jax.Array],
    config: OlmoHybridConfig,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Incremental forward: append ``tokens`` (B, T_new) at ``cache['length']``
    (a scalar, or (B,) per-row cursors). Returns (logits, new_cache).

    ``cache['valid']``, where present, is the number of real rows among the
    new ones (the rest is a bucket's pad tail): the states and the
    convolution's tail advance over those only. ``cache['decoding']``, where
    present on a decode step, is the (B,) mask of the rows that are decoding:
    the others' states stay as they are (a row in mid-prefill rides along in
    the engine's decode steps, and a state has no cursor to hide behind).
    ``cache['logits_at']``, where present, names the one row of each batch
    row whose logits are wanted (`layers.head_logits`).

    A decode step (T_new == 1) runs the rule's recurrent form on the state
    stack in place (`gdn_decode`) and `layers.cached_attention` (the
    flash-decode kernel); a chunk runs the chunkwise form and, after its
    write, attends against the full layers' buffers through
    `layers.cached_attention` by the cursor (the flash-prefill kernel)."""
    B, T_new = tokens.shape
    start = cache["length"]
    valid, decoding = cache.get("valid"), cache.get("decoding")
    positions = cache_positions(start, T_new, B)
    decode = T_new == 1
    kv = {n: cache[n] for n in ("k", "v") if n in cache}
    carry = {"kv": kv, "touched": jnp.zeros((), jnp.int32)}
    if "state_gdn" in cache:
        carry.update(S=cache["state_gdn"], conv=cache["state_conv"])
    if decode:
        lengths = positions[:, 0] + 1
        if kv:
            mask = jnp.arange(kv["k"].shape[2], dtype=jnp.int32)[None, None, :] < lengths[:, None, None]
        live = None if decoding is None else decoding[:, None, None]
    real = None if valid is None else (jnp.arange(T_new) < valid)[None, :, None]

    def layer_fn(kind, block, i, x, state):
        if kind == FULL:
            q, k, v = _qk_normed(block, x, config)
            leaves = cache_append(state["kv"], i, k, v, start)
            if decode:
                attn = cached_attention(q, leaves, i, mask=mask, lengths=lengths)
            else:
                attn = cached_attention(
                    q, leaves, i, start=start, q_block=config.attention_q_block
                )
            mixed = attention_out(block["attn"], attn)
            return _finish_block(block, x, mixed, config), {**state, "kv": leaves}
        tail = jax.lax.dynamic_index_in_dim(state["conv"], i, 0, keepdims=False)
        operands, proj = _linear_inputs(block, x, tail, config)
        new_tail = gated_delta.conv_tail(proj, tail, valid)
        touched = state["touched"]
        if decode:
            if live is not None:
                new_tail = jnp.where(live, new_tail, tail)
            o, S, n = decode_state(operands, state["S"], i, decoding)
            touched = touched + n
        else:
            q, k, v, g, beta = operands
            if real is not None:  # a pad row decays nothing and writes nothing
                g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
            old = jax.lax.dynamic_index_in_dim(state["S"], i, 0, keepdims=False)
            o, new = gated_delta.chunk_gated_delta(q, k, v, g, beta, old)
            S = jax.lax.dynamic_update_index_in_dim(state["S"], new, i, 0)
        conv = jax.lax.dynamic_update_index_in_dim(state["conv"], new_tail.astype(tail.dtype), i, 0)
        mixed = _linear_output(block, x, o, config)
        return _finish_block(block, x, mixed, config), {**state, "S": S, "conv": conv, "touched": touched}

    x, carry = _scan_periods(params, params["embed"][tokens], config, carry, layer_fn)
    new_cache = {"length": start + T_new, **carry["kv"]}
    if "S" in carry:
        new_cache.update(state_gdn=carry["S"], state_conv=carry["conv"])
        if decode:
            report_step_counts({"state_slots_touched": carry["touched"]})
    logits = head_logits(x, cache, lambda h: jnp.einsum(
        "bsd,dv->bsv",
        rms_norm(h, params["final_norm"], config.norm_eps),
        _lm_head(params, config).astype(h.dtype),
    ))
    return logits, new_cache


@functools.lru_cache(maxsize=16)
def _generator(config: OlmoHybridConfig, generation_config: Any, jit_loop: bool):
    from ..generation import GenerationConfig, Generator, cache_dtype

    gcfg = generation_config or GenerationConfig()
    kv_dtype = cache_dtype(gcfg)
    return Generator(
        lambda p, t, c: forward_with_cache(p, t, c, config),
        lambda b, m: init_cache(config, b, m, dtype=kv_dtype),
        gcfg,
        jit_loop=jit_loop,
    )


def generate(
    params: Params,
    prompt: jax.Array,
    config: OlmoHybridConfig,
    *,
    generation_config: Any = None,
    rng: jax.Array | None = None,
    jit_loop: bool = True,
) -> jax.Array:
    """Autoregressive generation for this family (see `llama.generate`)."""
    gen = _generator(config, generation_config, jit_loop)
    total = prompt.shape[1] + gen.config.max_new_tokens
    if total > config.max_seq_len:
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + max_new_tokens ({gen.config.max_new_tokens}) = "
            f"{total} exceeds max_seq_len={config.max_seq_len}"
        )
    return gen(params, prompt, rng=rng)
