"""SmallThinker-style sparse decoder: two kinds of layer, experts routed
from the layer's input.

What sets the family apart from `models/llama.py` (whose helpers it uses):

- **a layer pattern**: ``window_layout[l]`` says whether layer ``l`` attends
  within a sliding window, ``rope_layout[l]`` whether it rotates q and k
  (the published models pair them: a full-attention layer without positional
  term, then three windowed layers with rotary). The pattern is static; the
  layer scan runs over its periods with one period's layers unrolled in the
  body, so depth costs no compile time;
- **dropless top-k experts** (`ops.moe.moe_dropless`): gated experts with a
  ReLU gate, the router reading the layer's *input* (before attention and
  before any norm), top-k weights renormalised. The expert weights are not
  scanned: the stacks are handed whole to the expert kernel with the layer's
  index (an expert stack is most of the model; a layer sliced out of it
  would move those bytes a second time);
- **a cache with two kinds of leaves** (`init_cache`): ``k`` / ``v`` hold
  every position of the full-attention layers, ``k_win`` / ``v_win`` only
  the window of the windowed layers, as rings (`layers.cache_write_stacked`).
  All are ``(L_kind, B, T_kind, K*h)``, so the serving engine's slot
  helpers work on them unchanged. A cached forward may be told how many of
  its new rows are real (``cache["valid"]``, the engine's bucket-padded
  chunks): in a ring a pad tail would land on rows still in the window.

For layer ``l`` with input ``h`` (no bias anywhere):

    r     = h W_r                      # float32; the router reads the INPUT
    a     = rmsnorm(h; g1);  q, k, v = a W_q, a W_k, a W_v
    q, k  = rope(q, k)                 # only where rope_layout[l]
    h'    = h + softmax(q k^T / sqrt(head_dim) on visible) v W_o
    S     = top_k(softmax(r));  w_e = p_e / sum_S p
    m     = rmsnorm(h'; g2)
    out   = h' + sum_{e in S} w_e (relu(m W_g[e]) * (m W_u[e])) W_d[e]

`benchmarks/reference/smallthinker.py` writes the same equations with no
kernel, cache or scan; the tests hold this file to it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.moe import MOE_COUNTS, moe_dropless
from .layers import (
    AttentionSpec,
    apply_rope,
    attention_out,
    attention_qkv,
    cache_append,
    cache_positions,
    cached_attention,
    init_attention,
    note_attention_path,
    position_masked_attention,
    report_step_counts,
    ring_positions,
    traced_once_for,
    rms_norm,
    rope_frequencies,
    truncated_normal_init,
)

Params = Any


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    d_model: int = 2560
    n_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 64
    moe_top_k: int = 6
    d_expert: int = 768
    moe_activation: str = "relu"
    norm_topk_prob: bool = True
    sliding_window: int = 4096
    # One entry a layer: 1 = attends within `sliding_window` / rotates q and k.
    # Empty = every layer the same (no window, rotary everywhere).
    window_layout: tuple[int, ...] = ()
    rope_layout: tuple[int, ...] = ()
    max_seq_len: int = 16384
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # A prefill chunk's queries attend in blocks of this many rows
    # (`layers.position_masked_attention`).
    attention_q_block: int = 256

    def __post_init__(self):
        for name in ("window_layout", "rope_layout"):
            layout = getattr(self, name)
            if layout and len(layout) != self.n_layers:
                raise ValueError(f"{name} has {len(layout)} entries for {self.n_layers} layers")

    @property
    def attention_spec(self) -> AttentionSpec:
        return AttentionSpec(self.d_model, self.num_heads, self.num_kv_heads, self.head_dim)

    @property
    def layer_kinds(self) -> tuple[tuple[bool, bool], ...]:
        """(windowed, rotary) for each layer."""
        window = self.window_layout or (0,) * self.n_layers
        rope = self.rope_layout or (1,) * self.n_layers
        return tuple((bool(w), bool(r)) for w, r in zip(window, rope))

    @property
    def period(self) -> int:
        """Length of the layer pattern: the scan runs over whole periods."""
        kinds = self.layer_kinds
        for p in range(1, self.n_layers + 1):
            if self.n_layers % p == 0 and kinds == kinds[:p] * (self.n_layers // p):
                return p
        return self.n_layers

    @property
    def n_window_layers(self) -> int:
        return sum(w for w, _ in self.layer_kinds)

    @classmethod
    def tiny(cls, **overrides: Any) -> "SmallThinkerConfig":
        """A toy config for tests: two periods of the published pattern."""
        defaults = dict(
            vocab_size=256, d_model=64, n_layers=8, num_heads=4, num_kv_heads=2, head_dim=16,
            n_experts=8, moe_top_k=3, d_expert=32, sliding_window=16,
            window_layout=(0, 1, 1, 1) * 2, rope_layout=(0, 1, 1, 1) * 2,
            max_seq_len=128, rope_theta=10000.0, attention_q_block=8,
        )
        defaults.update(overrides)
        return cls(**defaults)

    def param_count(self) -> int:
        attn = self.d_model * self.head_dim * (2 * self.num_heads + 2 * self.num_kv_heads)
        experts = self.n_experts * 3 * self.d_model * self.d_expert
        block = attn + experts + self.d_model * self.n_experts + 2 * self.d_model
        embed = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        return self.n_layers * block + embed + self.d_model


def init_block(rng: jax.Array, config: SmallThinkerConfig, dtype=jnp.float32) -> Params:
    ka, kr, kg, ku, kd = jax.random.split(rng, 5)
    D, E, F = config.d_model, config.n_experts, config.d_expert
    std_in, std_out = 1.0 / np.sqrt(D), 1.0 / np.sqrt(F)
    return {
        "attn_norm": jnp.zeros((D,), dtype),
        "attn": init_attention(ka, config.attention_spec, dtype),
        "mlp_norm": jnp.zeros((D,), dtype),
        "moe": {
            "router": truncated_normal_init(kr, (D, E), std_in, dtype),
            "w_gate": truncated_normal_init(kg, (E, D, F), std_in, dtype),
            "w_up": truncated_normal_init(ku, (E, D, F), std_in, dtype),
            "w_down": truncated_normal_init(kd, (E, F, D), std_out, dtype),
        },
    }


def init(rng: jax.Array, config: SmallThinkerConfig, dtype=jnp.float32) -> Params:
    """Initialize params; every leaf under ``blocks`` has a leading
    ``n_layers`` axis. Layers are drawn one after another (`lax.map`): the
    f32 samples of one layer's experts are its only temporaries."""
    k_embed, k_blocks, k_out = jax.random.split(rng, 3)
    blocks = jax.lax.map(
        lambda k: init_block(k, config, dtype), jax.random.split(k_blocks, config.n_layers)
    )
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


def _rope_tables(config: SmallThinkerConfig) -> tuple[jax.Array, jax.Array]:
    cos, sin = rope_frequencies(config.head_dim, config.max_seq_len, config.rope_theta)
    return jnp.asarray(cos), jnp.asarray(sin)


def _lm_head(params: Params, config: SmallThinkerConfig) -> jax.Array:
    return params["embed"].T if config.tie_embeddings else params["lm_head"]


def _layer(block, experts, l, x, *, config, rotary, cos, sin, positions, attend):
    """One layer on (B, T, D). ``block`` is the layer's own norms, attention
    and router; ``experts`` the whole expert stacks, read at layer ``l``.
    ``attend(q, k, v) -> (attention output, state)`` is the caller's
    (cache-free, or through one kind of cache)."""
    B, T, D = x.shape
    a = rms_norm(x, block["attn_norm"], config.norm_eps)
    q, k, v = attention_qkv(block["attn"], a)
    if rotary:
        q, k = apply_rope(q, cos, sin, positions), apply_rope(k, cos, sin, positions)
    attn, state = attend(q, k, v)
    after = x + attention_out(block["attn"], attn)
    m = rms_norm(after, block["mlp_norm"], config.norm_eps)
    routed, counts = moe_dropless(
        {"router": block["router"], **experts},
        m.reshape(B * T, D),
        x.reshape(B * T, D),  # the router reads the layer's input
        top_k=config.moe_top_k,
        activation=config.moe_activation,
        renormalize=config.norm_topk_prob,
        layer=l,
    )
    return after + routed.reshape(B, T, D), state, counts


def _scan_periods(params, x, config, state, layer_fn):
    """Run every layer: a scan over the periods of the layer pattern with one
    period's layers unrolled in the body. ``layer_fn(block, experts, p, j, x,
    state) -> (x, state, counts)`` gets the traced index ``p`` of the period
    and the layer's static place ``j`` in it (layer ``p * period + j``).
    Returns (x, state, counts summed over the layers)."""
    P = config.period
    blocks = params["blocks"]
    experts = {name: blocks["moe"][name] for name in ("w_gate", "w_up", "w_down")}
    scanned = {
        "attn_norm": blocks["attn_norm"], "attn": blocks["attn"],
        "mlp_norm": blocks["mlp_norm"], "router": blocks["moe"]["router"],
    }
    scanned = jax.tree.map(lambda a: a.reshape((-1, P) + a.shape[1:]), scanned)
    zero = {name: jnp.zeros((), jnp.int32) for name in MOE_COUNTS}

    def body(carry, period_blocks):
        x, state, counts, p = carry
        for j in range(P):
            block = jax.tree.map(lambda a: a[j], period_blocks)
            x, state, c = layer_fn(block, experts, p, j, x, state)
            counts = {name: counts[name] + c[name] for name in counts}
        return (x, state, counts, p + 1), None

    with traced_once_for(config.n_layers // P):
        (x, state, counts, _), _ = jax.lax.scan(
            body, (x, state, zero, jnp.zeros((), jnp.int32)), scanned
        )
    return x, state, counts


def forward(params: Params, tokens: jax.Array, config: SmallThinkerConfig) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, vocab), cache-free."""
    B, S = tokens.shape
    if S > config.max_seq_len:
        raise ValueError(f"sequence length {S} exceeds max_seq_len={config.max_seq_len}")
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cos, sin = _rope_tables(config)
    kinds, P = config.layer_kinds, config.period

    def layer_fn(block, experts, p, j, x, state):
        windowed, rotary = kinds[j]

        def attend(q, k, v):
            out = position_masked_attention(
                q, k, v, positions, positions,
                window=config.sliding_window if windowed else None,
                q_block=config.attention_q_block,
            )
            return out, state

        return _layer(
            block, experts, p * P + j, x, config=config, rotary=rotary, cos=cos, sin=sin,
            positions=positions, attend=attend,
        )

    x, _, _ = _scan_periods(params, params["embed"][tokens], config, (), layer_fn)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, _lm_head(params, config).astype(x.dtype))


# ---------------------------------------------------------------- KV cache
def init_cache(
    config: SmallThinkerConfig, batch_size: int, max_len: int, dtype=jnp.bfloat16
) -> dict[str, jax.Array]:
    """Decode-time KV cache with one pair of leaves for each kind of layer:
    ``k`` / ``v`` (L_full, B, max_len, K*h) for the full-attention layers,
    ``k_win`` / ``v_win`` (L_window, B, min(window, max_len), K*h) rings for
    the windowed ones (position p in row ``p mod window``). A kind the model
    has no layer of has no leaves."""
    if dtype == jnp.int8:
        raise NotImplementedError("this family's cache is bf16 / fp32; int8 KV is not implemented")
    lanes = config.num_kv_heads * config.head_dim
    n_window = config.n_window_layers
    cache = {}
    if config.n_layers - n_window:
        shape = (config.n_layers - n_window, batch_size, max_len, lanes)
        cache.update(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
    if n_window:
        shape = (n_window, batch_size, min(config.sliding_window, max_len), lanes)
        cache.update(k_win=jnp.zeros(shape, dtype), v_win=jnp.zeros(shape, dtype))
    cache["length"] = jnp.zeros((), jnp.int32)
    return cache


def forward_with_cache(
    params: Params,
    tokens: jax.Array,
    cache: dict[str, jax.Array],
    config: SmallThinkerConfig,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Incremental forward: append ``tokens`` (B, T_new) at ``cache['length']``
    (a scalar, or (B,) per-row cursors) and attend against what is cached.
    Returns (logits, new_cache). ``cache['valid']``, where present, is the
    number of real rows among the new ones (the rest is a bucket's pad tail):
    the rings write only those.

    A decode step (T_new == 1) writes its row and reads both kinds of cache
    through `layers.cached_attention` (the flash-decode kernel, in place, with
    ``min(cursor + 1, window)`` valid rows on a ring). A chunk's full layers
    write and then go through `layers.cached_attention` too, by the cursor
    (the flash-prefill kernel, in place and up to the cursor, or a layer
    sliced out for `layers.position_masked_attention`); a windowed layer
    attends by position against the ring as it stood plus the chunk's own
    rows, and then writes."""
    B, T_new = tokens.shape
    start = cache["length"]
    valid = cache.get("valid")
    positions = cache_positions(start, T_new, B)
    cos, sin = _rope_tables(config)
    kinds = config.layer_kinds
    P = config.period
    # A layer's index within its kind: whole periods before it, then its
    # place among the period's layers of that kind.
    win_in_period = sum(w for w, _ in kinds[:P])
    before = [sum(w for w, _ in kinds[:j]) for j in range(P)]
    kv = {
        False: {n: cache[n] for n in ("k", "v") if n in cache},
        True: {n[0]: cache[n] for n in ("k_win", "v_win") if n in cache},
    }
    decode = T_new == 1
    if decode:
        lengths = {False: positions[:, 0] + 1}
        if kv[True]:
            lengths[True] = jnp.minimum(lengths[False], kv[True]["k"].shape[2])
        masks = {
            w: jnp.arange(kv[w]["k"].shape[2], dtype=jnp.int32)[None, None, :]
            < lengths[w][:, None, None]
            for w in lengths
        }

    def layer_fn(block, experts, p, j, x, state):
        windowed, rotary = kinds[j]
        i = p * win_in_period + before[j] if windowed else p * (P - win_in_period) + j - before[j]

        def attend(q, k, v):
            leaves = state[windowed]
            if decode:
                leaves = cache_append(leaves, i, k, v, start, ring=windowed)
                out = cached_attention(
                    q, leaves, i, mask=masks[windowed], lengths=lengths[windowed]
                )
            elif windowed:
                old = {
                    n: jax.lax.dynamic_index_in_dim(buf, i, 0, keepdims=False).reshape(
                        B, -1, *k.shape[2:]
                    )
                    for n, buf in leaves.items()
                }
                k_pos = jnp.concatenate(
                    [ring_positions(start, old["k"].shape[1], B), positions], axis=1
                )
                out = position_masked_attention(
                    q,
                    jnp.concatenate([old["k"].astype(k.dtype), k], axis=1),
                    jnp.concatenate([old["v"].astype(v.dtype), v], axis=1),
                    positions, k_pos,
                    window=config.sliding_window, q_block=config.attention_q_block,
                )
                note_attention_path("sliced")
                leaves = cache_append(leaves, i, k, v, start, ring=True, valid=valid)
            else:
                leaves = cache_append(leaves, i, k, v, start)
                out = cached_attention(
                    q, leaves, i, start=start, q_block=config.attention_q_block
                )
            return out, {**state, windowed: leaves}

        return _layer(
            block, experts, p * P + j, x, config=config, rotary=rotary, cos=cos, sin=sin,
            positions=positions, attend=attend,
        )

    x, kv, counts = _scan_periods(params, params["embed"][tokens], config, kv, layer_fn)
    report_step_counts(counts)
    new_cache = {"length": start + T_new, **kv[False]}
    new_cache.update({n + "_win": buf for n, buf in kv[True].items()})
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, _lm_head(params, config).astype(x.dtype))
    return logits, new_cache


@functools.lru_cache(maxsize=16)
def _generator(config: SmallThinkerConfig, generation_config: Any, jit_loop: bool):
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
    config: SmallThinkerConfig,
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
