"""The plain reference for SmallThinker-21BA3B-Instruct: `jax.numpy`, float32
at ``jax.default_matmul_precision("highest")``, no kernels, no cache, no
scan, nothing imported from the program under test.

The equations, from the catalog row's `config` and `described_as`
(`/opt/skills/guides/model-configs/architectures.jsonl`, source
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json).
For layer ``l`` with input ``h`` (rows x hidden), ``eps`` 1e-6, no bias:

    r   = h W_r                                  # hidden -> experts; the router reads the layer's INPUT
    a   = rmsnorm(h; g1)
    q, k, v = a W_q, a W_k, a W_v                # heads x head_dim, kv_heads x head_dim twice
    if rope_layout[l] == 1:  q, k = rope(q, k; theta, half-split pairs, position p)
    visible(i, j) = j <= i  and  (sliding_window_layout[l] == 0  or  i - j < sliding_window_size)
    h'  = h + softmax(q k^T / sqrt(head_dim) on visible) v W_o          # GQA
    p   = softmax(r);  S = the k largest;  w_e = p_e / sum_S p          # norm_topk_prob
    m   = rmsnorm(h'; g2)
    out = h' + sum_{e in S} w_e * (relu(m W_g[e]) * (m W_u[e])) W_d[e]  # sparse ReGLU

then ``rmsnorm(.; g)`` and an untied head. What the catalog does not settle
(the configuration's file lists both under ``assumed``): the router's input
is the un-normalised ``h`` (`Arch.router_input` = ``"input"``; ``"normed"``
feeds it ``a`` instead, the what-if that tells the two apart), and there are
no secondary experts. Layout only: weights are ``(in, out)`` matrices with
heads flattened into the out axis; a norm's weight multiplies directly.

It walks one row and one layer at a time: `get_layer(i)` hands it layer ``i``
with the norm, attention and router weights in float32 and the three expert
stacks ``(E, in, out)`` in the type they are stored in, cast to float32 one
expert at a time where they are used (exact). Every expert is computed for
all rows and kept for its routed rows by a mask; attention runs in query
blocks, a windowed layer's block over the keys its band can reach.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

Layer = dict[str, jax.Array]
GetLayer = Callable[[int], Layer]
EXPERT_BLOCK = 8  # experts computed in one einsum


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the equations need, under their published names."""

    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    moe_num_primary_experts: int
    moe_num_active_primary_experts: int
    moe_ffn_hidden_size: int
    sliding_window_size: int
    sliding_window_layout: tuple[int, ...]
    rope_layout: tuple[int, ...]
    rope_theta: float
    rms_norm_eps: float
    norm_topk_prob: bool = True
    activation: str = "relu"  # "silu" is a what-if
    router_input: str = "input"  # "normed" is a what-if

    @classmethod
    def from_config(cls, config: dict[str, Any]) -> "Arch":
        if not config["moe_primary_router_apply_softmax"]:
            raise ValueError("the reference writes the softmax router only")
        layers = config["num_hidden_layers"]  # a depth-cut model takes the layouts' first entries
        return cls(
            hidden_size=config["hidden_size"],
            num_hidden_layers=layers,
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            vocab_size=config["vocab_size"],
            moe_num_primary_experts=config["moe_num_primary_experts"],
            moe_num_active_primary_experts=config["moe_num_active_primary_experts"],
            moe_ffn_hidden_size=config["moe_ffn_hidden_size"],
            sliding_window_size=config["sliding_window_size"],
            sliding_window_layout=tuple(config["sliding_window_layout"][:layers]),
            rope_layout=tuple(config["rope_layout"][:layers]),
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
            norm_topk_prob=bool(config["norm_topk_prob"]),
        )


def _highest(fn):
    """Trace ``fn`` under "highest" matmul precision (on a TPU a float32
    matmul is otherwise done in bf16 passes)."""

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return traced


def rope_tables(arch: Arch, seq_len: int) -> tuple[np.ndarray, np.ndarray]:
    half = np.arange(0, arch.head_dim, 2, dtype=np.float64) / arch.head_dim
    inv_freq = 1.0 / (arch.rope_theta**half)
    angles = np.outer(np.arange(seq_len, dtype=np.float64), inv_freq)
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x ``(S, heads, head_dim)``; dimension i pairs with i + head_dim/2."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window: int | None, q_block: int) -> jax.Array:
    """q ``(S, H, h)``, k and v ``(S, K, h)``; query head j reads key/value
    head ``j // (H / K)``. Query rows s0.. see keys up to their own and, with
    ``window``, no key more than ``window - 1`` rows back: a block is given
    just the keys it can reach, which is exact."""
    S, H, h = q.shape
    K = k.shape[1]
    q = q.reshape(S, K, H // K, h)
    out = []
    for s0 in range(0, S, q_block):
        s1 = min(s0 + q_block, S)
        t0 = 0 if window is None else max(0, s0 - window + 1)
        rows = jnp.arange(s0, s1)[:, None]
        cols = jnp.arange(t0, s1)[None, :]
        visible = cols <= rows
        if window is not None:
            visible = visible & (rows - cols < window)
        scores = jnp.einsum("skgh,tkh->kgst", q[s0:s1], k[t0:s1]) / math.sqrt(h)
        probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgst,tkh->skgh", probs, v[t0:s1]))
    return jnp.concatenate(out, axis=0).reshape(S, H * h)


def experts(arch: Arch, p: Layer, m: jax.Array, r: jax.Array) -> jax.Array:
    """The routed experts' sum for rows ``m`` (S, D) with router logits
    ``r`` (S, E): every expert over all rows, kept where it was chosen."""
    E, k = arch.moe_num_primary_experts, arch.moe_num_active_primary_experts
    probs = jax.nn.softmax(r, axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, E - k][:, None]
    chosen = probs >= kth  # the k largest (ties are of measure zero in float32)
    weight = jnp.where(chosen, probs, 0.0)
    if arch.norm_topk_prob:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[arch.activation]
    out = jnp.zeros_like(m)
    for e0 in range(0, E, EXPERT_BLOCK):  # a few experts at a time: the same sums, fewer operations to compile
        e1 = min(e0 + EXPERT_BLOCK, E)
        gate = jnp.einsum("sd,edf->esf", m, p["experts_gate"][e0:e1].astype(jnp.float32))
        up = jnp.einsum("sd,edf->esf", m, p["experts_up"][e0:e1].astype(jnp.float32))
        hidden = act(gate) * up * weight[:, e0:e1].T[:, :, None]
        out = out + jnp.einsum("esf,efd->sd", hidden, p["experts_down"][e0:e1].astype(jnp.float32))
    return out


def decoder_layer(arch: Arch, windowed: bool, rotary: bool, q_block: int, p: Layer, x, cos, sin):
    """One layer on one row. x ``(S, D)`` float32."""
    S = x.shape[0]
    H, K, h = arch.num_attention_heads, arch.num_key_value_heads, arch.head_dim
    a = rms_norm(x, p["input_layernorm"], arch.rms_norm_eps)
    r = (x if arch.router_input == "input" else a) @ p["router"]
    q = (a @ p["q_proj"]).reshape(S, H, h)
    k = (a @ p["k_proj"]).reshape(S, K, h)
    v = (a @ p["v_proj"]).reshape(S, K, h)
    if rotary:
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    window = arch.sliding_window_size if windowed else None
    x = x + attention(q, k, v, window, q_block) @ p["o_proj"]
    m = rms_norm(x, p["post_attention_layernorm"], arch.rms_norm_eps)
    return x + experts(arch, p, m, r)


class Decoder:
    """The jitted pieces for one architecture. ``top`` is
    ``{"embed_tokens": (V, D), "norm": (D,), "lm_head": (D, V)}``: the norm
    in float32, the two tables in the type they are stored in, read only as
    ``embed[ids]`` and ``head[:, c0:c1]`` and cast where they are used."""

    def __init__(self, arch: Arch, *, q_block: int = 512, vocab_block: int = 16384):
        self.arch = arch
        self.vocab_block = vocab_block
        # One jitted layer for each kind of layer the layouts hold.
        self._layers = {
            kind: jax.jit(_highest(functools.partial(decoder_layer, arch, *kind, q_block)))
            for kind in set(zip(map(bool, arch.sliding_window_layout), map(bool, arch.rope_layout)))
        }
        self._norm = jax.jit(_highest(lambda w, x: rms_norm(x, w, arch.rms_norm_eps)))
        self._logits = jax.jit(_highest(lambda w, x: x @ w.astype(jnp.float32)))

    @classmethod
    @functools.lru_cache(maxsize=None)
    def of(cls, arch: Arch) -> "Decoder":
        return cls(arch)

    def forward_logits(self, get_layer: GetLayer, top, tokens, positions) -> list[np.ndarray]:
        """Logits at ``positions[r]`` (a slice or index array) of row ``r``
        of ``tokens`` after a full forward over the row. Rows may be
        right-padded to a common length: earlier positions do not see it."""
        arch = self.arch
        tokens = np.asarray(tokens)
        cos, sin = (jnp.asarray(t) for t in rope_tables(arch, tokens.shape[1]))
        rows = [top["embed_tokens"][jnp.asarray(row)].astype(jnp.float32) for row in tokens]
        for i in range(arch.num_hidden_layers):
            p = get_layer(i)
            layer = self._layers[bool(arch.sliding_window_layout[i]), bool(arch.rope_layout[i])]
            rows = [layer(p, x, cos, sin) for x in rows]
        out = []
        head, V, step = top["lm_head"], arch.vocab_size, self.vocab_block
        for x, where in zip(rows, positions):
            hidden = self._norm(top["norm"], x[where])
            blocks = [
                np.asarray(self._logits(head[:, c : min(c + step, V)], hidden))
                for c in range(0, V, step)
            ]
            out.append(np.concatenate(blocks, axis=-1))
        return out
