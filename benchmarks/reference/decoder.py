"""The plain reference: one pre-norm decoder (GQA, RoPE with rotate-half
pairing, SwiGLU, RMSNorm, optional q/k/v biases, untied head) in `jax.numpy`
and float32 at ``jax.default_matmul_precision("highest")``.

No kernels, no cache, no scan, nothing imported from the program under test.
It serves Mistral-7B-v0.3 and Qwen2.5-7B (the latter sets `qkv_bias`), follows
the published `modeling_mistral.py` / `modeling_qwen2.py` forward, and departs
from them in nothing but layout:

- weights are ``(in, out)`` matrices with heads flattened into the out axis
  (the HF files hold ``(out, in)``);
- a norm's weight multiplies directly (``x / rms * g``), as published.

It walks the layers one at a time: `get_layer(i)` hands it layer ``i`` as a
dict of float32 arrays, so only one layer's float32 copy is alive at once and
the whole thing fits beside the program's own state on a 16 GB chip.
Attention may run in query blocks (`q_block`) so that a 4096-token sequence
never holds a full ``heads x S x S`` score tensor; blocks are exact, not an
approximation (each query row still sees every earlier key).

Two entry points:

- `forward_logits`: logits at chosen positions of each sequence (serving
  check: a full forward over prompt + served tokens);
- `loss_and_grad_norm`: mean next-token cross entropy over a batch and the
  global L2 norm of its gradient with respect to every parameter, without
  ever holding a whole gradient: a per-layer `jax.vjp` loop that keeps each
  layer's input, each layer's squared gradient norm and, whole, only the
  gradients of the norm weights (two vectors a layer and the final one).
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


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the equations need, under their published names."""

    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    vocab_size: int
    rope_theta: float
    rms_norm_eps: float
    qkv_bias: bool = False

    @classmethod
    def from_config(cls, config: dict[str, Any]) -> "Arch":
        heads = config["num_attention_heads"]
        return cls(
            hidden_size=config["hidden_size"],
            num_hidden_layers=config["num_hidden_layers"],
            num_attention_heads=heads,
            num_key_value_heads=config["num_key_value_heads"],
            head_dim=config.get("head_dim") or config["hidden_size"] // heads,
            intermediate_size=config["intermediate_size"],
            vocab_size=config["vocab_size"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
            qkv_bias=bool(config.get("program", {}).get("qkv_bias", False)),
        )


def _highest(fn):
    """Jit ``fn`` so that it always traces under "highest" matmul precision
    (on a TPU a float32 matmul is otherwise done in bf16 passes)."""

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return traced


def rope_tables(arch: Arch, seq_len: int) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin of position x inverse frequency, ``(S, head_dim / 2)``."""
    half = np.arange(0, arch.head_dim, 2, dtype=np.float64) / arch.head_dim
    inv_freq = 1.0 / (arch.rope_theta**half)
    angles = np.outer(np.arange(seq_len, dtype=np.float64), inv_freq)
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x ``(B, S, heads, head_dim)``; dimension i pairs with i + head_dim/2."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend_block(q, k, v, first_row: int):
    """Causal softmax attention of query rows ``first_row ..`` over the keys
    ``0 ..``. q ``(B, s, K, g, h)``; k, v ``(B, T, K, h)``."""
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k) / math.sqrt(q.shape[-1])
    rows = first_row + jnp.arange(q.shape[1])
    visible = jnp.arange(k.shape[1])[None, :] <= rows[:, None]
    scores = jnp.where(visible[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgst,btkh->bskgh", probs, v)


def attention(q, k, v, q_block: int | None) -> jax.Array:
    """q ``(B, S, H, h)``, k and v ``(B, S, K, h)`` with ``H = K * g``:
    query head ``j`` reads key/value head ``j // g`` (HF `repeat_kv`)."""
    B, S, H, h = q.shape
    K = k.shape[2]
    q = q.reshape(B, S, K, H // K, h)
    if not q_block or q_block >= S:
        out = _attend_block(q, k, v, 0)
    else:
        # Rows s0.. see keys 0..s0+block only; a block is recomputed in the
        # backward pass instead of keeping its scores.
        block = jax.checkpoint(_attend_block, static_argnums=(3,))
        out = jnp.concatenate(
            [
                block(q[:, s0 : s0 + q_block], k[:, : s0 + q_block], v[:, : s0 + q_block], s0)
                for s0 in range(0, S, q_block)
            ],
            axis=1,
        )
    return out.reshape(B, S, H * h)


def decoder_layer(arch: Arch, q_block: int | None, p: Layer, x, cos, sin) -> jax.Array:
    """One published decoder layer. x ``(B, S, D)`` float32."""
    B, S, _ = x.shape
    H, K, h = arch.num_attention_heads, arch.num_key_value_heads, arch.head_dim
    y = rms_norm(x, p["input_layernorm"], arch.rms_norm_eps)
    q, k, v = y @ p["q_proj"], y @ p["k_proj"], y @ p["v_proj"]
    if arch.qkv_bias:
        q, k, v = q + p["q_bias"], k + p["k_bias"], v + p["v_bias"]
    q = _rope(q.reshape(B, S, H, h), cos, sin)
    k = _rope(k.reshape(B, S, K, h), cos, sin)
    x = x + attention(q, k, v.reshape(B, S, K, h), q_block) @ p["o_proj"]
    y = rms_norm(x, p["post_attention_layernorm"], arch.rms_norm_eps)
    return x + (jax.nn.silu(y @ p["gate_proj"]) * (y @ p["up_proj"])) @ p["down_proj"]


class Decoder:
    """The jitted pieces for one architecture. ``top`` is
    ``{"embed_tokens": (V, D), "norm": (D,), "lm_head": (D, V)}``: the norm
    in float32, the two tables in the type they are stored in and read only
    as ``embed[ids]`` and ``head[:, c0:c1]`` (cast to float32 where they are
    used, a block at a time, which is exact).
    `get_layer(i)` returns layer ``i`` in float32 (keys as in
    `decoder_layer`)."""

    def __init__(self, arch: Arch, *, q_block: int | None = 512, vocab_block: int = 16384):
        self.arch = arch
        self.vocab_block = vocab_block
        layer = functools.partial(decoder_layer, arch, q_block)
        self._layer = jax.jit(_highest(layer))

        def layer_vjp(p, x, cos, sin, g):
            _, pull = jax.vjp(lambda p, x: layer(p, x, cos, sin), p, x)
            dp, dx = pull(g)
            sq = sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(dp))
            return dx, sq, dp["input_layernorm"], dp["post_attention_layernorm"]

        self._layer_vjp = jax.jit(_highest(layer_vjp))
        self._norm = jax.jit(_highest(lambda w, x: rms_norm(x, w, arch.rms_norm_eps)))

        def norm_vjp(w, x, g):
            _, pull = jax.vjp(lambda w, x: rms_norm(x, w, arch.rms_norm_eps), w, x)
            dw, dx = pull(g)
            return dx, jnp.sum(jnp.square(dw)), dw

        self._norm_vjp = jax.jit(_highest(norm_vjp))
        self._logits = jax.jit(_highest(lambda w, x: x @ w.astype(jnp.float32)))

        # The head's cross entropy in blocks of the vocabulary, so that the
        # (positions x vocabulary) logits and the head's gradient never exist
        # whole. With z = log sum exp(logits) from a first pass,
        # d loss / d logits = (exp(logits - z) - onehot(label)) * weight.
        def block_logsumexp(w, x):
            return jax.scipy.special.logsumexp(x @ w, axis=-1)

        def block_backward(w, x, z, labels, weights, first_col):
            logits = x @ w
            cols = first_col + jnp.arange(w.shape[1])
            hit = labels[:, None] == cols[None, :]
            label_logit = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
            g = (jnp.exp(logits - z[:, None]) - hit) * weights[:, None]
            return label_logit, g @ w.T, jnp.sum(jnp.square(x.T @ g))

        self._block_logsumexp = jax.jit(_highest(block_logsumexp))
        self._block_backward = jax.jit(_highest(block_backward))

    @classmethod
    @functools.lru_cache(maxsize=None)
    def of(cls, arch: Arch) -> "Decoder":
        """One decoder an architecture: its jitted pieces are traced once,
        however many seeds a process walks."""
        return cls(arch)

    # ------------------------------------------------------------- forward
    def _walk(self, get_layer: GetLayer, top, tokens: np.ndarray, keep_inputs: bool):
        """Each row of ``tokens`` through every layer, one row and one layer
        at a time. Returns the final hidden state of each row (before the
        final norm) and, with ``keep_inputs``, each layer's inputs on the
        host (``[layer][row]``)."""
        arch = self.arch
        tokens = np.asarray(tokens)
        cos, sin = (jnp.asarray(t) for t in rope_tables(arch, tokens.shape[1]))
        embed = top["embed_tokens"]
        rows = [embed[jnp.asarray(row)].astype(jnp.float32)[None] for row in tokens]
        inputs = []
        for i in range(arch.num_hidden_layers):
            p = get_layer(i)
            if keep_inputs:
                inputs.append([np.asarray(x) for x in rows])
            rows = [self._layer(p, x, cos, sin) for x in rows]
        return rows, inputs, (cos, sin)

    def forward_logits(self, get_layer: GetLayer, top, tokens, positions) -> list[np.ndarray]:
        """Logits at ``positions[r]`` (a slice or index array) of row ``r``,
        after a full causal forward over the row. Rows may be right-padded
        to a common length: a causal model's earlier positions do not see
        the padding."""
        rows, _, _ = self._walk(get_layer, top, tokens, keep_inputs=False)
        out = []
        head, V, step = top["lm_head"], self.arch.vocab_size, self.vocab_block
        for x, where in zip(rows, positions):
            hidden = self._norm(top["norm"], x[0, where])
            blocks = [
                np.asarray(self._logits(head[:, c : min(c + step, V)], hidden))
                for c in range(0, V, step)
            ]
            out.append(np.concatenate(blocks, axis=-1))
        return out

    # ---------------------------------------------------- loss and gradient
    def _head_backward(self, top, hidden, labels, weights):
        """Cross entropy ``sum(weights * (z - logit[label]))`` of normed
        hidden states ``(N, D)``: the loss, its gradient with respect to
        ``hidden``, and the squared norm of the head's gradient."""
        head, V, step = top["lm_head"], self.arch.vocab_size, self.vocab_block

        def blocks():  # one float32 block of the head alive at a time
            for c in range(0, V, step):
                yield c, head[:, c : min(c + step, V)].astype(jnp.float32)

        z = jax.scipy.special.logsumexp(
            jnp.stack([self._block_logsumexp(w, hidden) for _, w in blocks()]), axis=0
        )
        label_logit, d_hidden, head_sq = 0.0, 0.0, 0.0
        for first_col, w in blocks():
            ll, dh, sq = self._block_backward(w, hidden, z, labels, weights, first_col)
            label_logit, d_hidden, head_sq = label_logit + ll, d_hidden + dh, head_sq + sq
        return jnp.sum(weights * (z - label_logit)), d_hidden, head_sq

    def loss_and_grad_norm(self, get_layer: GetLayer, top, tokens) -> dict[str, Any]:
        """Mean next-token cross entropy over every position but each row's
        last, the L2 norm of its gradient over all parameters and, under
        ``"norm_grads"``, the gradients of the norm weights themselves:
        ``input_layernorm`` and ``post_attention_layernorm`` as ``(layers,
        D)`` and the final ``norm`` as ``(D,)``."""
        arch = self.arch
        tokens = np.asarray(tokens)
        B, S = tokens.shape
        rows, inputs, (cos, sin) = self._walk(get_layer, top, tokens, keep_inputs=True)
        weights = jnp.full((S,), 1.0 / (B * (S - 1)), jnp.float32).at[-1].set(0.0)
        # Position i predicts token i + 1; each row's last position has
        # weight 0. Gradients add over rows before they are squared, so the
        # head and the final norm take all rows at once.
        labels = jnp.asarray(np.concatenate([np.roll(row, -1) for row in tokens]))
        stacked = jnp.concatenate([x[0] for x in rows])
        hidden = self._norm(top["norm"], stacked)
        loss, d_hidden, head_sq = self._head_backward(top, hidden, labels, jnp.tile(weights, B))
        d_stacked, norm_sq, d_norm = self._norm_vjp(top["norm"], stacked, d_hidden)
        by_layer: dict[str, list] = {"input_layernorm": [], "post_attention_layernorm": []}
        sq = float(head_sq) + float(norm_sq)
        d_rows = d_stacked.reshape(B, S, -1)
        del rows, stacked, hidden, d_hidden, d_stacked
        for i in reversed(range(arch.num_hidden_layers)):
            p = get_layer(i)
            # Weight gradients add over rows before they are squared: the
            # rows go through one vjp together when they fit, and a layer at
            # a time either way.
            x = jnp.concatenate([jnp.asarray(a) for a in inputs[i]])
            d_rows, layer_sq, d_input_norm, d_post_norm = self._layer_vjp(p, x, cos, sin, d_rows)
            sq += float(layer_sq)
            by_layer["input_layernorm"].insert(0, np.asarray(d_input_norm))
            by_layer["post_attention_layernorm"].insert(0, np.asarray(d_post_norm))
            inputs[i] = None
        # Embedding rows: gradients of repeated tokens add before squaring.
        flat = d_rows.reshape(B * S, -1)
        _, inverse = np.unique(tokens.reshape(-1), return_inverse=True)
        summed = jnp.zeros((int(inverse.max()) + 1, flat.shape[-1]), jnp.float32)
        sq += float(jnp.sum(jnp.square(summed.at[jnp.asarray(inverse.reshape(-1))].add(flat))))
        norm_grads = {k: np.stack(v) for k, v in by_layer.items()}
        norm_grads["norm"] = np.asarray(d_norm)
        return {"loss": float(loss), "grad_norm": math.sqrt(sq), "norm_grads": norm_grads}
