"""The plain reference for Olmo-Hybrid-7B: `jax.numpy`, float32 at
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
chunkwise form, nothing imported from the program under test.

The equations, from the catalog row's `config`
(`/opt/skills/guides/model-configs/architectures.jsonl`, source
https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json). The
linear-attention mixer is Gated DeltaNet (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464) with the negative-eigenvalue range of Grazzi et al.
(arXiv:2411.12537); key names as in the public `fla` layer. For a token
``x_t`` (hidden), H heads of key width d_k and value width d_v, no bias:

    1. u^q = W_q x, u^k = W_k x (H d_k each), u^v = W_v x (H d_v);
       z = W_g x (H d_v); a = W_a x (H); b = W_b x (H)
    2. c_t = silu(sum_{j=0..3} w_j * u_{t-3+j}) on every channel of u^q, u^k,
       u^v (u = 0 before the sequence starts)
    3. per head: q_t = c^q_t / |c^q_t| * d_k^(-1/2); k_t = c^k_t / |c^k_t|
       (|.| = sqrt(sum of squares + eps)); v_t = c^v_t
    4. beta_t = 2 sigmoid(b_t); g_t = -exp(A_log) softplus(a_t + dt_bias);
       alpha_t = exp(g_t)
    5. S_0 = 0 (d_k x d_v, float32);
       S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T;
       o_t = S_t^T q_t
    6. y_t = rmsnorm(o_t; w) * silu(z_t) head by head (w shared by the
       heads); the mixer's output is W_o y_t

The full-attention mixer: q, k, v, o projections, every query head its own
K/V head, causal softmax attention at scale head_dim^(-1/2). The feed-forward
is SwiGLU. What the catalog does not settle is a field of `Arch`, each the
Olmo family's convention (the configuration's file lists them under
``assumed``) and each a what-if of the sweep:

- ``block_norm = "post"``: ``h = x + rmsnorm(mixer(x))``,
  ``out = h + rmsnorm(ffn(h))``, one final rmsnorm before the head
  (``"pre"`` norms the mixer's and the feed-forward's inputs instead);
- ``qk_norm``: an rmsnorm with a weight of the whole projection's width on q
  and on k before the head split, in the full layers;
- ``rope_full_layers = False``: ``rope_theta`` is null, the full layers carry
  no rotary term (``True`` rotates q and k with ``what_if_rope_theta``);
- ``state_dtype = "float32"``: the rule's state (``"bfloat16"`` rounds it
  after every token);
- ``l2_eps``: the eps of step 3's norm.

Layout only: weights are ``(in, out)`` matrices with heads flattened into
the out axis, the convolution's ``(channels, 4)``; a norm's weight
multiplies directly. It walks one row and one layer at a time:
`get_layer(i)` hands it layer ``i`` in float32.
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
LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the equations need, under their published names, and the
    conventions the catalog leaves open."""

    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    layer_types: tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    vocab_size: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    linear_allow_neg_eigval: bool
    rms_norm_eps: float
    # assumed (each a what-if)
    block_norm: str = "post"
    qk_norm: bool = True
    rope_full_layers: bool = False
    what_if_rope_theta: float = 500000.0
    state_dtype: str = "float32"
    l2_eps: float = 1e-6
    # what-ifs of the mechanism itself
    decay: bool = True  # False: alpha fixed at 1
    conv: bool = True  # False: c = silu(u)
    qk_l2norm: bool = True  # False: q and k as the convolution left them

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_config(cls, config: dict[str, Any]) -> "Arch":
        if config["linear_num_value_heads"] != config["linear_num_key_heads"]:
            raise ValueError("the reference writes equal key and value head counts only")
        if (config.get("rope_parameters") or {}).get("rope_theta") is not None:
            raise ValueError("the reference writes the published null rope_theta only")
        layers = config["num_hidden_layers"]  # a depth-cut model takes the layout's first entries
        return cls(
            hidden_size=config["hidden_size"],
            intermediate_size=config["intermediate_size"],
            num_hidden_layers=layers,
            layer_types=tuple(config["layer_types"][:layers]),
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"],
            vocab_size=config["vocab_size"],
            linear_num_key_heads=config["linear_num_key_heads"],
            linear_num_value_heads=config["linear_num_value_heads"],
            linear_key_head_dim=config["linear_key_head_dim"],
            linear_value_head_dim=config["linear_value_head_dim"],
            linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
            linear_allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
        )


def _highest(fn):
    """Trace ``fn`` under "highest" matmul precision (on a TPU a float32
    matmul is otherwise done in bf16 passes)."""

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return traced


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def short_conv(u: jax.Array, w: jax.Array) -> jax.Array:
    """Step 2 without the silu: u ``(S, C)``, w ``(C, W)``; a sum over W
    shifted copies, zeros before the sequence starts."""
    S, W = u.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((W - 1, u.shape[1]), u.dtype), u], axis=0)
    return sum(padded[j : j + S] * w[:, j] for j in range(W))


def delta_rule(arch: Arch, q, k, v, alpha, beta, keep) -> tuple[jax.Array, jax.Array]:
    """Step 5, token by token. q, k ``(S, H, d_k)``, v ``(S, H, d_v)``, alpha
    and beta ``(S, H)``; returns o ``(S, H, d_v)`` and, for every entry n of
    ``keep`` (int32, ``(K,)``), the state after the first n tokens
    ``(K, H, d_k, d_v)`` in float32."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    state_dtype = jnp.dtype(arch.state_dtype)

    def token(carry, xs):
        S, kept = carry
        t, qt, kt, vt, at, bt = xs
        S = S.astype(jnp.float32)
        erased = S - bt[:, None, None] * kt[:, :, None] * jnp.einsum("hk,hkv->hv", kt, S)[:, None, :]
        S = at[:, None, None] * erased + bt[:, None, None] * kt[:, :, None] * vt[:, None, :]
        S = S.astype(state_dtype)
        kept = jnp.where((keep == t + 1)[:, None, None, None], S.astype(jnp.float32)[None], kept)
        return (S, kept), jnp.einsum("hkv,hk->hv", S.astype(jnp.float32), qt)

    start = jnp.zeros((H, dk, dv), state_dtype), jnp.zeros((keep.shape[0], H, dk, dv), jnp.float32)
    (_, kept), o = jax.lax.scan(token, start, (jnp.arange(q.shape[0]), q, k, v, alpha, beta))
    return o, kept


def linear_mixer(arch: Arch, p: Layer, x: jax.Array, keep: jax.Array) -> tuple[jax.Array, jax.Array]:
    S = x.shape[0]
    H, dk, dv = arch.linear_num_key_heads, arch.linear_key_head_dim, arch.linear_value_head_dim
    u = {n: x @ p[n + "_proj"] for n in "qkv"}
    z, a, b = x @ p["g_proj"], x @ p["a_proj"], x @ p["b_proj"]
    c = {n: jax.nn.silu(short_conv(u[n], p[n + "_conv"]) if arch.conv else u[n]) for n in "qkv"}
    q, k, v = c["q"].reshape(S, H, dk), c["k"].reshape(S, H, dk), c["v"].reshape(S, H, dv)
    if arch.qk_l2norm:
        unit = lambda t: t / jnp.sqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + arch.l2_eps)
        q, k = unit(q), unit(k)
    q = q * dk**-0.5
    beta = jax.nn.sigmoid(b) * (2.0 if arch.linear_allow_neg_eigval else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    alpha = jnp.exp(g) if arch.decay else jnp.ones_like(g)
    o, kept = delta_rule(arch, q, k, v, alpha, beta, keep)
    y = rms_norm(o, p["o_norm"], arch.rms_norm_eps) * jax.nn.silu(z.reshape(S, H, dv))
    return y.reshape(S, H * dv) @ p["o_proj"], kept


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x ``(S, heads, head_dim)``; dimension i pairs with i + head_dim/2."""
    S, _, h = x.shape
    inv_freq = 1.0 / (theta ** (np.arange(0, h, 2, dtype=np.float64) / h))
    angles = np.outer(np.arange(S, dtype=np.float64), inv_freq)
    cos, sin = (jnp.asarray(f(angles), jnp.float32)[:, None, :] for f in (np.cos, np.sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, q_block: int) -> jax.Array:
    """q ``(S, H, h)``, k and v ``(S, K, h)``; query head j reads key/value
    head ``j // (H / K)``; causal, in blocks of query rows."""
    S, H, h = q.shape
    K = k.shape[1]
    q = q.reshape(S, K, H // K, h)
    out = []
    for s0 in range(0, S, q_block):
        s1 = min(s0 + q_block, S)
        visible = jnp.arange(s1)[None, :] <= jnp.arange(s0, s1)[:, None]
        scores = jnp.einsum("skgh,tkh->kgst", q[s0:s1], k[:s1]) / math.sqrt(h)
        probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgst,tkh->skgh", probs, v[:s1]))
    return jnp.concatenate(out, axis=0).reshape(S, H * h)


def full_mixer(arch: Arch, q_block: int, p: Layer, x: jax.Array, keep: jax.Array) -> tuple[jax.Array, None]:
    del keep  # a full layer keeps rows, not a state
    S = x.shape[0]
    H, K, h = arch.num_attention_heads, arch.num_key_value_heads, arch.head_dim
    q, k, v = x @ p["q_proj"], x @ p["k_proj"], x @ p["v_proj"]
    if arch.qk_norm:
        q = rms_norm(q, p["q_norm"], arch.rms_norm_eps)
        k = rms_norm(k, p["k_norm"], arch.rms_norm_eps)
    q, k, v = q.reshape(S, H, h), k.reshape(S, K, h), v.reshape(S, K, h)
    if arch.rope_full_layers:
        q, k = _rope(q, arch.what_if_rope_theta), _rope(k, arch.what_if_rope_theta)
    return attention(q, k, v, q_block) @ p["o_proj"], None


def decoder_layer(arch: Arch, kind: str, q_block: int, p: Layer, x: jax.Array, keep: jax.Array):
    """One layer on one row. x ``(S, D)`` float32. Returns the layer's output
    and what its mixer kept (`delta_rule`'s states; None for a full layer)."""
    mixer = functools.partial(linear_mixer, arch) if kind == LINEAR else functools.partial(full_mixer, arch, q_block)
    eps = arch.rms_norm_eps

    def ffn(h):
        return (jax.nn.silu(h @ p["gate_proj"]) * (h @ p["up_proj"])) @ p["down_proj"]

    if arch.block_norm == "post":
        mixed, kept = mixer(p, x, keep)
        h = x + rms_norm(mixed, p["mixer_norm"], eps)
        return h + rms_norm(ffn(h), p["mlp_norm"], eps), kept
    mixed, kept = mixer(p, rms_norm(x, p["mixer_norm"], eps), keep)
    h = x + mixed
    return h + ffn(rms_norm(h, p["mlp_norm"], eps)), kept


class Decoder:
    """The jitted pieces for one architecture. ``top`` is
    ``{"embed_tokens": (V, D), "norm": (D,), "lm_head": (D, V)}``: the norm
    in float32, the two tables in the type they are stored in, read only as
    ``embed[ids]`` and ``head[:, c0:c1]`` and cast where they are used."""

    def __init__(self, arch: Arch, *, q_block: int = 512, vocab_block: int = 16384):
        self.arch = arch
        self.vocab_block = vocab_block
        self._layers = {
            kind: jax.jit(_highest(functools.partial(decoder_layer, arch, kind, q_block)))
            for kind in set(arch.layer_types)
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
        return self.forward(get_layer, top, tokens, positions, [(0,)] * len(tokens))[0]

    def forward(self, get_layer: GetLayer, top, tokens, positions, states_after):
        """`forward_logits`, and beside the logits, for every row ``r`` the
        rule's states after the row's first n tokens for each n of
        ``states_after[r]`` (as many for every row): an array ``(linear
        layers, len(states_after[r]), H, d_k, d_v)`` float32."""
        arch = self.arch
        tokens = np.asarray(tokens)
        rows = [top["embed_tokens"][jnp.asarray(row)].astype(jnp.float32) for row in tokens]
        keeps = [jnp.asarray(n, jnp.int32) for n in states_after]
        states: list[list[np.ndarray]] = [[] for _ in rows]
        for i in range(arch.num_hidden_layers):
            p = get_layer(i)
            layer = self._layers[arch.layer_types[i]]
            for r, x in enumerate(rows):
                rows[r], kept = layer(p, x, keeps[r])
                if kept is not None:
                    states[r].append(np.asarray(kept))
        out = []
        head, V, step = top["lm_head"], arch.vocab_size, self.vocab_block
        for x, where in zip(rows, positions):
            hidden = self._norm(top["norm"], x[where])
            blocks = [
                np.asarray(self._logits(head[:, c : min(c + step, V)], hidden))
                for c in range(0, V, step)
            ]
            out.append(np.concatenate(blocks, axis=-1))
        return out, [np.stack(s) for s in states]
