"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464, with the
negative-eigenvalue range of arXiv:2411.12537) in its two forms, and the
short causal convolution that feeds it.

A head keeps a state matrix ``S`` (d_k x d_v, float32). For token ``t`` with
a unit key ``k_t``, a query ``q_t``, a value ``v_t``, a decay ``alpha_t =
exp(g_t)`` in (0, 1] and a write strength ``beta_t`` in (0, 2):

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

- `recurrent_step` is that line for one token a row: what a decode step runs
  (`native/pallas/gated_delta.py:gdn_decode` on the chip, reading and writing
  the layer-stacked state where it lies);
- `chunk_gated_delta` is the same map for T tokens a row as matrix products
  over chunks of `CHUNK` tokens (the WY / UT transform): inside a chunk the
  T sequential rank-one updates collapse into one unit-lower-triangular
  inverse, and only the state is handed from chunk to chunk. Rows with
  ``g = 0`` and ``beta = 0`` leave the state as it is: that is how a
  bucket's pad tail and the padding up to a whole chunk are written.

Both take and return the state in float32 whatever the activations are.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ convolution
def causal_conv(x: jax.Array, tail: jax.Array, weight: jax.Array) -> jax.Array:
    """Depthwise causal convolution over time: ``y_t = sum_j weight[j] *
    x_{t - (W-1) + j}`` for x (B, T, C), with the W-1 rows before the first
    given by ``tail`` (B, W-1, C) (zeros at the start of a sequence).
    weight: (W, C). Returns (B, T, C) float32."""
    W = weight.shape[0]
    T = x.shape[1]
    seq = jnp.concatenate([tail.astype(jnp.float32), x.astype(jnp.float32)], axis=1)
    w = weight.astype(jnp.float32)
    return sum(seq[:, j : j + T] * w[j] for j in range(W))


def conv_tail(x: jax.Array, tail: jax.Array, valid: jax.Array | None = None) -> jax.Array:
    """The W-1 rows that precede the next token after ``x`` (B, T, C) was
    appended behind ``tail`` (B, W-1, C): the last W-1 of the rows so far,
    where only the first ``valid`` (a scalar; all when None) rows of ``x``
    are real."""
    n = tail.shape[1]
    seq = jnp.concatenate([tail, x.astype(tail.dtype)], axis=1)
    start = x.shape[1] if valid is None else valid
    return jax.lax.dynamic_slice_in_dim(seq, start, n, axis=1)


# -------------------------------------------------------------- recurrent form
def recurrent_step(q, k, v, alpha, beta, state):
    """One token a row. q, k: (B, H, d_k); v: (B, H, d_v); alpha, beta:
    (B, H); state: (B, H, d_k, d_v) float32. Returns (o (B, H, d_v) float32,
    new state)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    alpha, beta = alpha.astype(f32)[..., None], beta.astype(f32)[..., None]
    seen = jnp.einsum("bhk,bhkv->bhv", k, state, precision=_HIGHEST)
    write = beta * (v - alpha * seen)
    state = alpha[..., None] * state + k[..., :, None] * write[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, state, precision=_HIGHEST), state


def recurrent_gated_delta(q, k, v, g, beta, state):
    """The rule token by token (a `lax.scan`): the definition the chunkwise
    form is tested against. Shapes as `chunk_gated_delta`."""

    def step(state, xs):
        qt, kt, vt, gt, bt = xs
        o, state = recurrent_step(qt, kt, vt, jnp.exp(gt.astype(jnp.float32)), bt, state)
        return state, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, out = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1), state


# -------------------------------------------------------------- chunkwise form
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (..., n, n), n a
    power of two times 8 or less: block forward substitution, every step a
    batched product. The 8 x 8 diagonal blocks are inverted by their (finite)
    Neumann series ``(I + x)(I + x^2)(I + x^4)``, x = -block, whose terms
    stay small at that size; two blocks of m merge into one of 2m by
    ``[[t1, 0], [-t2 a21 t1, t2]]``, which is as stable as row-by-row
    substitution. The full-size series is not: its powers grow to 1e3 and
    more before they cancel."""
    n = a.shape[-1]
    lead = a.shape[:-2]
    m = min(n, 8)
    nb = n // m
    blocks = a.reshape(lead + (nb, m, nb, m))
    mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], axis=-3)  # (..., nb, m, m)
    eye = jnp.eye(m, dtype=a.dtype)
    x = -diag
    x2 = mm(x, x)
    t = mm(mm(eye + x, eye + x2), eye + mm(x2, x2))
    while m < n:
        pairs = nb // 2
        grid = a.reshape(lead + (pairs, 2, m, pairs, 2, m))
        a21 = jnp.stack([grid[..., i, 1, :, i, 0, :] for i in range(pairs)], axis=-3)
        t1, t2 = t[..., 0::2, :, :], t[..., 1::2, :, :]
        t21 = -mm(mm(t2, a21), t1)
        zero = jnp.zeros_like(t1)
        t = jnp.concatenate(
            [jnp.concatenate([t1, zero], axis=-1), jnp.concatenate([t21, t2], axis=-1)], axis=-2
        )
        m, nb = 2 * m, pairs
    return t[..., 0, :, :]


def chunk_prepare(q, k, v, g, beta, chunk: int = CHUNK):
    """Everything of the chunkwise form that does not need the state, for all
    chunks at once. q, k: (B, T, H, d_k); v: (B, T, H, d_v); g, beta:
    (B, T, H); T a multiple of ``chunk``. Returns float32 arrays laid out
    (B, H, N, ...) for N chunks of C rows:

    - ``qg``  (C, d_k): q_i exp(G_i), G the chunk's running sum of g;
    - ``kdt`` (d_k, C): k_j exp(G_last - G_j), transposed;
    - ``w``   (C, d_k), ``u`` (C, d_v): the WY factors ``T (beta k exp(G))``
      and ``T (beta v)``, ``T = (I + strict_lower(beta_i k_i.k_j
      exp(G_i - G_j)))^-1``;
    - ``p``   (C, C): ``q_i.k_j exp(G_i - G_j)`` for j <= i, else 0;
    - ``last`` (1, 1): exp(G_last), the chunk's whole decay.

    With them a chunk is ``v' = u - w S``, ``o = qg S + p v'``,
    ``S <- last S + kdt v'`` (`chunk_scan`)."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    N, C = T // chunk, chunk

    def chunks(a):  # (B, T, H, ...) -> (B, H, N, C, ...)
        a = a.astype(f32).reshape((B, N, C, H) + a.shape[3:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)
    G = jnp.cumsum(g, axis=-1)  # (B, H, N, C)
    rows = jnp.arange(C)
    lower = rows[:, None] >= rows[None, :]
    # exp of a masked difference: above the diagonal G_i - G_j > 0 may overflow.
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
    k_beta = k * beta[..., None]
    kk = jnp.einsum("...ik,...jk->...ij", k_beta, k, precision=_HIGHEST)
    strict = rows[:, None] > rows[None, :]
    t = unit_lower_inverse(jnp.where(strict, kk * decay, 0.0))
    w = jnp.matmul(t, k_beta * jnp.exp(G)[..., None], precision=_HIGHEST)
    u = jnp.matmul(t, v * beta[..., None], precision=_HIGHEST)
    p = jnp.einsum("...ik,...jk->...ij", q, k, precision=_HIGHEST) * decay
    last = G[..., -1:]
    return {
        "qg": q * jnp.exp(G)[..., None],
        "kdt": jnp.swapaxes(k * jnp.exp(last - G)[..., None], -1, -2),
        "w": w, "u": u, "p": p,
        "last": jnp.exp(last)[..., None],
    }


def chunk_scan(parts: dict[str, jax.Array], state: jax.Array):
    """The chunk-to-chunk part in plain XLA (a `lax.scan` over the chunks):
    what `native/pallas/gated_delta.py:gdn_chunk` runs on the chip. Returns
    (o (B, H, N, C, d_v), new state)."""

    def step(S, x):
        fresh = x["u"] - jnp.matmul(x["w"], S, precision=_HIGHEST)
        o = jnp.matmul(x["qg"], S, precision=_HIGHEST) + jnp.matmul(x["p"], fresh, precision=_HIGHEST)
        return x["last"] * S + jnp.matmul(x["kdt"], fresh, precision=_HIGHEST), o

    xs = {n: jnp.moveaxis(a, 2, 0) for n, a in parts.items()}
    state, out = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(out, 0, 2), state


def chunk_gated_delta(q, k, v, g, beta, state, *, chunk: int = CHUNK):
    """The rule for T tokens a row, chunk by chunk. q, k: (B, T, H, d_k)
    (k of unit length, q scaled); v: (B, T, H, d_v); g (log decay, <= 0)
    and beta: (B, T, H); state: (B, H, d_k, d_v) float32. Returns (o (B, T,
    H, d_v) float32, new state). T is padded up to whole chunks with rows
    that change nothing."""
    from ..native.pallas.gated_delta import maybe_gdn_chunk

    B, T, H, _ = q.shape
    pad = -T % chunk
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    parts = chunk_prepare(q, k, v, g, beta, chunk)
    done = maybe_gdn_chunk(parts, state)
    out, state = done if done is not None else chunk_scan(parts, state)
    out = jnp.moveaxis(out, 1, 3).reshape(B, T + pad, H, -1)  # (B, H, N, C, d) -> (B, T, H, d)
    return out[:, :T], state
