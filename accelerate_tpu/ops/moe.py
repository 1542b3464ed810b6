"""Mixture-of-Experts layer with expert parallelism over the ``expert`` axis.

The reference has no MoE support (Megatron-LM integration exposes none of it
through accelerate); this fills the framework's ``expert`` mesh axis —
declared in `parallel/mesh.py:MESH_AXES` — with a real consumer. The design
is the GShard/Switch capacity-based dispatch, which is THE TPU-native MoE
construction (static shapes, einsum dispatch, XLA inserts the all-to-alls):

- router: tokens -> softmax logits over E experts, top-k choice;
- capacity: each expert processes at most C = ceil(k*N/E * capacity_factor)
  tokens; overflow tokens are dropped (their combine weight is zero and the
  residual connection carries them through unchanged — standard Switch
  behavior);
- dispatch/combine are one-hot einsum contractions, so the whole layer is
  three matmuls + the expert FFN — no sorting, no dynamic shapes;
- expert weights carry a leading [E] axis; sharding it over the ``expert``
  mesh axis (see `llama.tp_plan`) makes XLA lower the dispatch einsum to an
  all-to-all over ICI — expert parallelism without any explicit collective
  in this file;
- aux losses: load-balance (Switch eq. 4) + router z-loss, returned for the
  model's loss function to weight in.

Beside it stands the **dropless** layer (`moe_dropless`), for serving and
for models whose routing may not lose a token: the same router arithmetic,
then the ``rows x top_k`` assignments sorted by expert (a counting sort:
one-hot ranks, no comparison sort), each expert's group padded to whole row
tiles, one grouped expert feed-forward over the sorted rows
(`native/pallas/moe_experts.py`, the weight stacks read in place; without
the kernel `jax.lax.ragged_dot` on one layer's experts), and the weighted
sum back. Padding rows are computed and counted; no row is dropped.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..models.layers import activation_fn, truncated_normal_init
from .fp8 import matmul_einsum

Params = Any


def init_moe(
    rng: jax.Array,
    d_model: int,
    d_ff: int,
    n_experts: int,
    dtype=jnp.float32,
) -> Params:
    """Router + E parallel swiglu experts (leading [E] axis on every weight)."""
    kr, kg, ku, kd = jax.random.split(rng, 4)
    std_in = 1.0 / np.sqrt(d_model)
    std_out = 1.0 / np.sqrt(d_ff)
    return {
        "router": truncated_normal_init(kr, (d_model, n_experts), std_in, dtype),
        "w_gate": truncated_normal_init(kg, (n_experts, d_model, d_ff), std_in, dtype),
        "w_up": truncated_normal_init(ku, (n_experts, d_model, d_ff), std_in, dtype),
        "w_down": truncated_normal_init(kd, (n_experts, d_ff, d_model), std_out, dtype),
    }


def _n_groups(n_tokens: int, tokens_per_group: int) -> int:
    """Smallest divisor of ``n_tokens`` keeping groups <= tokens_per_group."""
    for g in range(1, n_tokens + 1):
        if n_tokens % g == 0 and n_tokens // g <= tokens_per_group:
            return g
    return n_tokens


def router_probs(router: jax.Array, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(logits, softmax) of rows ``x`` (n, d) over the router's experts, in
    fp32 at full matmul precision: tiny FLOPs, and logit precision decides
    the expert choice. Shared by the capacity and the dropless layer."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return logits, jax.nn.softmax(logits, axis=-1)


def _group_moe(params: Params, xt: jax.Array, *, top_k: int, capacity: int):
    """Dispatch/FFN/combine for ONE token group. xt: (n, d)."""
    n, d = xt.shape
    E = params["router"].shape[-1]
    logits, probs = router_probs(params["router"], xt)  # (n, E)

    # Top-k selection (static k) with per-round masking.
    remaining = probs
    dispatch = jnp.zeros((n, E, capacity), xt.dtype)
    combine = jnp.zeros((n, E, capacity), jnp.float32)
    # Track per-expert fill across rounds so round 2 continues where 1 ended.
    fill = jnp.zeros((E,), jnp.int32)
    importance = jnp.zeros((E,), jnp.float32)  # fraction routed per expert
    for _ in range(top_k):
        choice = jnp.argmax(remaining, axis=-1)  # (n,)
        gate = jnp.take_along_axis(remaining, choice[:, None], axis=-1)[:, 0]
        onehot = jax.nn.one_hot(choice, E, dtype=jnp.int32)  # (n, E)
        # Position of each token within its chosen expert's buffer.
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot) + fill[None, :]
        pos = jnp.sum(pos_in_expert * onehot, axis=-1)  # (n,)
        keep = pos < capacity
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, 0), capacity, dtype=jnp.float32)
        contrib = (
            onehot.astype(jnp.float32)[:, :, None]
            * pos_oh[:, None, :]
            * keep.astype(jnp.float32)[:, None, None]
        )
        dispatch = dispatch + contrib.astype(xt.dtype)
        combine = combine + contrib * gate[:, None, None]
        fill = fill + jnp.sum(onehot * keep[:, None].astype(jnp.int32), axis=0)
        importance = importance + jnp.mean(onehot.astype(jnp.float32), axis=0)
        remaining = remaining * (1.0 - onehot.astype(probs.dtype))

    # Dispatch -> expert FFN -> combine. The expert projections (the FLOPs)
    # route through `matmul_einsum` so fp8 mode covers them; the one-hot
    # dispatch/combine contractions are data movement, not matmuls, and stay
    # in the compute dtype.
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, xt)  # (E, C, d)
    gate_h = matmul_einsum("ecd,edf->ecf", expert_in, params["w_gate"])
    up_h = matmul_einsum("ecd,edf->ecf", expert_in, params["w_up"])
    hidden = jax.nn.silu(gate_h) * up_h
    expert_out = matmul_einsum("ecf,efd->ecd", hidden, params["w_down"])
    out = jnp.einsum("nec,ecd->nd", combine.astype(xt.dtype), expert_out)

    # Renormalize: dropped tokens keep whatever gate mass survived; the usual
    # top-k renorm divides by the sum of kept gates (guarded for full drops).
    gate_sum = jnp.sum(combine, axis=(1, 2))  # (n,)
    out = out / jnp.maximum(gate_sum, 1e-9)[:, None].astype(out.dtype)

    # Aux stats. Load balance (Switch eq. 4): E * sum_e f_e * P_e where f_e
    # is the routed fraction and P_e the mean router prob. z-loss keeps
    # logits from drifting to fp32-hostile magnitudes.
    mean_prob = jnp.mean(probs, axis=0)  # (E,)
    load_balance = E * jnp.sum((importance / top_k) * mean_prob)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    kept = jnp.sum(dispatch.astype(jnp.float32))
    return out, load_balance, z_loss, kept


def moe_forward(
    params: Params,
    x: jax.Array,
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    tokens_per_group: int = 2048,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """(B, S, d) -> (B, S, d) plus aux losses.

    Tokens are split into groups of at most ``tokens_per_group`` with
    per-group expert capacity (the GShard group axis): the dispatch/combine
    one-hots are then O(N * top_k * capacity_factor * tokens_per_group / E)
    — linear in total tokens — instead of the O(N^2) a single global
    capacity would cost at training sequence lengths.
    """
    B, S, d = x.shape
    E = params["router"].shape[-1]
    N = B * S
    G = _n_groups(N, tokens_per_group)
    n = N // G
    capacity = max(int(math.ceil(top_k * n / E * capacity_factor)), 1)

    xg = x.reshape(G, n, d)
    out, load_balance, z_loss, kept = jax.vmap(
        lambda xt: _group_moe(params, xt, top_k=top_k, capacity=capacity)
    )(xg)
    aux = {
        "moe_load_balance": jnp.mean(load_balance).astype(jnp.float32),
        "moe_z_loss": jnp.mean(z_loss).astype(jnp.float32),
        # Fraction of token-slots dropped by capacity limits (diagnostic).
        "moe_drop_fraction": 1.0 - jnp.sum(kept) / (top_k * N),
    }
    return out.reshape(B, S, d), aux


def moe_reference(params: Params, x: jax.Array, *, top_k: int = 2) -> jax.Array:
    """Oracle: per-token dense computation of the same top-k mixture with
    unlimited capacity (for tests)."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    _, probs = router_probs(params["router"], xt)
    E = probs.shape[-1]
    _, topk_idx = jax.lax.top_k(probs, top_k)

    def one_expert(e):
        gate = xt @ params["w_gate"][e].astype(xt.dtype)
        up = xt @ params["w_up"][e].astype(xt.dtype)
        return (jax.nn.silu(gate) * up) @ params["w_down"][e].astype(xt.dtype)

    all_out = jnp.stack([one_expert(e) for e in range(E)], axis=1)  # (N, E, d)
    mask = jax.nn.one_hot(topk_idx, E).sum(axis=1)  # (N, E)
    weights = probs * mask
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    out = jnp.einsum("ne,ned->nd", weights.astype(xt.dtype), all_out)
    return out.reshape(B, S, d)


# ------------------------------------------------------------------ dropless
MOE_COUNTS = ("moe_assignments", "moe_rows_computed", "moe_experts_touched", "moe_expert_rows_max")


def dispatch_tile_rows(n_assignments: int, n_experts: int, dtype) -> int:
    """Row tile of the grouped expert product: about the rows an expert gets
    when routing is even, between the dtype's smallest tile and the MXU's
    128 rows. A decode step (rows << experts) pads each touched expert to
    the smallest tile; a prefill chunk fills whole MXU passes."""
    from ..native.pallas.moe_experts import min_tile_rows

    even = max(n_assignments // max(n_experts, 1), 1)
    tile = 1 << (even - 1).bit_length()
    return int(min(128, max(min_tile_rows(dtype), tile)))


def _sorted_layout(expert: jax.Array, held: jax.Array, n_experts: int, tile: int):
    """Where each assignment's row goes when the rows are sorted by expert
    and every expert's group is padded to whole tiles of ``tile`` rows.

    ``expert`` (A,) int32 in [0, n_experts) where ``held``. Returns ``dest``
    (A,): the row of each assignment (the static row count where not held),
    ``counts`` (E,) rows routed to each expert, ``tile_expert`` (T,) and
    ``n_tiles`` (): the expert of each row tile and how many tiles are in
    use, T the static bound ``(A + min(E, A) * (tile - 1)) // tile``."""
    A = expert.shape[0]
    n_row_tiles = (A + min(n_experts, A) * (tile - 1)) // tile
    onehot = ((expert[:, None] == jnp.arange(n_experts)[None, :]) & held[:, None]).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    counts = jnp.sum(onehot, axis=0)
    tiles = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    n_tiles = tile_end[-1]
    first_row = (tile_end - tiles) * tile
    dest = jnp.where(held, first_row[jnp.clip(expert, 0, n_experts - 1)] + rank, n_row_tiles * tile)
    # Tiles past the last one in use repeat its expert: nothing new to fetch.
    t = jnp.minimum(jnp.arange(n_row_tiles), jnp.maximum(n_tiles - 1, 0))
    tile_expert = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"), n_experts - 1)
    return dest, counts, tile_expert.astype(jnp.int32), n_tiles


def moe_dropless(
    params: Params,
    x: jax.Array,
    router_x: jax.Array | None = None,
    *,
    top_k: int,
    activation: str = "relu",
    renormalize: bool = True,
    layer: jax.Array | int | None = None,
    first_expert: int = 0,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Top-k routed gated experts that drop nothing. ``x`` (n, d) is what the
    experts read, ``router_x`` (n, d) what the router reads (``x`` when
    None: some models route from the layer's input, before attention).

    ``params``: ``router`` (d, E_router) and the expert weights ``w_gate`` /
    ``w_up`` (E, d, f), ``w_down`` (E, f, d) - or, with ``layer``, the
    layer-stacked weights (L, E, ...), read at ``layer`` without slicing the
    experts out of the stack (the router may then be stacked too, (L, d,
    E_router), or that layer's own). The weights hold
    experts ``first_expert .. first_expert + E`` of the router's E_router:
    every row is routed over all of them, and the result is the part the held
    experts give (all of it when E == E_router), as one chip of an
    expert-parallel deployment computes before the exchange.

    Weights of the chosen k are the router's softmax, renormalised over the
    k when ``renormalize``. Returns (out (n, d), counts): ``MOE_COUNTS`` as
    int32 scalars - assignments held here, rows the expert products ran
    (padding included), experts with at least one row, the largest group."""
    from ..native.pallas import kernel_mode
    from ..native.pallas import moe_experts as kernel

    n, d = x.shape
    stacked = layer is not None
    weights = [params[name] if stacked else params[name][None] for name in ("w_gate", "w_up", "w_down")]
    router = params["router"]
    if router.ndim == 3:
        router = jax.lax.dynamic_index_in_dim(router, layer, 0, keepdims=False)
    layer = jnp.asarray(layer if stacked else 0, jnp.int32)
    E = weights[0].shape[1]

    _, probs = router_probs(router, x if router_x is None else router_x)
    gate, chosen = jax.lax.top_k(probs, top_k)  # (n, k)
    if renormalize:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    expert = chosen.reshape(-1).astype(jnp.int32) - first_expert
    held = (expert >= 0) & (expert < E)

    mode = kernel_mode("moe_experts")
    tile = dispatch_tile_rows(n * top_k, E, x.dtype)
    if mode is None or not kernel.supported(weights[0], tile, x.dtype, compiled=mode == "compiled"):
        mode, tile = None, 1  # `ragged_dot` needs no padding
    dest, counts, tile_expert, n_tiles = _sorted_layout(expert, held, E, tile)
    n_rows = tile_expert.shape[0] * tile
    token = jnp.repeat(jnp.arange(n, dtype=jnp.int32), top_k)
    source = jnp.zeros((n_rows,), jnp.int32).at[dest].set(token, mode="drop")
    xs = x[source]  # padding rows read row 0; they are computed and never gathered
    if mode is not None:
        ys = kernel.moe_experts(
            xs, *weights, tile_expert, n_tiles, layer,
            tile_rows=tile, activation=activation_fn(activation), interpret=mode == "interpret",
        )
    else:
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False) for w in weights
        )
        hidden = activation_fn(activation)(jax.lax.ragged_dot(xs, w_gate.astype(xs.dtype), counts))
        hidden = hidden * jax.lax.ragged_dot(xs, w_up.astype(xs.dtype), counts)
        ys = jax.lax.ragged_dot(hidden, w_down.astype(xs.dtype), counts)
    picked = ys[jnp.minimum(dest, n_rows - 1)].reshape(n, top_k, d).astype(jnp.float32)
    # `where`, not a zero weight: a row that is not held gathers a row no tile wrote.
    weighted = jnp.where(held.reshape(n, top_k, 1), picked * gate[..., None], 0.0)
    out = jnp.sum(weighted, axis=1).astype(x.dtype)
    stats = (jnp.sum(held), n_tiles * tile, jnp.sum(counts > 0), jnp.max(counts))
    return out, {name: v.astype(jnp.int32) for name, v in zip(MOE_COUNTS, stats)}
