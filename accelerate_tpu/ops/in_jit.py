"""In-jit collective primitives & helpers.

The reference has no equivalent layer: its collectives (`utils/operations.py`)
always execute eagerly from Python via torch.distributed. On TPU the hot-path
collectives are XLA HLO ops compiled into the step function; this module gives
users and the framework a thin, named surface over them:

- `psum` / `pmean` / `pmax` / `pmin` — cross-replica reductions
- `all_gather_axis` — gather a sharded dim
- `ppermute` — neighbour exchange (ring collectives, pipeline transfers)
- `shard_map_over` — wrap a per-shard function over the global mesh

These matter when writing manual-collective regions (ring attention,
`parallel/ring.py`); plain GSPMD code never calls them — the compiler inserts
collectives from shardings.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec

shard_map = jax.shard_map

psum = lax.psum
pmean = lax.pmean
pmax = lax.pmax
pmin = lax.pmin
ppermute = lax.ppermute
axis_index = lax.axis_index


def all_gather_axis(x: jax.Array, axis_name: str, *, axis: int = 0, tiled: bool = True) -> jax.Array:
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def shard_map_over(
    fn: Callable[..., Any],
    mesh: Mesh,
    in_specs: Any,
    out_specs: Any,
    check_vma: bool = False,
) -> Callable[..., Any]:
    """`shard_map` with the framework mesh; per-shard code sees local blocks
    and may call the collectives above with the mesh axis names."""
    return shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )


def sequence_parallel_specs(
    mesh: Mesh, batch_size: int, batch_axes, axis_name: str
):
    """Shared entry scaffolding for the sequence-parallel attention schemes
    (ring / ulysses): returns ``(qkv_spec, mask_spec)`` with the batch dim
    sharded over ``batch_axes`` only when it divides (otherwise replicated —
    e.g. eval with a small batch on a large mesh; sequence sharding still
    applies)."""
    from jax.sharding import PartitionSpec as P

    batch_group = 1
    for a in batch_axes:
        batch_group *= mesh.shape[a]
    use_batch = (
        tuple(batch_axes) if batch_group > 1 and batch_size % batch_group == 0 else None
    )
    return P(use_batch, axis_name, None, None), P(use_batch, axis_name)


def ring_neighbors(axis_name: str, n: int) -> list[tuple[int, int]]:
    """Permutation pairs sending shard i -> i+1 (mod n) along a mesh axis."""
    return [(i, (i + 1) % n) for i in range(n)]
