"""Host-resident optimizer state — the ZeRO-Offload analog.

The reference trains over-HBM models by pushing optimizer state (and
optionally params) to host memory: DeepSpeed ``offload_optimizer`` /
``offload_param`` incl. NVMe (`utils/dataclasses.py:1019-1111`,
`utils/deepspeed.py:29`) and FSDP ``cpu_offload``
(`utils/dataclasses.py:1449-1861`).

The TPU-native mechanism is JAX memory kinds plus a layer-streamed update:
a ``NamedSharding(..., memory_kind="pinned_host")`` places the moments in
the host's pinned RAM while keeping them addressable by the compiled
program, and the train step updates them one layer at a time inside a
``lax.scan`` — each iteration DMAs one layer's moment slices into HBM,
runs the (MXU-adjacent, vectorized) adamw math, and DMAs the new slices
back, so peak HBM grows by ONE layer's moments instead of all of them.
Measured on v5e at 1.6B-adamw: whole-tree approaches compile every moment
(or every gradient copy) into simultaneous HLO temps — 13.5-33 GiB of
temps against 16 GiB of HBM — while the scan form holds temps at the
per-layer working set.

Like DeepSpeed's CPU-adam (`utils/deepspeed.py:29` — offload requires
DeepSpeedCPUAdam, not an arbitrary torch optimizer), the streaming step
must know the optimizer's math: use `host_offloaded_adamw(...)`, which is
also a plain whole-tree adamw wherever offload is inactive (so the same
training script runs under the CPU-simulated mesh).

On one 16 GiB v5e this is the difference between "adafactor-only 1.6B"
and "adam-class 8B fine-tune": adamw's two fp32 moments cost 8 bytes/param
— more than the bf16 weights themselves — and sit idle between updates.

Not every backend implements the placement custom-call (the CPU simulator
used for the 8-device mesh tests does not); `host_offload_supported()`
probes once, and callers fall back loudly to device-resident state.
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, NamedTuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

HOST_MEMORY_KIND = "pinned_host"


def offload_requested_from_env() -> bool:
    from ..utils.dataclasses import parse_flag_from_env

    return parse_flag_from_env("ATX_OFFLOAD_OPTIMIZER")


def place_opt_state(opt_state: Any, shardings: Any, engine: Any | None = None) -> Any:
    """Move a concrete optimizer-state pytree onto its (pinned-host)
    shardings through the shared transfer engine (`parallel/transfer.py`):
    big moment leaves stream in chunks from the worker pool instead of one
    blocking ``jax.device_put`` per leaf. Used by
    `Accelerator.prepare_train_state` when restoring host-offloaded state —
    the Python-level sibling of the in-jit streamed update below (which XLA
    already overlaps with compute)."""
    from ..telemetry import flight as _flight
    from .transfer import get_transfer_engine

    eng = engine if engine is not None else get_transfer_engine()
    if _flight.trace_requests_enabled():
        import time

        n_leaves = len(jax.tree_util.tree_leaves(opt_state))
        t0 = time.perf_counter()
        out = eng.put_tree(opt_state, shardings).result()
        _flight.record_span("hostoffload_h2d_place", t0=t0, leaves=n_leaves)
        return out
    return eng.put_tree(opt_state, shardings).result()


def host_opt_shardings(opt_shapes: Any, opt_shardings: Any) -> Any:
    """Placement for offloaded optimizer state: float leaves (the moments)
    move to pinned host; integer leaves (adam's step count) stay in device
    memory, where the streamed update reads them every step."""
    import jax.numpy as jnp

    def place(shape_leaf, sharding):
        if not isinstance(sharding, NamedSharding):
            return sharding
        if jnp.issubdtype(shape_leaf.dtype, jnp.floating):
            return sharding.with_memory_kind(HOST_MEMORY_KIND)
        return sharding

    return jax.tree.map(place, opt_shapes, opt_shardings)


@functools.lru_cache(maxsize=None)
def host_offload_supported() -> bool:
    """Can this backend keep state in pinned host memory AND run a
    computation there inside jit (`compute_on('device_host')`)? Probed with
    a tiny host-side update — exactly the shape the offloaded train step
    uses. The failure modes are compile-time (unimplemented placement
    custom-call on the CPU simulator), so the probe is cheap and safe."""
    import jax.numpy as jnp
    from jax.experimental.compute_on import compute_on

    try:
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("_probe",))
        host = NamedSharding(mesh, PartitionSpec(), memory_kind=HOST_MEMORY_KIND)

        def host_update(m, g):
            with compute_on("device_host"):
                return 0.9 * m + g

        m = jax.device_put(jnp.zeros((8,), jnp.float32), host)
        g = jax.device_put(jnp.ones((8,), jnp.float32), host)
        out = jax.jit(host_update, out_shardings=host)(m, g)
        return out.sharding.memory_kind == HOST_MEMORY_KIND
    except Exception:
        return False


def warn_host_offload_unsupported() -> None:
    warnings.warn(
        "offload_optimizer was requested but this backend cannot place "
        "arrays in pinned host memory (the CPU simulator lacks the "
        "placement custom-call); optimizer state stays in device memory. "
        "On real TPU hardware the offload is active.",
        stacklevel=3,
    )


# ------------------------------------------------- offload-aware optimizer
class HostOffloadedAdamW(NamedTuple):
    """Duck-types as an `optax.GradientTransformation` (init/update are the
    first two fields) while carrying the hyperparameters the streaming
    train-step path needs to re-derive the math per layer slice."""

    init: Any
    update: Any
    learning_rate: Any  # float or optax schedule (called with the count)
    b1: float
    b2: float
    eps: float
    weight_decay: float
    mu_dtype: Any
    # Top-level param-tree keys whose leaves are layer-stacked (leading dim
    # = n_layers) and therefore updated via the streaming scan. The in-house
    # model zoo stacks under "blocks"; custom models declare their own.
    stacked_paths: tuple


def host_offloaded_adamw(
    learning_rate: Any,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 1e-4,
    mu_dtype: Any = None,
    stacked_paths: tuple = ("blocks",),
) -> HostOffloadedAdamW:
    """AdamW that the offloaded train step can stream layer-by-layer
    (reference: DeepSpeed requires its own CPU-adam for offload_optimizer,
    `utils/deepspeed.py:29`). Without offload it behaves exactly like
    ``optax.adamw`` (same update rule, tested for parity), so one training
    script serves both the real chip and the CPU-simulated mesh."""
    import jax.numpy as jnp

    def init(params):
        def zeros(p, dt=None):
            return jnp.zeros(p.shape, dt or p.dtype)

        return {
            "count": jnp.zeros((), jnp.int32),
            "mu": jax.tree.map(lambda p: zeros(p, mu_dtype), params),
            "nu": jax.tree.map(lambda p: zeros(p, mu_dtype), params),
        }

    def _lr(count):
        return learning_rate(count) if callable(learning_rate) else learning_rate

    def update(grads, state, params):
        # Whole-tree path (used when offload is inactive).
        count = state["count"] + 1
        # optax convention: the schedule sees the number of PREVIOUS updates
        # (schedule(0) on the first step); bias correction uses `count`.
        lr_t = _lr(state["count"])

        def leaf(g, mu, nu, p):
            return _adamw_slice(
                g, mu, nu, p, count, lr_t, b1, b2, eps, weight_decay
            )

        out = jax.tree.map(leaf, grads, state["mu"], state["nu"], params)
        updates = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        new_mu = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        new_nu = jax.tree.map(lambda o: o[2], out, is_leaf=lambda x: isinstance(x, tuple))
        return updates, {"count": count, "mu": new_mu, "nu": new_nu}

    return HostOffloadedAdamW(
        init, update, learning_rate, b1, b2, eps, weight_decay, mu_dtype,
        tuple(stacked_paths),
    )


def _adamw_slice(
    g, mu, nu, p, count, lr_t, b1, b2, eps, weight_decay, grad_scale=None, xp=None
):
    """One adamw step for one leaf (or one layer slice of one leaf); fp32
    moment math, update returned in fp32 (caller casts to param dtype).
    ``grad_scale`` applies global-norm clipping per slice (so the caller
    never materializes a scaled copy of the whole gradient tree).

    ``xp`` is the array namespace: jnp (default — the in-jit streamed
    update) or numpy (the disk-tier update runs on the host against
    memmapped moments, `parallel/disk_offload.py`); one body serves both
    so the two tiers cannot drift numerically.

    On the jnp path, the `fused_adamw` Pallas kernel (`native/pallas/`)
    replaces this body with a single in-place pass when enabled and the
    leaf tiles; the numpy (disk-tier) path never dispatches."""
    if xp is None:
        import jax.numpy as xp  # type: ignore[no-redef]

        from ..native.pallas.fused_adamw import maybe_fused_adamw

        fused = maybe_fused_adamw(
            g, mu, nu, p, count, lr_t, b1, b2, eps, weight_decay, grad_scale
        )
        if fused is not None:
            return fused

    g32 = g.astype(mu.dtype)
    if grad_scale is not None:
        g32 = g32 * xp.asarray(grad_scale, dtype=mu.dtype)
    new_mu = b1 * mu + (1.0 - b1) * g32
    new_nu = b2 * nu + (1.0 - b2) * xp.square(g32)
    c = count.astype(new_mu.dtype) if hasattr(count, "astype") else new_mu.dtype.type(count)
    mu_hat = new_mu / (1.0 - b1**c)
    nu_hat = new_nu / (1.0 - b2**c)
    step = mu_hat / (xp.sqrt(nu_hat) + eps) + weight_decay * p.astype(new_mu.dtype)
    return (-lr_t * step), new_mu, new_nu


def streaming_adamw_update(
    tx: HostOffloadedAdamW,
    grads: Any,
    opt_state: Any,
    params: Any,
    param_specs: Any,
    mesh: Mesh,
    grad_scale: Any = None,
) -> tuple[Any, Any]:
    """The offloaded update: moments live in pinned host RAM; every leaf
    whose param is layer-stacked (our scan-over-layers model layout —
    leading dim = n_layers, leading spec entry None) is updated inside a
    `lax.scan` that DMAs one layer's moment slices HBM-ward, computes, and
    DMAs them back, bounding HBM temps at one layer's working set. Unstacked
    leaves (embeddings, norms, heads) round-trip whole.

    Runs INSIDE the train-step jit; XLA overlaps the per-layer DMAs with
    neighbouring compute."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    count = opt_state["count"] + 1
    # Schedule at the PRE-increment count (optax convention; see update()).
    lr_t = (
        tx.learning_rate(opt_state["count"])
        if callable(tx.learning_rate)
        else tx.learning_rate
    )

    flat_g, treedef = jax.tree_util.tree_flatten_with_path(grads)
    flat_mu = jax.tree.leaves(opt_state["mu"])
    flat_nu = jax.tree.leaves(opt_state["nu"])
    flat_p = jax.tree.leaves(params)
    flat_spec = jax.tree.leaves(param_specs, is_leaf=lambda x: isinstance(x, PartitionSpec))

    upd_leaves, mu_leaves, nu_leaves = [], [], []
    unstacked_bytes = 0
    for (path, g), mu, nu, p, spec in zip(flat_g, flat_mu, flat_nu, flat_p, flat_spec):
        stacked = (
            len(path) > 0
            and getattr(path[0], "key", None) in tx.stacked_paths
            and g.ndim >= 2
        )
        if not stacked:
            unstacked_bytes += 2 * int(np.prod(mu.shape)) * mu.dtype.itemsize
        sliced_spec = PartitionSpec(*spec[1:]) if len(spec) > 0 else PartitionSpec()
        host_slice = NamedSharding(mesh, sliced_spec, memory_kind=HOST_MEMORY_KIND)
        dev_slice = NamedSharding(mesh, sliced_spec)
        if stacked:
            L = g.shape[0]

            def body(carry, i, g=g, mu=mu, nu=nu, p=p, hs=host_slice, ds=dev_slice):
                mu_i = jax.device_put(
                    jax.lax.dynamic_index_in_dim(mu, i, 0, keepdims=False), ds
                )
                nu_i = jax.device_put(
                    jax.lax.dynamic_index_in_dim(nu, i, 0, keepdims=False), ds
                )
                g_i = jax.lax.dynamic_index_in_dim(g, i, 0, keepdims=False)
                p_i = jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False)
                u_i, mu2, nu2 = _adamw_slice(
                    g_i, mu_i, nu_i, p_i, count, lr_t,
                    tx.b1, tx.b2, tx.eps, tx.weight_decay,
                    grad_scale=grad_scale,
                )
                return carry, (
                    u_i.astype(p.dtype),
                    jax.device_put(mu2, hs),
                    jax.device_put(nu2, hs),
                )

            _, (u, new_mu, new_nu) = jax.lax.scan(
                body, 0, jnp.arange(L, dtype=jnp.int32)
            )
        else:
            full_host = NamedSharding(mesh, spec, memory_kind=HOST_MEMORY_KIND)
            full_dev = NamedSharding(mesh, spec)
            mu_d = jax.device_put(mu, full_dev)
            nu_d = jax.device_put(nu, full_dev)
            u, mu2, nu2 = _adamw_slice(
                g, mu_d, nu_d, p, count, lr_t,
                tx.b1, tx.b2, tx.eps, tx.weight_decay,
                grad_scale=grad_scale,
            )
            u = u.astype(p.dtype)
            new_mu = jax.device_put(mu2, full_host)
            new_nu = jax.device_put(nu2, full_host)
        upd_leaves.append(u)
        mu_leaves.append(new_mu)
        nu_leaves.append(new_nu)

    if unstacked_bytes > (2 << 30):
        # Whole-leaf round trips become simultaneous HBM temps; past ~2 GiB
        # that silently erodes the headroom offload exists to create.
        warnings.warn(
            f"{unstacked_bytes / 2**30:.1f} GiB of offloaded moments belong "
            "to leaves outside the declared layer-stacked paths "
            f"{tx.stacked_paths}; they round-trip through HBM whole. If the "
            "model stacks its layers under a different key, pass "
            "host_offloaded_adamw(..., stacked_paths=(<key>,)).",
            stacklevel=2,
        )
    unflatten = jax.tree_util.tree_unflatten
    updates = unflatten(treedef, upd_leaves)
    return updates, {
        "count": count,
        "mu": unflatten(treedef, mu_leaves),
        "nu": unflatten(treedef, nu_leaves),
    }
