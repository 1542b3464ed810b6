"""Async chunked host<->device transfer engine — the shared hot path for
every Python-dispatched byte that crosses the host/device link.

Why it exists: a blocking whole-leaf ``jax.device_put`` serializes the
link behind Python-level per-leaf dispatch (one giant call at a time), which
bounds the 8B big-model load, host-offloaded AdamW and over-RAM streamed
decode alike. This module turns every such transfer into *chunks issued
concurrently from a worker pool*, with prefetch and completion futures so
traffic overlaps compute instead of blocking it.

Three mechanisms, one engine:

- **Chunked H2D** (`TransferEngine.put`): a large host leaf is split into
  row-chunks; each chunk is read (memmap -> RAM), cast, and
  ``jax.device_put`` from the pool (multiple streams in flight), then
  folded into a preallocated device buffer with a donated
  ``dynamic_update_slice`` — device memory holds the destination buffer
  plus a bounded window of chunks, never 2x the leaf.
- **Layer prefetch queue** (`TransferEngine.prefetch`): while layer *k*
  executes, layers *k+1..k+depth* are already in flight (double-buffered
  device slots; ``big_modeling.streamed_scan`` rides this).
- **D2H draining** (`TransferEngine.get` / `get_tree`): device->host
  copies start asynchronously and complete on the pool, returning
  futures — optimizer-moment writeback overlaps the next step's compute
  (``parallel/disk_offload.py`` rides this).

Consumers (the three hot paths the engine unifies): big-model load +
over-RAM layer streaming (`big_modeling.py`), host-offloaded /
disk-offloaded AdamW (`accelerator.py` + `parallel/disk_offload.py`), and
generic pytree placement (`parallel/sharding.shard_pytree`).

Knobs (read at engine construction; all safe to leave alone):

- ``ATX_TRANSFER_CHUNK_MIB`` (default 64): chunk size; smaller chunks
  overlap better through high-latency links, larger chunks amortize
  per-call overhead on fast PCIe hosts.
- ``ATX_TRANSFER_WORKERS`` (default 4): concurrent transfer streams.
- ``ATX_TRANSFER_PREFETCH`` (default 2): layer prefetch depth (>= 2 keeps
  one layer computing while the next is fully in flight).
- ``ATX_OFFLOAD_OVERLAP`` (default on): lets the offloaded-optimizer
  tiers overlap step *N* moment traffic with step *N+1* compute
  (`overlap_enabled`); set to 0 to force the old blocking behavior.
"""

from __future__ import annotations

import collections
import functools
import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Iterator

import jax
import numpy as np
from jax.sharding import NamedSharding, SingleDeviceSharding

__all__ = [
    "TransferEngine",
    "TreeFuture",
    "get_transfer_engine",
    "overlap_enabled",
]

DEFAULT_CHUNK_MIB = 64
DEFAULT_WORKERS = 4
DEFAULT_PREFETCH_DEPTH = 2


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def overlap_enabled() -> bool:
    """Offloaded-optimizer overlap mode (``ATX_OFFLOAD_OVERLAP``): ON by
    default — step N's moment D2H/writeback/flush overlaps step N+1's
    compute. Opt out with 0/false/off (the result is bit-identical either
    way — overlap changes scheduling, never the math; tested)."""
    v = os.environ.get("ATX_OFFLOAD_OVERLAP", "1").strip().lower()
    return v not in ("0", "false", "no", "off", "")


class TreeFuture:
    """Future over a pytree of per-leaf transfer futures (what
    `TransferEngine.put_tree` / `get_tree` return)."""

    def __init__(self, treedef: Any, futures: list) -> None:
        self._treedef = treedef
        self._futures = futures

    def result(self, timeout: float | None = None) -> Any:
        leaves = [f.result(timeout) for f in self._futures]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def done(self) -> bool:
        return all(f.done() for f in self._futures)


class TransferEngine:
    """Shared async chunked transfer engine (module docstring). One
    instance per process is the intent (`get_transfer_engine`); tests
    construct their own with tiny ``chunk_bytes`` to force the chunk
    path on small arrays.

    Thread model: ``workers`` pool threads run chunk reads + device_put
    dispatch (the concurrent streams); a small assembler pool folds chunks
    into destination buffers and completes leaf futures. Worker exceptions
    propagate through ``Future.result()`` — nothing is swallowed."""

    def __init__(
        self,
        *,
        chunk_bytes: int | None = None,
        workers: int | None = None,
        prefetch_depth: int | None = None,
    ) -> None:
        self.chunk_bytes = int(
            chunk_bytes
            if chunk_bytes is not None
            else _env_int("ATX_TRANSFER_CHUNK_MIB", DEFAULT_CHUNK_MIB) << 20
        )
        self.chunk_bytes = max(1, self.chunk_bytes)
        self.workers = max(
            1,
            int(
                workers
                if workers is not None
                else _env_int("ATX_TRANSFER_WORKERS", DEFAULT_WORKERS)
            ),
        )
        self.prefetch_depth = max(
            1,
            int(
                prefetch_depth
                if prefetch_depth is not None
                else _env_int("ATX_TRANSFER_PREFETCH", DEFAULT_PREFETCH_DEPTH)
            ),
        )
        self._pool = ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="atx-transfer"
        )
        # Assembly only ever waits on _pool futures (never on other
        # assembly jobs), so the two pools cannot deadlock each other.
        self._assembler = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="atx-transfer-asm"
        )
        self._jit_lock = threading.Lock()
        self._fold_jits: dict = {}
        self._alloc_jits: dict = {}
        # Link-traffic telemetry (docs/observability.md). Counters are
        # thread-safe; incremented from pool workers alongside the copies
        # they describe, so the registry view tracks in-flight progress.
        from .. import telemetry as _telemetry

        self._c_h2d = _telemetry.counter(
            "transfer_h2d_bytes", "Host-to-device bytes moved by TransferEngine")
        self._c_d2h = _telemetry.counter(
            "transfer_d2h_bytes", "Device-to-host bytes drained by TransferEngine")
        self._c_chunks = _telemetry.counter(
            "transfer_chunks", "Chunked H2D copy windows dispatched")
        self._h_chunk = _telemetry.histogram(
            "transfer_chunk_bytes",
            "Size of each H2D transfer (whole leaf or chunk window)",
            buckets=_telemetry.DEFAULT_BYTES_BUCKETS,
        )

    # ------------------------------------------------------------- generic
    def submit(self, fn: Callable, *args: Any, **kwargs: Any) -> Future:
        """Run ``fn`` on the transfer worker pool (host-side staging,
        writeback, or any transfer-adjacent work that should overlap the
        caller). Exceptions surface at ``.result()``."""
        return self._pool.submit(fn, *args, **kwargs)

    def close(self) -> None:
        self._pool.shutdown(wait=True)
        self._assembler.shutdown(wait=True)

    # ----------------------------------------------------------------- H2D
    def _should_chunk(self, x: Any, sharding: Any) -> bool:
        """Chunk host (numpy/memmap) leaves whose leading dim is not
        partitioned — a chunk then satisfies the same sharding as the whole
        leaf, and the fold preserves the layout. Device-resident arrays and
        dim-0-sharded leaves take the single-shot path (resharding and
        scatter belong to XLA / make_array, not to row chunking)."""
        if not isinstance(x, np.ndarray):
            return False
        if x.ndim == 0 or x.shape[0] <= 1:
            return False
        if x.nbytes <= self.chunk_bytes:
            return False
        if sharding is None or isinstance(sharding, SingleDeviceSharding):
            return True
        if isinstance(sharding, NamedSharding):
            spec = sharding.spec
            return len(spec) == 0 or spec[0] is None
        return False

    def _fold_fn(self, sharding: Any):
        """Jitted ``buf[start:start+rows] = chunk`` with a donated buffer:
        the destination updates in place, so device memory holds the buffer
        plus one in-flight chunk window, never a full second copy."""
        key = sharding
        with self._jit_lock:
            fn = self._fold_jits.get(key)
            if fn is None:

                def fold(buf, chunk, start):
                    return jax.lax.dynamic_update_slice_in_dim(
                        buf, chunk, start, axis=0
                    )

                kwargs: dict = {"donate_argnums": (0,)}
                if isinstance(sharding, NamedSharding):
                    kwargs["out_shardings"] = sharding
                fn = jax.jit(fold, **kwargs)
                self._fold_jits[key] = fn
            return fn

    def _alloc(self, shape: tuple, dtype: Any, sharding: Any):
        if sharding is None:
            import jax.numpy as jnp

            return jnp.zeros(shape, dtype)
        if isinstance(sharding, SingleDeviceSharding):
            import jax.numpy as jnp

            return jax.device_put(jnp.zeros(shape, dtype), sharding)
        key = (tuple(shape), np.dtype(dtype).str, sharding)
        with self._jit_lock:
            fn = self._alloc_jits.get(key)
            if fn is None:
                import jax.numpy as jnp

                if len(self._alloc_jits) > 512:  # runaway-shape backstop
                    self._alloc_jits.clear()
                fn = jax.jit(
                    functools.partial(jnp.zeros, tuple(shape), dtype),
                    out_shardings=sharding,
                )
                self._alloc_jits[key] = fn
        return fn()

    def put(self, x: Any, sharding: Any = None, dtype: Any = None) -> Future:
        """Asynchronously place one leaf on device; returns a Future whose
        result is the device array. Host leaves larger than ``chunk_bytes``
        (leading dim unsharded) go through the chunked multi-stream path;
        everything else is a single pooled ``device_put``. ``dtype`` casts
        on the worker (per chunk — the full-precision leaf is never
        materialized twice on the host)."""
        if self._should_chunk(x, sharding):
            return self._put_chunked(x, sharding, dtype)

        def _single(x=x, sharding=sharding, dtype=dtype):
            if dtype is not None:
                if isinstance(x, np.ndarray):
                    x = np.asarray(x, dtype=np.dtype(dtype))
                elif hasattr(x, "astype"):
                    x = x.astype(dtype)
            nbytes = int(getattr(x, "nbytes", 0) or 0)
            if nbytes:
                self._c_h2d.inc(nbytes)
                self._h_chunk.observe(nbytes)
            if sharding is None:
                return jax.device_put(x)
            return jax.device_put(x, sharding)

        return self._pool.submit(_single)

    def _put_chunked(self, x: np.ndarray, sharding: Any, dtype: Any) -> Future:
        shape = tuple(x.shape)
        out_dtype = np.dtype(dtype) if dtype is not None else np.dtype(x.dtype)
        row_bytes = int(np.prod(shape[1:], dtype=np.int64)) * out_dtype.itemsize
        rows = max(1, self.chunk_bytes // max(1, row_bytes))
        starts = list(range(0, shape[0], rows))

        def read_put(s: int):
            # The memmap/RAM read, the cast, and the device_put all happen
            # here on a pool worker — concurrent chunks are the multiple
            # streams that aggregate link bandwidth.
            chunk = np.asarray(x[s : s + rows], dtype=out_dtype)
            self._c_h2d.inc(chunk.nbytes)
            self._c_chunks.inc()
            self._h_chunk.observe(chunk.nbytes)
            if sharding is None:
                return jax.device_put(chunk)
            return jax.device_put(chunk, sharding)

        # Bounded in-flight window: the first chunks start transferring
        # NOW (before the assembler gets scheduled), the rest are issued
        # as the fold consumes — host+device never hold the whole leaf
        # twice.
        window = self.workers + 2
        pending: collections.deque = collections.deque(
            self._pool.submit(read_put, s) for s in starts[:window]
        )
        result: Future = Future()

        def assemble():
            try:
                buf = self._alloc(shape, out_dtype, sharding)
                fold = self._fold_fn(sharding)
                for i, s in enumerate(starts):
                    f = pending.popleft()
                    if i + window < len(starts):
                        pending.append(self._pool.submit(read_put, starts[i + window]))
                    buf = fold(buf, f.result(), s)
                result.set_result(buf)
            except BaseException as e:  # propagate worker errors verbatim
                for f in pending:
                    f.cancel()
                result.set_exception(e)

        self._assembler.submit(assemble)
        return result

    def put_tree(self, tree: Any, shardings: Any = None, dtype: Any = None) -> TreeFuture:
        """`put` over a pytree. ``shardings`` is None (default placement),
        one Sharding applied to every leaf, or a matching pytree of
        Shardings (None leaves allowed)."""
        flat, treedef = jax.tree_util.tree_flatten(tree)
        if shardings is None:
            sh_flat = [None] * len(flat)
        elif isinstance(shardings, jax.sharding.Sharding):
            sh_flat = [shardings] * len(flat)
        else:
            sh_flat, _ = jax.tree_util.tree_flatten(
                shardings,
                is_leaf=lambda s: s is None or isinstance(s, jax.sharding.Sharding),
            )
            if len(sh_flat) != len(flat):
                raise ValueError(
                    f"put_tree: shardings tree has {len(sh_flat)} leaves but "
                    f"the value tree has {len(flat)}."
                )
        futures = [self.put(x, s, dtype) for x, s in zip(flat, sh_flat)]
        return TreeFuture(treedef, futures)

    # ----------------------------------------------------------------- D2H
    def get(self, x: Any) -> Future:
        """Asynchronous device->host drain of one leaf: the copy starts
        immediately (``copy_to_host_async``) and completes on a pool
        worker; the Future resolves to a numpy array."""
        if isinstance(x, jax.Array):
            try:
                x.copy_to_host_async()
            except Exception:
                pass  # backends without async copy fall through to asarray

        def _drain(x=x):
            out = np.asarray(x)
            self._c_d2h.inc(out.nbytes)
            return out

        return self._pool.submit(_drain)

    def get_tree(self, tree: Any) -> TreeFuture:
        """`get` over a pytree — all leaves drain concurrently."""
        flat, treedef = jax.tree_util.tree_flatten(tree)
        futures = [self.get(x) for x in flat]
        return TreeFuture(treedef, futures)

    # ------------------------------------------------------------ prefetch
    def prefetch(
        self, n: int, stage: Callable[[int], Any], depth: int | None = None
    ) -> Iterator[Any]:
        """Layer-granularity prefetch queue: yields ``stage(0..n-1)``
        results in order, keeping ``depth`` stages in flight — while the
        caller consumes item *k*, items *k+1..k+depth* are transferring
        (the double-buffered device slots of `big_modeling.streamed_scan`).

        ``stage(i)`` is called exactly once per index, in order, and may
        return a Future/TreeFuture (resolved here) or a plain value. A
        stage that raised re-raises at its yield point."""
        depth = self.prefetch_depth if depth is None else max(1, int(depth))

        def gen():
            pending: collections.deque = collections.deque()
            for i in range(min(depth, n)):
                pending.append(stage(i))
            for i in range(n):
                item = pending.popleft()
                if i + depth < n:
                    # Refill BEFORE blocking on the current item so the
                    # pipeline stays `depth` deep while we wait.
                    pending.append(stage(i + depth))
                yield item.result() if hasattr(item, "result") else item

        return gen()


_ENGINE: TransferEngine | None = None
_ENGINE_LOCK = threading.Lock()


def get_transfer_engine() -> TransferEngine:
    """The process-wide engine (one worker pool shared by every consumer —
    concurrent loads/steps share the link fairly instead of oversubscribing
    it with private pools)."""
    global _ENGINE
    with _ENGINE_LOCK:
        if _ENGINE is None:
            _ENGINE = TransferEngine()
        return _ENGINE
