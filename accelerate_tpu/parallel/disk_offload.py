"""Disk-tier (NVMe-analog) optimizer-state offload — beyond the host tier.

Reference: DeepSpeed ZeRO-Infinity offloads optimizer state to NVMe
(`utils/dataclasses.py:1055-1111` ``offload_optimizer.device: nvme`` +
``nvme_path``, `utils/deepspeed.py:29` — requires DeepSpeedCPUAdam); the
repo's host tier (`parallel/host_offload.py`) stops at pinned host RAM.
This module adds the disk tier: adam moments live in fp32 **memmaps** on
disk and never reside in HBM *or* host RAM beyond one layer's working set.

Design (TPU-native split, mirroring DeepSpeed's CPU-adam shape):

- the COMPILED step computes loss/grads (+ the global-norm clip scale) on
  device — all the MXU math stays under jit;
- the UPDATE runs on the host, streamed one layer-slice at a time: read
  the slice's mu/nu from the memmap, fetch the grad slice, run the SAME
  ``_adamw_slice`` body as the in-jit host tier (numpy namespace — one
  implementation, no numeric drift), write the moments back, and stage
  the parameter update;
- params are then updated on device with one transfer per leaf.

The memmaps double as the optimizer checkpoint: they persist in
``offload_dir`` across process restarts (`DiskMomentStore` reopens them),
so `save_state`/`load_state` only need the step count — the moments are
already on disk, exactly like DeepSpeed's NVMe swap files.

Single-process by design (like DeepSpeed's per-node NVMe swap): sharded
non-addressable params are refused loudly with the remediation (use the
pinned-host tier, whose update runs inside the compiled SPMD program).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, NamedTuple

import jax
import numpy as np

from ..telemetry import flight as _flight
from .host_offload import _adamw_slice

__all__ = ["DiskMomentStore", "DiskOffloadedAdamW", "disk_offloaded_adamw"]

# In-flight async moment writebacks (flush + sentinel clear), keyed by the
# store directory's realpath. A second store instance over the same dir
# (checkpoint-resume tests, same-process handoff) joins the pending flush
# before judging the dirty sentinel.
_PENDING_WRITEBACK: dict[str, Any] = {}
_PENDING_LOCK = threading.Lock()


class DiskMomentStore:
    """fp32 adam moments as memmaps under ``offload_dir`` (one ``.mu.bin``/
    ``.nu.bin`` pair per param leaf, plus a manifest with shapes so a
    restart can validate it is resuming the same model).

    Crash safety: `begin_update` writes a dirty sentinel (``dirty.json``)
    BEFORE the first memmap mutation of a step and `end_update` removes it
    after the flush — a process that dies mid-update leaves the sentinel
    behind, and both resume (this constructor) and same-process retry
    (`begin_update`) refuse while it is set. Without it, a crash between
    two leaves would let a retry re-apply the update to already-written
    moments (double-stepped mu/nu — round-5 advisor finding)."""

    def __init__(self, offload_dir: str) -> None:
        self.dir = offload_dir
        os.makedirs(offload_dir, exist_ok=True)
        self._maps: dict[str, tuple[np.memmap, np.memmap]] = {}
        # Join any async flush still in flight over this dir before judging
        # the sentinel (a clean in-progress writeback is not a crash).
        self.wait_writeback()
        self._refuse_if_dirty(resuming=True)

    # ------------------------------------------------ dirty-sentinel guard
    def _dirty_path(self) -> str:
        return os.path.join(self.dir, "dirty.json")

    def _refuse_if_dirty(self, resuming: bool) -> None:
        path = self._dirty_path()
        if not os.path.exists(path):
            return
        try:
            with open(path) as f:
                at = json.load(f).get("count")
        except ValueError:
            at = "?"
        raise ValueError(
            f"disk-offloaded moments in {self.dir!r} carry a dirty sentinel: "
            f"a moment update (toward step {at}) died mid-update, so some "
            "leaves hold step-N moments and others step-N-1 — "
            + ("resuming" if resuming else "retrying")
            + " would re-apply the update to the already-written leaves "
            "(double-stepped mu/nu). Point offload_dir at a fresh directory "
            "to restart the optimizer, or restore a full checkpoint."
        )

    def begin_update(self, count: int) -> None:
        """Mark the store dirty BEFORE the first memmap mutation of the
        update toward ``count``; refuses if a previous update never
        completed (crash or mid-update exception)."""
        self._refuse_if_dirty(resuming=False)
        path = self._dirty_path()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"count": int(count)}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def end_update(self) -> None:
        """Clear the dirty sentinel (the update fully hit the memmaps and
        the flush completed)."""
        try:
            os.remove(self._dirty_path())
        except FileNotFoundError:
            pass

    # ------------------------------------------------- async moment flush
    def _pending_key(self) -> str:
        return os.path.realpath(self.dir)

    def wait_writeback(self) -> None:
        """Join the in-flight async flush for this dir, re-raising any
        writeback error here (the overlap contract: step N's flush must
        complete — successfully — before step N+1 touches the moments)."""
        with _PENDING_LOCK:
            fut = _PENDING_WRITEBACK.pop(self._pending_key(), None)
        if fut is not None:
            fut.result()

    def flush_async(self, count: int, engine: Any | None = None) -> None:
        """`flush` + `end_update` on a transfer-engine worker so the msync
        and count.json write overlap the NEXT step's compute instead of
        blocking this one (the D2H-drain completion-future pattern —
        `parallel/transfer.py`). `wait_writeback` joins it."""
        from .transfer import get_transfer_engine

        eng = engine if engine is not None else get_transfer_engine()

        def _do():
            self.flush(count=count)
            self.end_update()

        with _PENDING_LOCK:
            prev = _PENDING_WRITEBACK.get(self._pending_key())
            if prev is not None and not prev.done():
                # Never reorder two writebacks over one dir.
                fut = eng.submit(lambda: (prev.result(), _do())[1])
            else:
                fut = eng.submit(_do)
            _PENDING_WRITEBACK[self._pending_key()] = fut

    def _paths(self, key: str) -> tuple[str, str, str]:
        safe = key.replace("/", "__")
        return (
            os.path.join(self.dir, f"{safe}.mu.bin"),
            os.path.join(self.dir, f"{safe}.nu.bin"),
            os.path.join(self.dir, f"{safe}.json"),
        )

    def open(self, key: str, shape: tuple[int, ...]) -> tuple[np.memmap, np.memmap]:
        """Open (or create zero-initialized) moment memmaps for a leaf."""
        if key in self._maps:
            return self._maps[key]
        mu_p, nu_p, man_p = self._paths(key)
        if os.path.exists(man_p):
            with open(man_p) as f:
                manifest = json.load(f)
            if tuple(manifest["shape"]) != tuple(shape):
                raise ValueError(
                    f"disk-offloaded moments at {man_p} were written for "
                    f"shape {manifest['shape']}, not {tuple(shape)} — the "
                    "offload_dir belongs to a different model; point "
                    "offload_dir somewhere fresh."
                )
            mode = "r+"
        else:
            for p in (mu_p, nu_p):
                with open(p, "wb") as f:
                    f.truncate(int(np.prod(shape)) * 4)  # zero-filled fp32
            with open(man_p, "w") as f:
                json.dump({"shape": list(shape), "dtype": "float32"}, f)
            mode = "r+"
        pair = (
            np.memmap(mu_p, mode=mode, dtype=np.float32, shape=tuple(shape)),
            np.memmap(nu_p, mode=mode, dtype=np.float32, shape=tuple(shape)),
        )
        self._maps[key] = pair
        return pair

    def flush(self, count: int | None = None) -> None:
        for mu, nu in self._maps.values():
            mu.flush()
            nu.flush()
        if count is not None:
            # Atomic replace: this rewrites every step, and a crash inside a
            # plain open('w') would leave an empty file that blocks resume.
            path = os.path.join(self.dir, "count.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"count": int(count)}, f)
            os.replace(tmp, path)

    def count(self) -> int | None:
        """The step count the moments were last flushed at (None = fresh
        store). Lets resume detect a state/moments mismatch: restoring any
        checkpoint other than the latest would otherwise silently pair an
        old count with newer moments. Joins any in-flight async flush first
        so the answer reflects the latest completed update."""
        self.wait_writeback()
        path = os.path.join(self.dir, "count.json")
        if not os.path.exists(path):
            return None
        with open(path) as f:
            return int(json.load(f)["count"])


class DiskOffloadedAdamW(NamedTuple):
    """Duck-types as `optax.GradientTransformation` (init/update first) —
    but the real update path is `Accelerator.make_train_step`'s disk
    branch, which streams through ``store``. The plain ``update`` exists
    so the object is still a valid optax transformation for code that
    inspects it; calling it raises with the remediation."""

    init: Any
    update: Any
    learning_rate: Any
    b1: float
    b2: float
    eps: float
    weight_decay: float
    store: DiskMomentStore
    stacked_paths: tuple


def disk_offloaded_adamw(
    learning_rate: Any,
    *,
    offload_dir: str,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 1e-4,
    stacked_paths: tuple = ("blocks",),
) -> DiskOffloadedAdamW:
    """AdamW whose moments live on DISK (the ZeRO-Infinity ``nvme`` tier).

    Use with ``Accelerator.create_train_state``/``make_train_step`` — the
    step splits into a compiled grad pass and a host-streamed update (see
    module docstring). ``offload_dir`` holds the fp32 moment memmaps and
    persists across restarts (it IS the optimizer checkpoint)."""
    import jax.numpy as jnp

    store = DiskMomentStore(offload_dir)

    def init(params):
        # Touch every leaf's memmaps now so resume-shape mismatches fail at
        # create_train_state, not mid-training.
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        for path, leaf in flat:
            store.open(_key(path), tuple(leaf.shape))
        return {"count": jnp.zeros((), jnp.int32)}

    def update(grads, state, params=None):
        raise NotImplementedError(
            "disk_offloaded_adamw cannot run as a plain optax transformation "
            "(its moments are disk memmaps outside the jit); drive it through "
            "Accelerator.make_train_step, which builds the split "
            "grad-pass + streamed-host-update step."
        )

    return DiskOffloadedAdamW(
        init, update, learning_rate, b1, b2, eps, weight_decay, store,
        tuple(stacked_paths),
    )


def _key(path: tuple) -> str:
    from ..parallel.sharding import _path_str

    return _path_str(path)


def disk_streamed_update(
    tx: DiskOffloadedAdamW,
    grads: Any,
    params: Any,
    count: int,
    grad_scale: float | None,
    *,
    overlap: bool | None = None,
) -> Any:
    """Host-side streamed adamw over disk-resident moments.

    ``grads``/``params`` are device arrays (fully addressable — the single
    -process constraint is checked by the caller); returns a pytree of
    numpy UPDATES (same structure/dtype as params) for the caller to apply
    on device. Layer-stacked leaves stream one layer at a time, so peak
    host RAM is a small window of layers' (grad + 2 moments); moments hit
    the memmaps (page cache -> disk) as they are produced.

    Overlap mode (default ON — ``ATX_OFFLOAD_OVERLAP``, see
    `parallel/transfer.py`): the D2H drain of slice *i+1*'s grad/param
    runs on the transfer engine's workers while slice *i*'s numpy math
    executes, and the final memmap flush + count bump is handed to a
    writeback worker whose completion future the NEXT update joins — so
    the msync overlaps step N+1's compiled grad pass instead of blocking
    step N. The math (and therefore the moments) is bit-identical with
    overlap on or off: the same slices run the same ops in the same
    order; only the scheduling moves (tested)."""
    from .transfer import get_transfer_engine, overlap_enabled

    do_overlap = overlap_enabled() if overlap is None else bool(overlap)
    engine = get_transfer_engine()
    # Transfer-overlap spans (docs/observability.md):
    # host clocks only, so the update math stays bit-identical either way.
    trace = _flight.trace_requests_enabled()
    t_update0 = time.perf_counter() if trace else 0.0
    # Step N-1's async flush must have COMPLETED (successfully) before this
    # update reads or mutates the memmaps; its errors re-raise here.
    t_wb0 = time.perf_counter() if trace else 0.0
    tx.store.wait_writeback()
    if trace:
        # How long step N stalls on step N-1's memmap flush — the overlap
        # mode exists to drive this span toward zero.
        _flight.record_span(
            "hostoffload_writeback_wait", t0=t_wb0, overlap=do_overlap
        )
    # Dirty sentinel BEFORE the first memmap mutation: a crash anywhere in
    # the loop below leaves it set, and resume/retry refuse loudly instead
    # of re-applying the update to already-written leaves.
    tx.store.begin_update(count)
    from ..resilience.commit import fault_point

    fault_point("disk.after_sentinel")
    # One host float per step: a schedule returns a jax scalar, and letting
    # it into the numpy slice math would silently promote every slice to a
    # device op (round-tripping each layer through the slow link twice —
    # the exact traffic this tier exists to avoid). Schedule at the
    # PRE-increment count (optax convention: schedule(0) on the first step).
    lr_t = (
        float(tx.learning_rate(count - 1)) if callable(tx.learning_rate)
        else float(tx.learning_rate)
    )
    c = np.float32(count)
    flat_g, treedef = jax.tree_util.tree_flatten_with_path(grads)
    flat_p = jax.tree.leaves(params)

    # Flat worklist of (leaf index, layer index | None) slices spanning ALL
    # leaves, so the D2H prefetch pipelines across leaf boundaries too.
    jobs: list[tuple[int, int | None]] = []
    opened: list[tuple[np.memmap, np.memmap]] = []
    stacked_flags: list[bool] = []
    updates: list[np.ndarray] = []
    for li, ((path, g), p) in enumerate(zip(flat_g, flat_p)):
        key = _key(path)
        opened.append(tx.store.open(key, tuple(g.shape)))
        stacked = (
            len(path) > 0
            and getattr(path[0], "key", None) in tx.stacked_paths
            and g.ndim >= 2
        )
        stacked_flags.append(stacked)
        updates.append(np.empty(g.shape, dtype=np.dtype(p.dtype)))
        if stacked:
            jobs.extend((li, i) for i in range(g.shape[0]))
        else:
            jobs.append((li, None))

    def fetch(job: tuple[int, int | None]) -> tuple[np.ndarray, np.ndarray]:
        li, i = job
        g, p = flat_g[li][1], flat_p[li]
        if i is not None:
            g, p = g[i], p[i]
        return (
            np.asarray(jax.device_get(g), np.float32),
            np.asarray(jax.device_get(p), np.float32),
        )

    if do_overlap:
        fetched = engine.prefetch(
            len(jobs), lambda idx: engine.submit(fetch, jobs[idx])
        )
    else:
        fetched = (fetch(job) for job in jobs)

    d2h_wait = [0.0]
    if trace:
        # Host-visible D2H stall: time blocked pulling the next fetched
        # slice (with prefetch armed, work already in flight hides here).
        def _timed(it: Any) -> Any:
            while True:
                t0 = time.perf_counter()
                try:
                    item = next(it)
                except StopIteration:
                    return
                d2h_wait[0] += time.perf_counter() - t0
                yield item

        fetched = _timed(iter(fetched))

    for (li, i), (g_h, p_h) in zip(jobs, fetched):
        mu, nu = opened[li]
        out = updates[li]
        if i is not None:
            u_i, mu_i, nu_i = _adamw_slice(
                g_h, mu[i], nu[i], p_h, c, lr_t,
                tx.b1, tx.b2, tx.eps, tx.weight_decay,
                grad_scale=grad_scale, xp=np,
            )
            mu[i] = mu_i
            nu[i] = nu_i
            out[i] = u_i.astype(out.dtype)
        else:
            u, mu_n, nu_n = _adamw_slice(
                g_h, mu[...], nu[...], p_h, c, lr_t,
                tx.b1, tx.b2, tx.eps, tx.weight_decay,
                grad_scale=grad_scale, xp=np,
            )
            mu[...] = mu_n
            nu[...] = nu_n
            out[...] = u.astype(out.dtype)

    t_flush0 = time.perf_counter() if trace else 0.0
    if do_overlap:
        # msync + count bump + sentinel clear overlap step N+1's compute;
        # the next update (or the next store over this dir) joins it.
        tx.store.flush_async(count=count, engine=engine)
    else:
        tx.store.flush(count=count)
        tx.store.end_update()
    if trace:
        _flight.record_span(
            "hostoffload_memmap_flush", t0=t_flush0, overlap=do_overlap
        )
        _flight.record_span(
            "hostoffload_update",
            t0=t_update0,
            step=int(count),
            slices=len(jobs),
            overlap=do_overlap,
            d2h_wait_ms=round(d2h_wait[0] * 1e3, 3),
        )
    return jax.tree_util.tree_unflatten(treedef, updates)
