"""Big-model inference: shape-only init, HBM-budget planning, streamed
sharded loading, and host-RAM offload for over-HBM models.

TPU-native redesign of the reference big-modeling stack:

- `init_empty_weights` (reference `big_modeling.py:58`): torch meta device ->
  `jax.eval_shape`. Nothing is allocated; the result is a pytree of
  ShapeDtypeStructs that the planner and loaders consume.
- `infer_sharding_plan` (reference `utils/modeling.py:1281`
  `infer_auto_device_map` + `:923` `get_balanced_memory`): the reference
  greedily assigns whole layers to devices ("device map"); on TPU the analog
  is a PartitionSpec per leaf over the mesh — GSPMD shards every layer across
  all chips instead of pinning layers to single chips, which is both the
  faster and the simpler layout. The planner starts from the family's TP/FSDP
  rules, measures per-device bytes against the HBM budget, widens sharding if
  needed, and spills the largest leaves to host RAM last (the
  `cpu_offload` analog, reference `big_modeling.py:170`).
- `load_checkpoint_and_dispatch` (reference `big_modeling.py:511`,
  `utils/modeling.py:1787`): streams a checkpoint leaf-by-leaf straight into
  sharded device buffers — each device fetches exactly its slice via
  `jax.make_array_from_callback`, so no host ever materializes the full
  model. Reads this framework's sharded format, consolidated `.npz`, and
  HF-style safetensors (single file or `*.index.json` shards).
- `offload_blocks` / `streamed_scan` (reference `hooks.py:226`
  `AlignDevicesHook`, `utils/offload.py:127`): for scan-over-layers models
  whose stacked blocks exceed HBM, block params stay in host RAM and stream
  one layer ahead of compute (double buffering) — the forward-hook
  weight-staging pattern without monkey-patching forward.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .parallel.sharding import (
    Rules,
    _path_str,
    _sanitize_spec,
    _shard_largest_dim,
)

__all__ = [
    "init_empty_weights",
    "compute_leaf_sizes",
    "ShardingPlan",
    "infer_sharding_plan",
    "load_checkpoint_and_dispatch",
    "offload_blocks",
    "streamed_scan",
]


def init_empty_weights(init_fn: Callable[..., Any], *args: Any, **kwargs: Any) -> Any:
    """Shape-only "materialization" of a model (reference `init_empty_weights`,
    `big_modeling.py:58`): returns the params pytree as ShapeDtypeStructs
    without allocating anything, on host or device."""
    return jax.eval_shape(init_fn, *args, **kwargs)


def _leaf_bytes(leaf: Any, dtype: Any | None = None) -> int:
    shape = tuple(getattr(leaf, "shape", ()))
    dt = np.dtype(dtype) if dtype is not None else np.dtype(leaf.dtype)
    return int(np.prod(shape)) * dt.itemsize if shape else dt.itemsize


def compute_leaf_sizes(shapes: Any, dtype: Any | None = None) -> dict[str, int]:
    """Per-leaf byte sizes (reference `compute_module_sizes`,
    `utils/modeling.py:656`). ``dtype`` overrides each leaf's dtype (e.g.
    planning a bf16 deployment of fp32-initialized weights)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return {_path_str(path): _leaf_bytes(leaf, dtype) for path, leaf in flat}


@dataclass
class ShardingPlan:
    """The TPU "device map": a PartitionSpec per leaf + host-offload set.

    ``specs`` is a pytree matching the params; ``offload`` holds the leaf
    paths that stay in host RAM; ``fits`` says whether the on-device portion
    fits the per-device budget; ``per_device_bytes`` is the planned resident
    HBM per chip (offloaded leaves count only via ``streaming_bytes`` — the
    largest single offloaded leaf that must be staged during execution).
    """

    specs: Any
    mesh: Mesh
    offload: set[str] = field(default_factory=set)
    per_device_bytes: int = 0
    streaming_bytes: int = 0
    budget_bytes: int | None = None
    total_bytes: int = 0
    fits: bool = True

    def summary(self) -> str:
        gib = 1 << 30
        lines = [
            f"total params: {self.total_bytes / gib:.2f} GiB",
            f"per-device resident: {self.per_device_bytes / gib:.2f} GiB"
            + (f" (budget {self.budget_bytes / gib:.2f} GiB)" if self.budget_bytes else ""),
            f"fits: {self.fits}",
        ]
        if self.offload:
            lines.append(
                f"host-offloaded leaves: {len(self.offload)} "
                f"(streaming working set {self.streaming_bytes / gib:.2f} GiB)"
            )
        return "\n".join(lines)


def infer_sharding_plan(
    shapes: Any,
    mesh: Mesh,
    *,
    hbm_budget: int | None = None,
    rules: Rules = (),
    dtype: Any | None = None,
    no_offload_patterns: Sequence[str] = (),
    min_weight_size: int = 2**11,
) -> ShardingPlan:
    """Plan shardings for a shape-only model against a per-chip HBM budget
    (reference `infer_auto_device_map`, `utils/modeling.py:1281`).

    Strategy (greedy, three passes — mirrors the reference's
    biggest-first greedy assignment but over PartitionSpecs):

    1. apply the family ``rules`` (TP plan) where they match;
    2. if per-device bytes exceed the budget, shard every still-replicated
       leaf's largest divisible dim across the whole mesh (FSDP-widen),
       biggest leaves first, until it fits;
    3. still over budget: move the biggest leaves to host RAM (``offload``),
       excluding ``no_offload_patterns`` (e.g. embeddings read every step).

    ``fits=False`` on the returned plan means even full offload of eligible
    leaves cannot fit the resident set — the caller needs a bigger mesh.
    """
    n_devices = int(np.prod(list(mesh.shape.values()))) or 1
    all_axes = tuple(mesh.shape.keys())
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    sizes = compute_leaf_sizes(shapes, dtype)
    total = sum(sizes.values())

    specs: dict[str, PartitionSpec] = {}
    for path, leaf in flat:
        key = _path_str(path)
        shape = tuple(leaf.shape)
        spec = PartitionSpec()
        for pattern, rule_spec in rules:
            if re.search(pattern, key):
                spec = _sanitize_spec(rule_spec, shape, mesh, path=key)
                break
        specs[key] = spec

    def shard_factor(key: str, leaf: Any) -> int:
        """How many ways the planned spec divides this leaf."""
        factor = 1
        for entry in specs[key]:
            if entry is None:
                continue
            axes = (entry,) if isinstance(entry, str) else tuple(entry)
            factor *= int(np.prod([mesh.shape[a] for a in axes]))
        return factor

    def resident_per_device() -> int:
        return sum(
            sizes[_path_str(p)] // shard_factor(_path_str(p), l)
            for p, l in flat
            if _path_str(p) not in offload
        )

    offload: set[str] = set()

    # Pass 2: FSDP-widen replicated/under-sharded leaves, biggest first.
    if hbm_budget is not None and resident_per_device() > hbm_budget:
        order = sorted(flat, key=lambda pl: -sizes[_path_str(pl[0])])
        for path, leaf in order:
            key = _path_str(path)
            if shard_factor(key, leaf) >= n_devices:
                continue
            widened = _shard_largest_dim(
                tuple(leaf.shape), all_axes, mesh, min_weight_size
            )
            if widened != PartitionSpec():
                specs[key] = widened
            if resident_per_device() <= hbm_budget:
                break

    # Pass 3: host-offload the biggest leaves that remain.
    if hbm_budget is not None and resident_per_device() > hbm_budget:
        order = sorted(flat, key=lambda pl: -sizes[_path_str(pl[0])])
        for path, leaf in order:
            key = _path_str(path)
            if any(re.search(pat, key) for pat in no_offload_patterns):
                continue
            offload.add(key)
            if resident_per_device() <= hbm_budget:
                break

    resident = resident_per_device()
    streaming = max(
        (sizes[k] // shard_factor(k, None) for k in offload), default=0
    )
    spec_leaves = [specs[_path_str(p)] for p, _ in flat]
    return ShardingPlan(
        specs=jax.tree_util.tree_unflatten(treedef, spec_leaves),
        mesh=mesh,
        offload=offload,
        per_device_bytes=resident,
        streaming_bytes=streaming,
        budget_bytes=hbm_budget,
        total_bytes=total,
        fits=hbm_budget is None or resident <= hbm_budget,
    )


# ----------------------------------------------------------- checkpoint readers
class _NpzSource:
    """Consolidated `.npz` checkpoint (the `consolidate_checkpoint` output)."""

    def __init__(self, path: str) -> None:
        self._npz = np.load(path)
        self._last: tuple[str, np.ndarray] | None = None

    def keys(self) -> Iterable[str]:
        return self._npz.files

    def read_slice(self, key: str, idx: tuple[slice, ...]) -> np.ndarray:
        # NpzFile re-reads + decompresses the zip member on every access, and
        # an N-device mesh requests N slices of each leaf — cache the
        # last-decoded array (leaves are read leaf-at-a-time, so one entry
        # suffices without pinning the whole checkpoint in RAM).
        if self._last is None or self._last[0] != key:
            self._last = (key, self._npz[key])
        return self._last[1][idx]

    def close(self) -> None:
        self._last = None
        self._npz.close()


class _ShardedSource:
    """This framework's sharded checkpoint directory (index_*.json)."""

    def __init__(self, directory: str) -> None:
        from .checkpointing import _ShardReader

        self._reader = _ShardReader(directory)

    def keys(self) -> Iterable[str]:
        return self._reader.index.keys()

    def read_slice(self, key: str, idx: tuple[slice, ...]) -> np.ndarray:
        info = self._reader.leaf_info(key)
        return self._reader.read_slice(
            key, idx, tuple(info["shape"]), np.dtype(info["dtype"])
        )

    def close(self) -> None:
        self._reader.close()


class _SafetensorsSource:
    """HF-style safetensors: one `.safetensors` file or a sharded repo dir
    with `*.index.json` (reference `load_state_dict`, `utils/modeling.py:1615`
    — lazy per-tensor reads, never the whole file)."""

    def __init__(self, path: str) -> None:
        from safetensors import safe_open

        self._safe_open = safe_open
        self._files: dict[str, Any] = {}
        self._key_to_file: dict[str, str] = {}
        if os.path.isdir(path):
            index = None
            for name in os.listdir(path):
                if name.endswith(".index.json"):
                    index = os.path.join(path, name)
                    break
            if index is not None:
                with open(index) as f:
                    weight_map = json.load(f)["weight_map"]
                for key, fname in weight_map.items():
                    self._key_to_file[key] = os.path.join(path, fname)
            else:
                for name in sorted(os.listdir(path)):
                    if name.endswith(".safetensors"):
                        self._scan_file(os.path.join(path, name))
        else:
            self._scan_file(path)

    def _scan_file(self, path: str) -> None:
        with self._safe_open(path, framework="numpy") as f:
            for key in f.keys():
                self._key_to_file[key] = path

    def _open(self, path: str) -> Any:
        if path not in self._files:
            self._files[path] = self._safe_open(path, framework="numpy").__enter__()
        return self._files[path]

    def keys(self) -> Iterable[str]:
        return self._key_to_file.keys()

    def read_slice(self, key: str, idx: tuple[slice, ...]) -> np.ndarray:
        f = self._open(self._key_to_file[key])
        return f.get_slice(key)[idx]

    def close(self) -> None:
        for f in self._files.values():
            f.__exit__(None, None, None)
        self._files.clear()


def _open_source(path: str):
    if os.path.isfile(path) and path.endswith(".npz"):
        return _NpzSource(path)
    if os.path.isfile(path) and path.endswith(".safetensors"):
        return _SafetensorsSource(path)
    if os.path.isdir(path):
        names = os.listdir(path)
        if any(re.match(r"^index_\d+\.json$", n) for n in names):
            return _ShardedSource(path)
        if any(n.endswith(".safetensors") or n.endswith(".index.json") for n in names):
            return _SafetensorsSource(path)
    raise ValueError(f"Unrecognized checkpoint layout at {path}")


def load_checkpoint_and_dispatch(
    shapes: Any,
    checkpoint_path: str,
    plan: ShardingPlan,
    *,
    key_map: Callable[[str], str] | None = None,
    dtype: Any | None = None,
    offload_dir: str | None = None,
) -> Any:
    """Stream a checkpoint into sharded device buffers per ``plan``
    (reference `load_checkpoint_and_dispatch`, `big_modeling.py:511`).

    Each on-device leaf is built with `jax.make_array_from_callback`: every
    device pulls exactly its planned slice from the source — works for
    checkpoints far larger than any single host's RAM. Leaves in
    ``plan.offload`` are returned as host numpy arrays (stream them through
    `streamed_scan` at execution time).

    ``key_map`` translates this model's leaf paths to source tensor names
    (e.g. HF checkpoint naming); ``dtype`` casts on the fly (bf16 deploys of
    fp32 checkpoints).
    """
    source = _open_source(checkpoint_path)

    def make_fetch(key: str, leaf: Any) -> Callable[[tuple], np.ndarray]:
        src_key = key_map(key) if key_map else key
        return lambda idx, _k=src_key: np.asarray(source.read_slice(_k, tuple(idx)))

    try:
        return dispatch_leaves(
            shapes, plan, make_fetch, dtype=dtype, offload_dir=offload_dir,
            source_id=source_fingerprint(checkpoint_path) if offload_dir else "",
        )
    finally:
        source.close()


def source_fingerprint(checkpoint_path: str) -> str:
    """Identity of a checkpoint directory for the disk-offload cache: the
    resolved path plus each weight file's (name, size, mtime). Two
    same-architecture checkpoints (base model vs finetune) must never share
    cached .bin dumps."""
    path = os.path.realpath(os.fspath(checkpoint_path))
    parts = [path]
    if os.path.isdir(path):
        for name in sorted(os.listdir(path)):
            if name.endswith((".safetensors", ".npz", ".bin")):
                st = os.stat(os.path.join(path, name))
                parts.append(f"{name}:{st.st_size}:{st.st_mtime_ns}")
    elif os.path.exists(path):
        st = os.stat(path)
        parts.append(f"{st.st_size}:{st.st_mtime_ns}")
    return "|".join(parts)


def _disk_offload_leaf(
    directory: str,
    key: str,
    shape: tuple,
    dtype: np.dtype,
    fetch: Callable[[tuple], np.ndarray],
    chunk_bytes: int = 1 << 28,
    fingerprint: str = "",
) -> np.ndarray:
    """Write one offloaded leaf to ``<directory>/<key>.bin`` (chunked along
    dim 0, so host RAM holds at most ``chunk_bytes`` of it) and return a
    read-mode memmap — the reference ``offload_weight`` / offload_dir
    layout (`utils/offload.py:34,127`: per-tensor .dat + index.json), numpy
    flavored. A leaf whose index entry already matches is reused, so
    repeated loads of the same repo skip the dump."""
    os.makedirs(directory, exist_ok=True)
    fname = key.replace("/", ".") + ".bin"
    path = os.path.join(directory, fname)
    index_path = os.path.join(directory, "index.json")
    index: dict = {}
    if os.path.exists(index_path):
        try:
            with open(index_path) as f:
                index = json.load(f)
        except ValueError:
            index = {}
    entry = {"shape": list(shape), "dtype": str(dtype), "source": fingerprint}
    if index.get(key) != entry or not os.path.exists(path):
        tmp = path + ".tmp"
        mm = np.memmap(tmp, mode="w+", dtype=dtype, shape=shape)
        row_bytes = int(np.prod(shape[1:], dtype=np.int64)) * dtype.itemsize
        rows = max(1, chunk_bytes // max(1, row_bytes))
        for start in range(0, shape[0], rows):
            stop = min(shape[0], start + rows)
            idx = (slice(start, stop),) + tuple(slice(0, d) for d in shape[1:])
            mm[start:stop] = np.asarray(fetch(idx), dtype=dtype)
        mm.flush()
        del mm
        os.replace(tmp, path)
        index[key] = entry
        with open(index_path, "w") as f:
            json.dump(index, f)
    return np.memmap(path, mode="r", dtype=dtype, shape=shape)


def dispatch_leaves(
    shapes: Any,
    plan: ShardingPlan,
    make_fetch: Callable[[str, Any], Callable[[tuple], np.ndarray]],
    *,
    dtype: Any | None = None,
    leaf_override: Callable[[str, Any, Callable], Any] | None = None,
    offload_dir: str | None = None,
    source_id: str = "",
) -> Any:
    """Shared streaming-dispatch core: for each leaf of ``shapes``,
    ``make_fetch(plan_key, leaf)`` returns a host-side callback mapping a
    slice index to the leaf's content; sharded leaves are built with
    `jax.make_array_from_callback` (each device pulls exactly its planned
    slice), ``plan.offload`` leaves come back as full host numpy arrays.
    Both `load_checkpoint_and_dispatch` and the HF-named streaming loader
    (`models/hf.py`) ride this loop.

    ``leaf_override(plan_key, leaf, fetch)`` may return either a finished
    replacement leaf, or a ``(host_fn, place_fn)`` pair — the host stage
    runs on the pipeline's IO worker, the place stage on the shared
    transfer engine's worker pool — or None to take the normal path.

    The loop is a pipeline: while the transfer engine pushes leaf i's
    bytes to the device(s) (chunked, multiple concurrent streams —
    `parallel/transfer.py`), a worker thread is already reading and
    transforming leaf i+1 (and i+2). Loads through a slow device link are
    then bounded by max(read+pack, transfer) instead of their sum, and the
    transfer term itself is no longer serialized behind one Python-level
    ``device_put`` call per leaf. One IO worker, because the
    checkpoint source's lazy file handles are not thread-safe; the read
    order also stays sequential, which is what spinning-disk and network
    filesystems want."""
    from concurrent.futures import Future, ThreadPoolExecutor

    from .parallel.transfer import get_transfer_engine

    engine = get_transfer_engine()

    def _done(value: Any) -> Future:
        f: Future = Future()
        f.set_result(value)
        return f

    mesh = plan.mesh
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    spec_leaves = jax.tree.leaves(
        plan.specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )

    def _norm(idx: tuple, shape: tuple) -> tuple:
        return tuple(
            (s.start or 0, shape[d] if s.stop is None else s.stop)
            for d, s in enumerate(idx)
        )

    def make_stages(path, leaf, spec):
        """-> (host_fn, place_fn): host_fn runs on the IO worker and returns
        the staged host-side payload; place_fn consumes it and returns a
        FUTURE of the finished leaf (device traffic rides the shared
        transfer engine — chunked multi-stream H2D for fully-owned leaves,
        pooled make_array for multi-host sharded ones)."""
        key = _path_str(path)
        shape = tuple(leaf.shape)
        target_dtype = np.dtype(dtype) if dtype is not None else np.dtype(leaf.dtype)
        fetch = make_fetch(key, leaf)
        if leaf_override is not None:
            replaced = leaf_override(key, leaf, fetch)
            if replaced is not None:
                if isinstance(replaced, tuple) and callable(replaced[0]):
                    h, p = replaced
                    return h, (lambda staged, _p=p: engine.submit(_p, staged))
                return (lambda _r=replaced: _r), _done
        if key in plan.offload:
            if offload_dir is not None:
                # Disk offload: the leaf never fully materializes in host
                # RAM — streamed to disk in chunks, returned as a memmap
                # whose per-layer slices `streamed_scan` reads on demand
                # (reference disk_offload, `big_modeling.py:260`).
                return (
                    lambda: _disk_offload_leaf(
                        offload_dir, key, shape, target_dtype, fetch,
                        fingerprint=source_id,
                    ),
                    _done,
                )
            return (
                lambda: np.asarray(
                    fetch(tuple(slice(0, d) for d in shape)), dtype=target_dtype
                ),
                _done,
            )
        sharding = NamedSharding(mesh, spec)
        full_idx = tuple((0, d) for d in shape)

        def host_fn():
            # Prefetch exactly this process's addressable shard slices
            # (deduped across replicas) so multi-host behavior is unchanged:
            # no host ever reads bytes it doesn't own.
            staged: dict[tuple, np.ndarray] = {}
            for dev, idx in sharding.devices_indices_map(shape).items():
                if dev.process_index != jax.process_index():
                    continue
                nidx = _norm(idx, shape)
                if nidx not in staged:
                    staged[nidx] = np.asarray(fetch(idx), dtype=target_dtype)
            return staged

        def place_fn(staged):
            if set(staged.keys()) == {full_idx}:
                # This process stages the whole leaf (single chip, or a
                # replicated/one-slice layout): the chunked engine path
                # replaces the single serialized device_put call.
                return engine.put(staged[full_idx], sharding=sharding)
            return engine.submit(
                lambda: jax.make_array_from_callback(
                    shape, sharding, lambda idx: staged[_norm(idx, shape)]
                )
            )

        return host_fn, place_fn

    stages = [
        make_stages(path, leaf, spec)
        for (path, leaf), spec in zip(flat, spec_leaves)
    ]
    # Pipeline: one IO worker reads+packs ahead (sequential, the source's
    # lazy handles are not thread-safe and disks want sequential reads);
    # placement goes through the shared transfer engine, whose worker pool
    # keeps several chunk streams in flight per leaf (a link that
    # serializes per call aggregates with concurrent streams). The window
    # keeps at most
    # `depth` staged payloads + `window` un-finished placements alive so
    # host RAM stays bounded.
    depth = max(2, engine.prefetch_depth)
    window = depth + 1
    out: list = []
    with ThreadPoolExecutor(max_workers=1) as io_ex:
        host_futures = [io_ex.submit(h) for h, _p in stages[:depth]]
        place_futures: list = []
        for i, (_h, place) in enumerate(stages):
            if i + depth < len(stages):
                host_futures.append(io_ex.submit(stages[i + depth][0]))
            place_futures.append(place(host_futures[i].result()))
            host_futures[i] = None  # release the staged payload reference
            if i >= window:
                place_futures[i - window].result()  # backpressure
        out = [f.result() for f in place_futures]
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------- layer streaming
def offload_blocks(blocks: Any) -> Any:
    """Move a stacked block pytree (leading layer axis on every leaf) to host
    RAM (reference `cpu_offload`, `big_modeling.py:170`). All leaves drain
    concurrently through the transfer engine's D2H path."""
    from .parallel.transfer import get_transfer_engine

    return get_transfer_engine().get_tree(blocks).result()


def streamed_scan(
    body: Callable[[Any, Any], Any],
    carry: Any,
    host_blocks: Any,
    *,
    sharding: Any | None = None,
    dtype: Any | None = None,
    engine: Any | None = None,
    prefetch_depth: int | None = None,
) -> Any:
    """Run ``carry = body(carry, block_i)`` over layer-stacked host-resident
    blocks, streaming layers ahead of compute (the `AlignDevicesHook`
    pre-forward staging pattern, reference `hooks.py:329`, without forward
    monkey-patching).

    Staging rides the shared transfer engine (`parallel/transfer.py`):
    while layer *i* computes, layers *i+1..i+depth* are already in flight
    — chunked ``device_put`` issued from the engine's worker pool, with
    ``prefetch_depth`` (default ``ATX_TRANSFER_PREFETCH``, >= 2)
    double-buffered device slots. Memmap-backed leaves (disk offload) have
    their disk reads staged chunk-by-chunk through the same path, so the
    read, the cast, and the H2D copy of layer *i+1* all overlap layer
    *i*'s compute.

    ``host_blocks`` leaves are numpy arrays (or memmaps) with a leading
    layer axis. ``sharding`` optionally places staged layers (a pytree of
    NamedShardings matching one layer, or a single sharding applied to
    every leaf).
    """
    from .parallel.transfer import get_transfer_engine

    eng = engine if engine is not None else get_transfer_engine()
    n_layers = jax.tree.leaves(host_blocks)[0].shape[0]

    def stage(i: int) -> Any:
        layer = jax.tree.map(lambda x: x[i], host_blocks)
        return eng.put_tree(layer, shardings=sharding, dtype=dtype)

    for block in eng.prefetch(n_layers, stage, depth=prefetch_depth):
        carry = body(carry, block)
    return carry
