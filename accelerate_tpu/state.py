"""Process, accelerator, and gradient state singletons.

TPU-native redesign of the reference state layer
(`/root/reference/src/accelerate/state.py` — `PartialState` :123,
`AcceleratorState` :850, `GradientState` :1181). The shared-``__dict__``
singleton pattern (reference `state.py:162,178`) is kept: every instance of a
state class aliases one process-wide dict, so any module can do
``ProcessState()`` and observe the same initialized state.

What changes vs the reference:

- Backend detection + ``torch.distributed.init_process_group``
  (`state.py:226,:267,:734-799`) collapses into `jax.distributed.initialize`
  (multi-host control plane) — collectives are XLA HLO ops over ICI/DCN, so
  there is no backend zoo to manage.
- "One process per device" becomes "one process per host"; `jax.devices()` /
  `jax.local_devices()` give the global/local accelerator view.
- Device placement (`state.py:801-825`) is not a process property: arrays are
  placed by shardings on the mesh (`parallel/mesh.py`).
"""

from __future__ import annotations

import logging
import os
import random as _random
import threading
import time as _time
from contextlib import contextmanager
from typing import Any, Callable, Iterator

import jax
import numpy as np

from .parallel.mesh import Mesh, MeshConfig, build_mesh
from .utils.environment import get_int_from_env, get_str_from_env, parse_flag_from_env

logger = logging.getLogger(__name__)

_jax_distributed_initialized = False
_init_lock = threading.Lock()


def _maybe_collective_log(kind: str, name: str) -> None:
    """Opt-in runtime collective-log mirror (``ATX_COLLECTIVE_LOG=1``, see
    `analysis/collective_log.py`). One env lookup when off; never raises."""
    if os.environ.get("ATX_COLLECTIVE_LOG", "").strip().lower() not in (
        "1",
        "true",
        "yes",
        "on",
    ):
        return
    try:
        from .analysis.collective_log import runtime_record

        runtime_record(kind, name)
    except Exception:  # pragma: no cover - diagnostics must not break sync
        pass


# The checkout's own compile cache: a fixed path (never a temp name, pid or
# time) so every process started from this checkout shares one cache;
# git-ignored.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_compile_cache"
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable, before the
    first compile; returns the directory in use. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
    set in code; otherwise the cache lives in `COMPILE_CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def maybe_initialize_jax_distributed() -> None:
    """Initialize the JAX multi-host control plane if the launcher asked for it.

    The launcher (`commands/launch.py`) sets ``ATX_COORDINATOR_ADDRESS``,
    ``ATX_NUM_PROCESSES`` and ``ATX_PROCESS_ID`` in each child — the analog of
    the reference's ``MASTER_ADDR/MASTER_PORT/RANK/WORLD_SIZE`` contract
    (`utils/launch.py:98-470`). On GCE TPU pods `jax.distributed.initialize()`
    can also self-discover via instance metadata, so we call it bare when
    ``ATX_MULTIHOST=1`` without explicit coordinates.
    """
    global _jax_distributed_initialized
    with _init_lock:
        if _jax_distributed_initialized:
            return
        coordinator = get_str_from_env(
            ("ATX_COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS"), ""
        )
        num_processes = get_int_from_env(("ATX_NUM_PROCESSES", "JAX_NUM_PROCESSES"), 0)
        process_id = get_int_from_env(("ATX_PROCESS_ID", "JAX_PROCESS_ID"), -1)
        if coordinator and num_processes > 1:
            _initialize_distributed_with_retries(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id if process_id >= 0 else None,
            )
            _jax_distributed_initialized = True
        elif parse_flag_from_env("ATX_MULTIHOST"):
            _initialize_distributed_with_retries()
            _jax_distributed_initialized = True


def _initialize_distributed_with_retries(**kwargs: Any) -> None:
    """`jax.distributed.initialize` with bounded exponential backoff + jitter.

    The coordination service is the flakiest moment of a pod launch: workers
    race the coordinator's bind, and a slow heartbeat at init kills the whole
    group (the failure mode behind the two flaky multi-process tests on the
    ROADMAP). Knobs:

    - ``ATX_COORD_INIT_RETRIES`` (default 3): retries *after* the first
      failure, backing off 1s → 2s → 4s … (capped at 30s) with up to +100%
      jitter so restarted workers don't re-stampede the coordinator.
    - ``ATX_COORD_TIMEOUT_SECS``: forwarded as ``initialization_timeout`` so
      a dead coordinator fails fast instead of blocking for jax's default.
    """
    retries = get_int_from_env(("ATX_COORD_INIT_RETRIES",), 3)
    timeout_secs = get_int_from_env(("ATX_COORD_TIMEOUT_SECS",), 0)
    if timeout_secs > 0:
        kwargs["initialization_timeout"] = timeout_secs
    delay = 1.0
    failures = 0
    while True:
        try:
            jax.distributed.initialize(**kwargs)
            return
        except Exception as e:
            failures += 1
            if failures > retries:
                raise
            sleep_for = delay * (1.0 + _random.random())
            logger.warning(
                "jax.distributed.initialize failed (attempt %d/%d): %s — "
                "retrying in %.1fs",
                failures,
                retries,
                e,
                sleep_for,
            )
            _time.sleep(sleep_for)
            delay = min(delay * 2.0, 30.0)


class ProcessState:
    """Singleton with information about the current process & the device world.

    Analog of the reference `PartialState` (`state.py:123`): rank helpers,
    process-ordered execution, host-side work splitting. One instance per
    *host* process (JAX SPMD: each process drives all its local devices).
    """

    _shared_state: dict[str, Any] = {}

    def __init__(self, **kwargs: Any) -> None:
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        maybe_initialize_jax_distributed()
        self.debug = parse_flag_from_env("ATX_DEBUG_MODE")
        self.process_index = jax.process_index()
        self.num_processes = jax.process_count()
        self.local_devices = jax.local_devices()
        self.device_count = jax.device_count()
        self.platform = jax.devices()[0].platform
        self.device = jax.devices()[0]
        self._initialized = True

    # ------------------------------------------------------------------ basic
    @property
    def initialized(self) -> bool:
        return self.__dict__.get("_initialized", False)

    @property
    def local_device_count(self) -> int:
        return len(self.local_devices)

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        # One process per host under JAX SPMD, so every process is its host's
        # local-main. Kept as a property for API parity with the reference.
        return True

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    @property
    def use_distributed(self) -> bool:
        return self.num_processes > 1 or self.device_count > 1

    def __repr__(self) -> str:
        return (
            f"ProcessState(process_index={self.process_index}, "
            f"num_processes={self.num_processes}, platform={self.platform!r}, "
            f"device_count={self.device_count})"
        )

    # ------------------------------------------------------------- sync/order
    def wait_for_everyone(self) -> None:
        """Block until all processes reach this point.

        Reference `state.py:359`. Uses a named cross-process barrier via the
        JAX runtime; no-op in single-process mode.
        """
        _maybe_collective_log("barrier", "wait_for_everyone")
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("atx_wait_for_everyone")

    def _goes_first(self, is_main: bool) -> Iterator[None]:
        if not is_main:
            self.wait_for_everyone()
        yield
        if is_main:
            self.wait_for_everyone()

    @contextmanager
    def main_process_first(self) -> Iterator[None]:
        yield from self._goes_first(self.is_main_process)

    @contextmanager
    def local_main_process_first(self) -> Iterator[None]:
        yield from self._goes_first(self.is_local_main_process)

    def on_main_process(self, function: Callable) -> Callable:
        """Decorator: run only on the main process (reference `state.py:537`)."""

        def wrapper(*args: Any, **kwargs: Any) -> Any:
            if self.is_main_process:
                return function(*args, **kwargs)
            return None

        return wrapper

    def on_local_main_process(self, function: Callable) -> Callable:
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            if self.is_local_main_process:
                return function(*args, **kwargs)
            return None

        return wrapper

    def on_last_process(self, function: Callable) -> Callable:
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            if self.is_last_process:
                return function(*args, **kwargs)
            return None

        return wrapper

    def on_process(self, function: Callable, process_index: int) -> Callable:
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            if self.process_index == process_index:
                return function(*args, **kwargs)
            return None

        return wrapper

    # ------------------------------------------------------------- splitting
    @contextmanager
    def split_between_processes(
        self, inputs: Any, apply_padding: bool = False
    ) -> Iterator[Any]:
        """Split ``inputs`` (list/tuple/dict/np.ndarray/str) across processes.

        Host-side work partitioning for uneven inputs — reference
        `state.py:407-495`. With ``apply_padding`` the last element is
        repeated so every process gets the same count (pair with
        `gather_for_metrics(..., use_gather_object=True)` style dedup).
        """
        if self.num_processes == 1:
            yield inputs
            return

        if isinstance(inputs, dict):
            split: dict[Any, Any] = {}
            length = None
            for key, value in inputs.items():
                if length is None:
                    length = len(value)
                elif len(value) != length:
                    raise ValueError(
                        "All dict values must have the same length to be split"
                    )
            for key, value in inputs.items():
                with self.split_between_processes(value, apply_padding) as v:
                    split[key] = v
            yield split
            return

        length = len(inputs)
        num_per_process = length // self.num_processes
        remainder = length % self.num_processes
        # First `remainder` processes get one extra element.
        start = num_per_process * self.process_index + min(self.process_index, remainder)
        extra = 1 if self.process_index < remainder else 0
        end = start + num_per_process + extra

        chunk = inputs[start:end]
        if apply_padding and remainder != 0:
            target = num_per_process + 1
            if isinstance(chunk, np.ndarray):
                if len(chunk) == 0 and length:
                    chunk = inputs[-1:]
                while 0 < len(chunk) < target:
                    chunk = np.concatenate([chunk, chunk[-1:]])
            elif isinstance(chunk, (list, tuple)):
                pad = list(chunk)
                fill = pad[-1] if pad else (inputs[-1] if length else None)
                while len(pad) < target:
                    pad.append(fill)
                chunk = type(chunk)(pad) if isinstance(chunk, tuple) else pad
        yield chunk

    def print(self, *args: Any, **kwargs: Any) -> None:
        if self.is_main_process:
            print(*args, **kwargs)

    # ---------------------------------------------------------------- control
    @classmethod
    def _reset_state(cls) -> None:
        """Clear the singleton (test isolation — reference `state.py:1175`)."""
        cls._shared_state.clear()

    def destroy_process_group(self) -> None:
        """Shut down the multi-host control plane (end-of-program)."""
        global _jax_distributed_initialized
        if _jax_distributed_initialized:
            jax.distributed.shutdown()
            _jax_distributed_initialized = False


class AcceleratorState:
    """Singleton adding mesh + precision + strategy config on top of ProcessState.

    Analog of reference `AcceleratorState` (`state.py:850`), minus the
    per-backend special cases: here the entire "which parallelism" question is
    answered by the mesh shape and the sharding strategy
    (`parallel/sharding.py`), not a DistributedType ladder.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(
        self,
        mesh_config: MeshConfig | None = None,
        mixed_precision: str | None = None,
        **kwargs: Any,
    ) -> None:
        self.__dict__ = self._shared_state
        self.process_state = ProcessState()
        if self.initialized:
            if mesh_config is not None or mixed_precision is not None:
                logger.warning(
                    "AcceleratorState is already initialized; the mesh_config/"
                    "mixed_precision arguments passed now are ignored. Call "
                    "AcceleratorState._reset_state() first to reconfigure."
                )
            return
        self.mixed_precision = mixed_precision or os.environ.get(
            "ATX_MIXED_PRECISION", "no"
        )
        # Launcher env contract fallback (ATX_MESH_*), mirroring the reference
        # plugins' ACCELERATE_* __post_init__ reads.
        self._mesh_config = mesh_config if mesh_config is not None else MeshConfig.from_env()
        self._mesh: Mesh | None = None
        self._initialized = True

    @property
    def initialized(self) -> bool:
        return self.__dict__.get("_initialized", False)

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self._mesh = build_mesh(self._mesh_config)
        return self._mesh

    def set_mesh(self, mesh: Mesh) -> None:
        self._mesh = mesh

    # Pass-through process helpers so AcceleratorState is a superset.
    def __getattr__(self, name: str) -> Any:
        # Called only when normal lookup fails; delegate to ProcessState.
        ps = self.__dict__.get("process_state")
        if ps is not None and hasattr(ps, name):
            return getattr(ps, name)
        raise AttributeError(name)

    @classmethod
    def _reset_state(cls, reset_partial_state: bool = False) -> None:
        cls._shared_state.clear()
        if reset_partial_state:
            ProcessState._reset_state()


class GradientState:
    """Singleton tracking gradient accumulation & dataloader-edge information.

    Analog of reference `GradientState` (`state.py:1181-1322`). In the TPU
    design gradient accumulation happens *inside* the jitted train step
    (microbatch `lax.scan`), so ``sync_gradients`` is True at every outer
    step; the fields remain because the data pipeline uses this object to
    advertise `end_of_dataloader` / `remainder` for metric-correct gathering
    (`gather_for_metrics`, reference `accelerator.py:2645-2668`).
    """

    _shared_state: dict[str, Any] = {}

    def __init__(self, gradient_accumulation_steps: int | None = None) -> None:
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.num_steps = 1
            self.active_dataloader = None
            self.dataloader_references: list[Any] = [None]
        if gradient_accumulation_steps is not None:
            self.num_steps = gradient_accumulation_steps

    @property
    def initialized(self) -> bool:
        return self.__dict__.get("num_steps", None) is not None

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        if not self.in_dataloader:
            return -1
        return self.active_dataloader.remainder

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _add_dataloader(self, dataloader: Any) -> None:
        self.dataloader_references.append(dataloader)
        self.active_dataloader = dataloader

    def _remove_dataloader(self, dataloader: Any) -> None:
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1]

    def __repr__(self) -> str:
        return (
            f"GradientState(num_steps={self.num_steps}, "
            f"sync_gradients={self.sync_gradients}, "
            f"in_dataloader={self.in_dataloader})"
        )

    @classmethod
    def _reset_state(cls) -> None:
        cls._shared_state.clear()


def is_initialized() -> bool:
    return AcceleratorState._shared_state.get("_initialized", False)
