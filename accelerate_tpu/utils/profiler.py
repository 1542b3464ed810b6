"""Profiling: `jax.profiler` traces behind the reference's profile API.

Analog of `ProfileKwargs` (reference `utils/dataclasses.py:436-549`) and
`Accelerator.profile()` (reference `accelerator.py:3614-3672`). The reference
wraps `torch.profiler` and exports Chrome traces; the TPU equivalent captures
XPlane traces via `jax.profiler.trace` — viewable in TensorBoard or Perfetto —
plus device-memory snapshots (`jax.profiler.device_memory_profile`).

Differences by design:
- No activity list (CPU/CUDA): a JAX trace always captures host + device
  timelines; `host_tracer_level` / `python_tracer_level` tune host detail.
- No schedule(wait/warmup/active): JAX traces are span-based. The
  `skip_first` analog is the caller running warmup steps before entering the
  context (compile time would otherwise dominate the trace).
- `with_flops` analog: `estimate_step_flops` uses XLA's own cost analysis of
  a compiled step instead of operator-level bookkeeping.
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass
from typing import Any, Callable, Iterator

import jax

PROFILE_DIR_DEFAULT = "atx_profile"


@dataclass
class ProfileKwargs:
    """Trace-capture configuration (reference `ProfileKwargs`,
    `utils/dataclasses.py:436`).

    ``output_trace_dir``: where XPlane trace files land (TensorBoard
    `logdir`); defaults to ``atx_profile`` under the project dir.
    ``host_tracer_level``: 0-3, host-side instrumentation detail.
    ``python_tracer_level``: 0/1, Python-call capture (costly; off by default).
    ``create_perfetto_trace``: also emit a ``.perfetto-trace`` file.
    ``on_trace_ready``: called with the trace directory after capture
    (reference on_trace_ready callback).
    """

    output_trace_dir: str | None = None
    host_tracer_level: int = 2
    python_tracer_level: int = 0
    create_perfetto_trace: bool = False
    on_trace_ready: Callable[[str], None] | None = None

    def build_options(self) -> Any:
        """Map to `jax.profiler.ProfileOptions`."""
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = self.host_tracer_level
        options.python_tracer_level = self.python_tracer_level
        return options


@contextlib.contextmanager
def profile(
    profile_kwargs: ProfileKwargs | None = None,
    *,
    logging_dir: str | None = None,
) -> Iterator[ProfileKwargs]:
    """Capture a device+host trace of the enclosed block.

    Every process traces (each host's runtime only sees its own chips); the
    XPlane files are written under per-host subdirectories so one TensorBoard
    logdir aggregates a pod's capture.
    """
    kwargs = profile_kwargs or ProfileKwargs()
    trace_dir = kwargs.output_trace_dir or os.path.join(
        logging_dir or ".", PROFILE_DIR_DEFAULT
    )
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(
        trace_dir,
        create_perfetto_trace=kwargs.create_perfetto_trace,
        profiler_options=kwargs.build_options(),
    )
    try:
        yield kwargs
    finally:
        jax.profiler.stop_trace()
        if kwargs.on_trace_ready is not None:
            kwargs.on_trace_ready(trace_dir)


def annotate(name: str, **kwargs: Any):
    """Named span visible in the trace timeline (reference
    `torch.profiler.record_function` analog)."""
    return jax.profiler.TraceAnnotation(name, **kwargs)


def step_annotation(step: int, name: str = "train"):
    """Mark one training step so TensorBoard's step-time views group ops."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


def save_memory_profile(path: str) -> str:
    """Write a pprof-format snapshot of live device memory
    (`jax.profiler.save_device_memory_profile`)."""
    directory = os.path.dirname(os.path.abspath(path))
    os.makedirs(directory, exist_ok=True)
    jax.profiler.save_device_memory_profile(path)
    return path


def estimate_step_flops(compiled: Any) -> float | None:
    """FLOPs XLA attributes to one invocation of a compiled function
    (`with_flops` analog). Returns None when cost analysis is unavailable."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops = (cost or {}).get("flops")
    return float(flops) if flops is not None else None
