"""Dispatch-by-availability for the Pallas kernel tier.

Every kernel in this package is OPTIONAL: the call site always carries the
exact current XLA lowering as its fallback, and `kernel_mode(name)` decides
per trace whether the Pallas kernel replaces it. The code chooses from what
it can observe: the compiled kernel iff the backend is a TPU and pallas
imports, the fallback lowering everywhere else (so a CPU runs the XLA path
untouched). `force_kernels(mode)` is the seam for tests, the chip gate's
kernel-vs-fallback parity and the `perf/` comparisons:

- ``"off"``        — never use the kernel (fallback lowering);
- ``"on"``         — what the code picks by itself (compiled iff TPU);
- ``"interpret"``  — the kernel in Pallas interpret mode (runs anywhere,
  slowly): the CPU parity-test path.

Like the fp8/int8 modes (`ops/fp8.py`), the mode is read at TRACE time: a
function jitted inside one mode keeps that mode's lowering, so a comparison
of two modes traces the function once in each.

Shape/dtype support is the CALL SITE's job — `kernel_mode` answers "may
this kernel run", the kernel module's own `supported()` predicate answers
"can it, for these operands". Both must say yes or the fallback runs.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any

_FORCE = threading.local()

# name -> one-line description (introspection via `kernel_status`).
_REGISTRY: dict[str, str] = {}

_MODES = ("off", "on", "interpret")


def register_kernel(name: str, doc: str = "") -> None:
    _REGISTRY[name] = doc


@functools.lru_cache(maxsize=None)
def pallas_available() -> bool:
    try:
        from jax.experimental import pallas  # noqa: F401
        from jax.experimental.pallas import tpu  # noqa: F401

        return True
    except Exception:  # pragma: no cover - environment dependent
        return False


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def kernel_mode(name: str) -> str | None:
    """May kernel ``name`` replace its fallback in the current trace?

    Returns ``None`` (run the exact fallback lowering), ``"compiled"`` (TPU
    Pallas), or ``"interpret"`` (Pallas interpret mode — any backend).
    """
    forced = getattr(_FORCE, "mode", None) or {}
    mode = forced.get(name, forced.get(None, "on"))
    if mode == "off" or not pallas_available():
        return None
    if mode == "interpret":
        return "interpret"
    return "compiled" if _on_tpu() else None


@contextlib.contextmanager
def force_kernels(mode: str, name: str | None = None):
    """Override the code's own choice while active (including during jit
    tracing): ``force_kernels("interpret")`` puts every kernel in
    interpret mode (the CPU parity-test path), ``force_kernels("off")``
    pins the fallback lowerings, ``force_kernels("on", "fused_adamw")``
    overrides one kernel only. Nests; inner wins for its keys, and a
    kernel's own entry beats the one for all kernels."""
    if mode not in _MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; expected one of {_MODES}")
    prev = getattr(_FORCE, "mode", None)
    new = dict(prev or {})
    new[name] = mode
    _FORCE.mode = new
    try:
        yield
    finally:
        _FORCE.mode = prev


def kernel_status() -> list[dict[str, Any]]:
    """Registry snapshot: every registered kernel with its resolved mode
    under the current overrides (the `atx lint kernels` / docs surface)."""
    return [
        {"kernel": name, "doc": doc, "mode": kernel_mode(name) or "fallback"}
        for name, doc in sorted(_REGISTRY.items())
    ]
