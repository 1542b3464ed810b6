"""Step-time breakdown for the training loop.

JAX dispatch is asynchronous: a jitted step call returns as soon as the work
is enqueued, so host-side wall clocks around the call measure the *dispatch*
(host Python + enqueue cost), not device compute. :class:`StepStats`
splits the two from host timestamps alone:

- ``train_step_ms``: EMA of the interval between consecutive step entries —
  the true sustained step time once the pipeline is saturated (the device
  backpressures dispatch through the stream).
- ``train_dispatch_ms``: EMA of the jitted call's host wall time (not a gap
  on the device: the device can be busy with the previous step meanwhile).
  When this approaches ``train_step_ms`` the loop is host-bound.
- ``train_device_ms``: on sampled steps only (``ATX_METRICS_SAMPLE_EVERY``,
  default 0 = never), a ``block_until_ready`` on the step outputs measures
  dispatch-begin -> outputs-ready — an upper bound on device compute
  including queued prior work. With sampling off there are ZERO device
  syncs: every other field is pure ``time.perf_counter`` + shape math.
- ``train_tokens_per_sec`` / ``train_hfu``: EMA'd throughput from the batch
  leaf shapes and *hardware* FLOPs utilisation via
  `utils/profiler.estimate_step_flops` (XLA's own cost analysis of the
  compiled step, so operations recomputed under remat count: it reads above
  a model-FLOPs utilisation) against the chip's peak.
- ``train_compiles``: jit cache-size deltas — recompiles on the hot path
  (the runtime twin of the ATX302 shape-drift lint).

Blocking on already-computed outputs never changes their values, so losses
are bit-identical with stats on or off; instrumentation never touches rng,
step math, or dispatch order.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..utils.environment import get_int_from_env
from .registry import REGISTRY, Registry

__all__ = ["StepStats", "peak_device_flops", "tokens_in_batch"]

# Per-chip bf16 peak FLOP/s by device_kind substring (public TPU specs).
_PEAK_FLOPS = (
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

# Indirection so tests can count sync calls (zero-sync assertion).
_block_until_ready = jax.block_until_ready


def peak_device_flops(device: Any | None = None) -> float | None:
    """Peak bf16 FLOP/s of one chip; None on a CPU device (MFU reads 0
    there). An accelerator whose kind is not in the table raises."""
    if device is None:
        device = jax.devices()[0]
    kind = str(getattr(device, "device_kind", "")).lower()
    for key, peak in _PEAK_FLOPS:
        if key in kind:
            return peak
    if getattr(device, "platform", None) == "cpu":
        return None
    raise ValueError(
        f"no peak FLOP/s for device kind {device.device_kind!r}: add it to "
        "telemetry.stepstats._PEAK_FLOPS"
    )


def tokens_in_batch(batch: Any) -> int:
    """Tokens per step from leaf *shapes* only (no device reads): the widest
    integer leaf's batch*seq product, falling back to the widest leaf."""
    best = 0
    fallback = 0
    for leaf in jax.tree_util.tree_leaves(batch):
        shape = getattr(leaf, "shape", None)
        if not shape:
            continue
        n = int(shape[0]) * (int(shape[1]) if len(shape) > 1 else 1)
        fallback = max(fallback, n)
        dtype = getattr(leaf, "dtype", None)
        try:
            if dtype is not None and jnp.issubdtype(dtype, jnp.integer):
                best = max(best, n)
        except TypeError:
            continue
    return best or fallback


class StepStats:
    """Per-train-step telemetry publisher. One instance per built train step
    (`Accelerator.make_train_step`); gauges land on the shared registry so
    the `/metrics` endpoint, tracker glue, and bench read the same fields."""

    def __init__(
        self,
        *,
        registry: Registry | None = None,
        sample_every: int | None = None,
        ema_alpha: float | None = None,
        flops_fn: Callable[[], float | None] | None = None,
        peak_flops_total: float | None = None,
    ):
        reg = registry if registry is not None else REGISTRY
        if sample_every is None:
            sample_every = get_int_from_env(("ATX_METRICS_SAMPLE_EVERY",), 0)
        self.sample_every = max(0, int(sample_every))
        if ema_alpha is None:
            ema_alpha = float(os.environ.get("ATX_METRICS_EMA", "0.2"))
        self.ema_alpha = min(1.0, max(0.0, ema_alpha))
        self._flops_fn = flops_fn
        self._flops_per_step: float | None = None
        self._flops_resolved = flops_fn is None
        self.peak_flops_total = peak_flops_total

        self._g_step = reg.gauge(
            "train_step_ms", "EMA interval between step entries (ms)")
        self._g_dispatch = reg.gauge(
            "train_dispatch_ms", "EMA host wall time of the jitted call (ms)")
        self._g_device = reg.gauge(
            "train_device_ms",
            "Sampled dispatch-begin to outputs-ready wall (ms)")
        self._g_tps = reg.gauge(
            "train_tokens_per_sec", "EMA training throughput", aggregate="sum")
        self._g_hfu = reg.gauge(
            "train_hfu",
            "Hardware FLOPs utilisation: XLA's count of the compiled step, "
            "recomputed operations included (0 when peak unknown)")
        self._c_steps = reg.counter("train_steps", "Steps dispatched")
        self._c_compiles = reg.counter(
            "train_compiles", "Jit cache growth events (ATX302 runtime twin)")

        self._emas: dict[str, float] = {}
        self._t_entry: float | None = None
        self._last_entry: float | None = None
        self._last_interval_s: float | None = None
        self._last_cache_size = 0
        self._steps = 0
        self._compiles = 0
        self._sampled_device_ms: float | None = None

    # -- hot-path hooks ----------------------------------------------------

    def on_entry(self, tokens_per_step: int | None = None) -> None:
        """Call at step entry, before dispatch. Host clocks only."""
        now = time.perf_counter()
        if self._last_entry is not None:
            interval_s = now - self._last_entry
            if interval_s > 0:
                self._last_interval_s = interval_s
                step_ms = self._ema("step_ms", interval_s * 1e3)
                self._g_step.set(step_ms)
                if tokens_per_step:
                    tps = self._ema("tps", tokens_per_step / interval_s)
                    self._g_tps.set(tps)
                self._update_hfu(interval_s)
        self._last_entry = now
        self._t_entry = now

    def on_dispatched(self, outputs: Any = None, cache_size: int | None = None) -> None:
        """Call right after the jitted step returns (work enqueued)."""
        now = time.perf_counter()
        self._steps += 1
        self._c_steps.inc()
        if self._t_entry is not None:
            self._g_dispatch.set(
                self._ema("dispatch_ms", (now - self._t_entry) * 1e3)
            )
        if cache_size is not None and cache_size > self._last_cache_size:
            self._compiles += cache_size - self._last_cache_size
            self._c_compiles.inc(cache_size - self._last_cache_size)
            self._last_cache_size = cache_size
        if (
            self.sample_every
            and outputs is not None
            and self._steps % self.sample_every == 0
        ):
            _block_until_ready(outputs)
            device_ms = (time.perf_counter() - (self._t_entry or now)) * 1e3
            self._sampled_device_ms = self._ema("device_ms", device_ms)
            self._g_device.set(self._sampled_device_ms)

    # -- internals ---------------------------------------------------------

    def _ema(self, key: str, value: float) -> float:
        prev = self._emas.get(key)
        out = value if prev is None else prev + self.ema_alpha * (value - prev)
        self._emas[key] = out
        return out

    def _update_hfu(self, interval_s: float) -> None:
        if not self.peak_flops_total:
            # Unknown chip peak (e.g. CPU runs): report 0 and never call
            # flops_fn — resolving it may cost an AOT compile.
            self._g_hfu.set(0.0)
            self._emas.setdefault("hfu", 0.0)
            return
        if not self._flops_resolved:
            self._flops_resolved = True
            try:
                self._flops_per_step = self._flops_fn()  # type: ignore[misc]
            except Exception:
                self._flops_per_step = None
        if self._flops_per_step:
            hfu = self._flops_per_step / (interval_s * self.peak_flops_total)
            self._g_hfu.set(self._ema("hfu", hfu))
        else:
            self._g_hfu.set(0.0)
            self._emas.setdefault("hfu", 0.0)

    # -- read side ---------------------------------------------------------

    @property
    def steps(self) -> int:
        return self._steps

    @property
    def compiles(self) -> int:
        """Compiles seen by THIS train step (the registry counter is the
        process-wide total across all built steps)."""
        return self._compiles

    def latest(self) -> dict[str, float]:
        """Flat float dict for the tracker glue (`Accelerator.log`) and
        bench lines — same field names as the registry gauges."""
        out = {
            "train_step_ms": self._emas.get("step_ms", 0.0),
            "train_dispatch_ms": self._emas.get("dispatch_ms", 0.0),
            "train_tokens_per_sec": self._emas.get("tps", 0.0),
            "train_hfu": self._emas.get("hfu", 0.0),
            "train_compiles": float(self._compiles),
        }
        if self._sampled_device_ms is not None:
            out["train_device_ms"] = self._sampled_device_ms
        return out
