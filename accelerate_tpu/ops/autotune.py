"""Persisted block-size autotune cache for the Pallas kernel tier.

`ops/flash_attention.py::pick_block` is a static heuristic ("largest tile
that divides"). This module promotes it to a small persisted cache so a
measured-best block survives process restarts and is shared across kernels:

- entries are keyed ``op|shape|dtype`` inside a per-chip-generation JSON
  file (``$ATX_AUTOTUNE_DIR/<chip>.json``) — a v5e tuning never leaks onto
  a v4;
- an environment override always wins: ``ATX_BLOCK_<OP>`` (e.g.
  ``ATX_BLOCK_FLASH_ATTENTION=1024``) forces the block for every shape of
  that op, the knob used when bisecting a tuning regression;
- without ``ATX_AUTOTUNE_DIR`` the cache is purely in-memory (tests, and
  one-shot jobs that shouldn't write dotfiles);
- a cached block that no longer divides the requested dim (shape drifted)
  is ignored, never returned stale.

ATX603 uses the same table as ground truth: a dot whose dims defeat every
cached/heuristic block is exactly the tiling-waste case it flags.
"""

from __future__ import annotations

import json
import os
import re
import threading
from typing import Any

from .flash_attention import pick_block, tuned_call_kwargs  # noqa: F401  (re-export)

_ENV_DIR = "ATX_AUTOTUNE_DIR"
_DEFAULT_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)


def _chip_name() -> str:
    from ..analysis.roofline import chip_spec_for

    return chip_spec_for().name


def _env_override(op: str) -> int | None:
    raw = os.environ.get("ATX_BLOCK_" + re.sub(r"\W", "_", op).upper())
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        return None


class AutotuneCache:
    """Per-chip block table: in-memory always, JSON-persisted when a
    directory is configured. Thread-safe; writes are atomic (tmp+rename)
    so a killed process never leaves a torn table."""

    def __init__(self, chip: str | None = None, directory: str | None = None):
        self.chip = chip or _chip_name()
        self.directory = directory if directory is not None else os.environ.get(_ENV_DIR)
        self._lock = threading.Lock()
        self._table: dict[str, int] = {}
        self._loaded = False

    # ---------------------------------------------------------- internals
    @property
    def path(self) -> str | None:
        if not self.directory:
            return None
        return os.path.join(self.directory, f"{self.chip}.json")

    @staticmethod
    def key(op: str, shape: tuple[int, ...], dtype: Any) -> str:
        dt = getattr(dtype, "name", None) or str(dtype)
        return f"{op}|{'x'.join(str(int(d)) for d in shape)}|{dt}"

    def _load(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        path = self.path
        if path is None or not os.path.exists(path):
            return
        try:
            with open(path) as fh:
                disk = json.load(fh)
            blocks = disk.get("blocks", disk)
            # Disk entries fill gaps; in-memory puts from this process win.
            merged = {k: int(v) for k, v in blocks.items()}
            merged.update(self._table)
            self._table = merged
        except (OSError, ValueError):
            pass  # unreadable cache == empty cache

    def _persist(self) -> None:
        path = self.path
        if path is None:
            return
        try:
            os.makedirs(self.directory, exist_ok=True)
            tmp = path + f".tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(
                    {"chip": self.chip, "blocks": dict(sorted(self._table.items()))},
                    fh,
                    indent=2,
                )
                fh.write("\n")
            os.replace(tmp, path)
        except OSError:
            pass  # read-only FS: stay in-memory

    # ------------------------------------------------------------- access
    def get(self, op: str, shape: tuple[int, ...], dtype: Any) -> int | None:
        override = _env_override(op)
        if override is not None:
            return override
        with self._lock:
            self._load()
            return self._table.get(self.key(op, shape, dtype))

    def put(self, op: str, shape: tuple[int, ...], dtype: Any, block: int) -> None:
        key = self.key(op, shape, dtype)
        with self._lock:
            self._load()
            if self._table.get(key) == int(block):
                return
            self._table[key] = int(block)
            self._persist()


_default_cache: AutotuneCache | None = None
_default_lock = threading.Lock()


def default_cache() -> AutotuneCache:
    """Process-wide cache; rebuilt if ATX_AUTOTUNE_DIR changed (tests)."""
    global _default_cache
    with _default_lock:
        current_dir = os.environ.get(_ENV_DIR)
        if _default_cache is None or _default_cache.directory != current_dir:
            _default_cache = AutotuneCache()
        return _default_cache


def cached_pick_block(
    op: str,
    dim: int,
    candidates: tuple[int, ...] = _DEFAULT_CANDIDATES,
    dtype: Any = "any",
    cache: AutotuneCache | None = None,
):
    """`pick_block` with the persisted table consulted first. Precedence:
    ``ATX_BLOCK_<OP>`` env override > cached entry > heuristic. A cached or
    overridden block that doesn't divide ``dim`` is ignored (the kernels
    never pad). Heuristic picks are written back so the table documents
    what actually ran."""
    cache = cache or default_cache()
    hit = cache.get(op, (dim,), dtype)
    if hit is not None and hit > 0 and dim % hit == 0:
        return hit
    block = pick_block(dim, candidates)
    if block is not None:
        cache.put(op, (dim,), dtype, block)
    return block
