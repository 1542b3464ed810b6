"""Per-device-kind fp8 matmul speedup telemetry.

fp8 on a chip without fp8 MXU support is a lose-lose: XLA upcasts the
scaled values, so you pay quantization error for zero speedup. The launcher
refuses `--mixed_precision fp8` on device kinds with recorded speedup <= 1
unless `--force_fp8` is passed (reference analog: the TE/ao fp8 recipes are
only wired for hardware that benefits, `utils/ao.py:103`).

`record()` persists a speedup measured on the local chip (an fp8 against a
bf16 matmul of the same shape), which then overrides the built-in table.
"""

from __future__ import annotations

import json
import os

# kind -> fp8/bf16 matmul speedup. v5e has no fp8 MXU: the fp8 path lowers
# to upcast-and-multiply. The entry predates `benchmarks/` and is in no
# ledger line; all the launcher reads of it is that it is <= 1.
_BUILTIN: dict[str, float] = {
    "TPU v5 lite": 0.51,
}


def _store_path() -> str:
    root = os.environ.get("ATX_CACHE_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "accelerate_tpu"
    )
    return os.path.join(root, "fp8_telemetry.json")


def record(device_kind: str, speedup: float) -> None:
    """Persist a measured fp8 speedup for this device kind."""
    path = _store_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    data: dict[str, float] = {}
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        pass
    data[device_kind] = float(speedup)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f)
    os.replace(tmp, path)


def lookup(device_kind: str) -> float | None:
    """Recorded speedup for this device kind; measurements override the
    built-in table, None when the kind has never been measured."""
    try:
        with open(_store_path()) as f:
            data = json.load(f)
        if device_kind in data:
            return float(data[device_kind])
    except (OSError, ValueError):
        pass
    return _BUILTIN.get(device_kind)
