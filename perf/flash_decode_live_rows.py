"""On the chip: `flash_decode` alone over a layer-stacked KV cache at the
three serve cells' shapes, with cursors as the cells leave them and, as the
control, with every row full.

    chiprun -- python3 perf/flash_decode_live_rows.py

- ``chat``: 32 layers x 32 slots x 1024 rows of 8 heads x 128 (bf16), nine
  slots at 150-700 live rows and the rest empty (attended length 1);
- ``long``: 32 x 4 x 8192, every slot at 2,100-6,240;
- ``mixed_full`` / ``mixed_ring``: 3 x 16 x 16,384 and 9 x 16 x 4096 rows of
  4 heads x 128 with 7 query heads a kv head, chat turns (~430 rows) beside
  documents of 4k-14k (a ring holds ``min(cursor, 4096)``).

A loop over the layers calls the kernel once a layer, as the model's scan
does. Prints one JSON line: milliseconds a call at the cell's cursors
(``live``) and with every row full (``full``), beside the time the live rows
(K and V) take at 819 GB/s and, where the module says what it fetches
(`rows_fetched`), the time of those rows. After the module's own block, the
others are timed through ``ATX_BLOCK_DECODE_ATTENTION``. The script times the
tree it lies in: copied into another checkout's ``perf/``, that checkout's
kernel. Refuses to run without a TPU.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.native.pallas import decode_attention  # noqa: E402

HBM_BYTES_PER_S = 819e9
BLOCK_ENV = "ATX_BLOCK_DECODE_ATTENTION"
# name: (layers, slots, rows a slot, query heads, kv heads, cursors)
_RNG = np.random.default_rng(32)
_DOCS = _RNG.integers(4000, 14000, 5)
_TURNS = _RNG.integers(200, 700, 11)
SHAPES = {
    "chat": (32, 32, 1024, 32, 8, np.r_[_RNG.integers(150, 700, 9), np.ones(23, np.int64)]),
    "long": (32, 4, 8192, 32, 8, _RNG.integers(2100, 6240, 4)),
    "mixed_full": (3, 16, 16384, 28, 4, np.r_[_DOCS, _TURNS]),
    "mixed_ring": (9, 16, 4096, 28, 4, np.minimum(np.r_[_DOCS, _TURNS], 4096)),
}
HEAD = 128


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def every_layer(q, k, v, lengths):
    def body(i, acc):
        return acc + decode_attention.flash_decode(q, k, v, lengths, i).astype(jnp.float32)

    return jax.lax.fori_loop(0, k.shape[0], body, jnp.zeros(q.shape, jnp.float32))


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("flash_decode_live_rows: no TPU; nothing was run", file=sys.stderr)
        return 2
    out = {"device_kind": jax.devices()[0].device_kind}
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    for name, (layers, slots, rows, heads, kv_heads, cursors) in SHAPES.items():
        lanes = kv_heads * HEAD
        row_bytes = 2 * lanes * 2  # K and V, bf16
        k = jax.random.normal(keys[0], (layers, slots, rows, lanes), jnp.bfloat16)
        v = jax.random.normal(keys[1], (layers, slots, rows, lanes), jnp.bfloat16)
        q = jax.random.normal(keys[2], (slots, 1, heads, HEAD), jnp.bfloat16)
        cell = out[name] = {
            "cursors": cursors.tolist(),
            "live_rows_at_roof_ms": int(cursors.sum()) * row_bytes / HBM_BYTES_PER_S * 1e3,
            "full_at_roof_ms": slots * rows * row_bytes / HBM_BYTES_PER_S * 1e3,
        }
        for forced in (None, 128, 256, 512, 1024):
            os.environ.pop(BLOCK_ENV, None)
            if forced is not None:
                os.environ[BLOCK_ENV] = str(forced)
            try:
                blk = decode_attention.pick_block(rows, lanes * 2)
            except TypeError:  # a tree whose blocks are one head wide
                blk = decode_attention.pick_block(rows)
            if f"blk{blk}" in cell:
                continue
            timing = cell[f"blk{blk}"] = {"own_choice": forced is None}
            if hasattr(decode_attention, "rows_fetched"):
                fetched = decode_attention.rows_fetched(cursors, rows, lanes * 2)
                timing["fetched_rows_at_roof_ms"] = fetched * row_bytes / HBM_BYTES_PER_S * 1e3
            # A fresh function object a block size: jit caches by object.
            fn = jax.jit(lambda *a: every_layer(*a))
            for which, lengths in (("live", cursors), ("full", np.full(slots, rows))):
                timing[f"{which}_ms"] = timed(fn, q, k, v, jnp.asarray(lengths, jnp.int32)) / layers
        os.environ.pop(BLOCK_ENV, None)
        del k, v
    print(json.dumps(out))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "flash_decode_live_rows.json"), "a") as f:
        f.write(json.dumps({"tree": os.path.dirname(os.path.dirname(os.path.abspath(__file__))), **out}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
