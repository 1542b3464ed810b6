"""On the chip: `flash_prefill` alone against the XLA lowering it replaces, a
prefill chunk over the batch-1 row view of a layer-stacked KV cache at the
serve cells' shapes.

    chiprun -- python3 perf/flash_prefill_live_rows.py

- ``long``: a 1024-row chunk of 32 query heads over 8192 rows of 8 kv heads
  x 128 (bf16), the cursor at 0, 1024, ... 7168;
- ``chat`` / ``hybrid`` / ``mixed``: a 256-row chunk over 1024 rows (32/8
  heads), 2048 rows (30/30) and 16,384 rows (28/4), the cursor at 0, in the
  middle and at the slot's end; ``mixed_1024`` a 1024-row chunk over 16,384.

A loop over the layers calls `layers.cached_attention` once a layer, as the
model's scan does: with the kernel on, and with `prefill_attn` forced off
(the sliced lowering: the (B, S, T) mask the llama family hands it, or the
cursor and 256-query blocks as the other two families do). Prints one JSON
line: milliseconds a layer for both, the kernel's share of 197 TFLOP/s on the
rows a query sees (4 x heads x 128 operations a visible (query, key) pair),
and the kernel at other tiles (``--tiles bq,bk ...``) where a cursor is
marked for it. Refuses to run without a TPU.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.models.layers import cache_positions, cached_attention  # noqa: E402
from accelerate_tpu.native.pallas import force_kernels, prefill_attention  # noqa: E402

PEAK_FLOPS = 197e12
HEAD = 128
LAYERS = 4
# name: (chunk rows, slot rows, query heads, kv heads, hands a mask, cursors)
SHAPES = {
    "long": (1024, 8192, 32, 8, True, tuple(range(0, 8192, 1024))),
    "chat": (256, 1024, 32, 8, True, (0, 256, 768)),
    "chat_32": (32, 1024, 32, 8, True, (0, 480, 992)),
    "chat_64": (64, 1024, 32, 8, True, (0, 448, 960)),
    "hybrid": (256, 2048, 30, 30, False, (0, 1024, 1792)),
    "hybrid_64": (64, 2048, 30, 30, False, (0, 1024, 1984)),
    "mixed": (256, 16384, 28, 4, False, (0, 8192, 16128)),
    "mixed_1024": (1024, 16384, 28, 4, False, (0, 4096, 15360)),
}
# Cursors at which the other tiles are timed too.
SWEPT = {"long": (0, 2048, 7168), "mixed_1024": (4096,), "chat": (256,), "hybrid": (1024,)}


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3 / LAYERS


def every_layer(attend):
    def run(q, k, v, start):
        def body(i, acc):
            return acc + attend(q, k, v, start, i).astype(jnp.float32)

        return jax.lax.fori_loop(0, LAYERS, body, jnp.zeros(q.shape, jnp.float32))

    return jax.jit(run)


def through_layers(masked: bool):
    """The call the families make: `cached_attention` by the cursor."""

    def attend(q, k, v, start, i):
        mask = None
        if masked:
            positions = cache_positions(start, q.shape[1], q.shape[0])
            mask = jnp.arange(k.shape[2], dtype=jnp.int32)[None, None, :] <= positions[:, :, None]
        return cached_attention(q, {"k": k, "v": v}, i, mask=mask, start=start, q_block=256)

    return attend


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiles", nargs="*", default=["128,512", "256,256", "256,1024"])
    parser.add_argument("--only", nargs="*", default=list(SHAPES))
    args = parser.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("flash_prefill_live_rows: no TPU; nothing was run", file=sys.stderr)
        return 2
    out = {"device_kind": jax.devices()[0].device_kind}
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    for name in args.only:
        S, T, heads, kv_heads, masked, cursors = SHAPES[name]
        k = jax.random.normal(keys[0], (LAYERS, 1, T, kv_heads * HEAD), jnp.bfloat16)
        v = jax.random.normal(keys[1], (LAYERS, 1, T, kv_heads * HEAD), jnp.bfloat16)
        q = jax.random.normal(keys[2], (1, S, heads, HEAD), jnp.bfloat16)
        own = prefill_attention.pick_tiles(S, T, heads // kv_heads)
        cell = out[name] = {"tiles": own, "supported": prefill_attention.supported(q, k, compiled=True)}
        kernel = every_layer(through_layers(masked))
        with force_kernels("off", "prefill_attn"):
            xla = every_layer(through_layers(masked))
            jax.block_until_ready(xla(q, k, v, jnp.int32(0)))  # traced with the kernel off
        others = {}
        for spec in args.tiles:
            tiles = tuple(int(t) for t in spec.split(","))
            if tiles != own and S % tiles[0] == 0 and T % tiles[1] == 0:
                others[spec] = every_layer(
                    lambda q, k, v, start, i, tiles=tiles: prefill_attention.flash_prefill(
                        q, k, v, start, i, tiles=tiles
                    )
                )
        for cursor in cursors:
            start = jnp.int32(cursor)
            visible = S * cursor + S * (S + 1) // 2
            at_peak_ms = 4 * heads * HEAD * visible / PEAK_FLOPS * 1e3
            row = cell[f"cursor_{cursor}"] = {
                "xla_ms": timed(xla, q, k, v, start),
                "kernel_ms": timed(kernel, q, k, v, start),
                "visible_at_peak_ms": at_peak_ms,
            }
            row["kernel_share_of_peak"] = at_peak_ms / row["kernel_ms"]
            if cursor in SWEPT.get(name, ()):
                for spec, fn in others.items():
                    try:
                        row[f"kernel_ms_{spec}"] = timed(fn, q, k, v, start)
                    except Exception as e:  # a tile the chip's compiler refuses
                        row[f"kernel_ms_{spec}"] = f"{type(e).__name__}: {str(e)[:120]}"
        del k, v
    print(json.dumps(out))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "flash_prefill_live_rows.json"), "a") as f:
        f.write(json.dumps(out) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
