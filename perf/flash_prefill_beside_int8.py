"""On the chip: does `flash_prefill` take longer beside `int8_matmul` over one
row tile of quantized rows than beside the split plan, or alone?

    python3 perf/flash_prefill_beside_int8.py    # on a TPU host

A loop over the layers does, a layer, what the long cell's 1024-row chunk
program does around its attention: the feed-forward up projection
(1024 x 4096 x 14336, int8) and `flash_prefill` of the chunk at cursor 3072
over a slot of 8192 rows (32 query heads, 8 kv heads of 128), each call's
input depending on the last call's output. ``one_row_tile`` runs the
module's plan, ``split_rows`` the plan of two row tiles (in-kernel
quantization), ``alone`` `flash_prefill` only. A profiler capture of each
gives both kernels' median device time a call; prints one JSON line.
Refuses to run without a TPU.
"""

import json
import os
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.native.pallas import prefill_attention, quant_matmul  # noqa: E402
from benchmarks.trace import xplane  # noqa: E402

L, D, F = 8, 4096, 14336
ROWS, SLOT, HEADS, KV_HEADS, HEAD, CURSOR = 1024, 8192, 32, 8, 128, 3072


def layers(with_int8: bool):
    def run(x, q, stack, scales, k, v):
        def body(i, acc):
            nudge = (acc[:, :1] * 1e-6).astype(jnp.bfloat16)
            if with_int8:
                y = quant_matmul.int8_matmul_fused("md,df->mf", x + nudge, stack, scales[i], i)
                qi = q + (y[:, :1] * 1e-6).reshape(1, ROWS, 1, 1)
            else:
                qi = q + nudge.reshape(1, ROWS, 1, 1)
            o = prefill_attention.flash_prefill(qi, k, v, jnp.int32(CURSOR), i)
            return acc + o[0, :, 0, :].astype(jnp.float32)

        return jax.lax.fori_loop(0, L, body, jnp.zeros((ROWS, HEAD), jnp.float32))

    return jax.jit(run)


def capture(fn, args, rounds=3):
    """Median device ms a call of each kernel, and wall ms a loop."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        t0 = time.perf_counter()
        for _ in range(rounds):
            out = fn(*args)
        jax.block_until_ready(out)
        wall = (time.perf_counter() - t0) / rounds * 1e3
        jax.profiler.stop_trace()
        ops = xplane.load(xplane.find_xplane(d)).devices[0].ops
    row = {"loop_wall_ms": wall}
    for kernel in ("flash_prefill", "int8_matmul"):
        times = [(e - s) * 1e3 for n, s, e in ops if n.lstrip("%").startswith(kernel)]
        if times:
            row[f"{kernel}_ms"] = statistics.median(times)
            row[f"{kernel}_calls"] = len(times)
    return row


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("flash_prefill_beside_int8: no TPU; nothing was run", file=sys.stderr)
        return 2
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    args = (
        jax.random.normal(keys[0], (ROWS, D)).astype(jnp.bfloat16),
        jax.random.normal(keys[1], (1, ROWS, HEADS, HEAD)).astype(jnp.bfloat16),
        jax.random.randint(keys[2], (L, D, F), -127, 128, jnp.int8),
        jnp.full((L, 1, F), 1e-3, jnp.float32),
        jax.random.normal(keys[3], (L, 1, SLOT, KV_HEADS * HEAD)).astype(jnp.bfloat16),
        jax.random.normal(keys[4], (L, 1, SLOT, KV_HEADS * HEAD)).astype(jnp.bfloat16),
    )
    out = {"device_kind": jax.devices()[0].device_kind, "layers": L, "cursor": CURSOR}
    plan = quant_matmul.one_row_tile_plan
    for name in ("alone", "one_row_tile", "split_rows", "one_row_tile_again", "alone_again"):
        if name.startswith("split"):
            quant_matmul.one_row_tile_plan = lambda *a: None
        out[name] = capture(layers(not name.startswith("alone")), args)
        quant_matmul.one_row_tile_plan = plan
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
