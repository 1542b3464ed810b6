"""On the chip: the dropless expert layer's grouped products, the Pallas
kernel against `jax.lax.ragged_dot`, at a decode step's 16 rows and a
prefill chunk's 256 and 1024 (SmallThinker widths: 64 experts of 2560 x 768,
6 a row, bf16, a two-layer stack).

    chiprun -- python3 perf/moe_grouped_products.py

Three ways, each the whole layer (routing, sort, products, weighted sum):
``kernel`` reads the (L, E, ...) stacks in place; ``ragged`` is the fallback
as the model would run it, the layer's experts sliced out of the stack first;
``ragged_own`` is handed that layer's (E, ...) weights, so it pays no slice
(what `ragged_dot` alone costs). Prints one JSON line; refuses to run
without a TPU.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.native.pallas import force_kernels  # noqa: E402
from accelerate_tpu.ops import moe  # noqa: E402

L, E, D, F, K = 2, 64, 2560, 768, 6


def timed(fn, *args, n=30):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("moe_grouped_products: no TPU; nothing was run", file=sys.stderr)
        return 2
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    stacked = {
        "router": (jax.random.normal(keys[0], (L, D, E)) / 50).astype(bf),
        "w_gate": (jax.random.normal(keys[1], (L, E, D, F)) / 50).astype(bf),
        "w_up": (jax.random.normal(keys[2], (L, E, D, F)) / 50).astype(bf),
        "w_down": (jax.random.normal(keys[3], (L, E, F, D)) / 28).astype(bf),
    }
    own = jax.tree.map(lambda w: w[1], stacked)
    out = {"device_kind": jax.devices()[0].device_kind}
    for rows in (16, 256, 1024):
        x = jax.random.normal(keys[4], (rows, D)).astype(bf)
        h = jax.random.normal(keys[5], (rows, D)).astype(bf)
        # The kernel mode is read when a function is traced, and jit caches
        # by function object: one lambda for each mode.
        with force_kernels("on"):
            kernel = jax.jit(lambda p, x, h: moe.moe_dropless(p, x, h, top_k=K, layer=1))
            t_kernel = timed(kernel, stacked, x, h)
            got, counts = kernel(stacked, x, h)
        with force_kernels("off"):
            ragged = jax.jit(lambda p, x, h: moe.moe_dropless(p, x, h, top_k=K, layer=1))
            t_ragged = timed(ragged, stacked, x, h)
            t_own = timed(jax.jit(lambda p, x, h: moe.moe_dropless(p, x, h, top_k=K)), own, x, h)
            want, _ = ragged(stacked, x, h)
        err = float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max())
        out[f"rows_{rows}"] = {
            "kernel_ms": t_kernel, "ragged_ms": t_ragged, "ragged_own_ms": t_own,
            "max_abs_diff": err, "scale": float(jnp.abs(want.astype(jnp.float32)).max()),
            **{k: int(v) for k, v in counts.items()},
        }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
