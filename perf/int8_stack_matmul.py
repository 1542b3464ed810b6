"""On the chip: `int8_matmul` over a layer stack, read in place against
sliced a layer at a time, at a decode step's 32 rows and a prefill chunk's
256 and 1024 (Mistral-7B widths: 4096 x 14336 and 14336 x 4096, a stack of
16 layers, 0.94 GB a matrix).

    chiprun -- python3 perf/int8_stack_matmul.py

A scan over the layers calls the kernel once a layer, as the model does.
``in_place`` closes over the stack and hands the kernel the layer index;
``sliced`` scans over the stack, so XLA copies a layer out before each call.
``in_place`` is timed at several VMEM budgets (the weight tile the plan
picks is printed): the module's own first. Prints one JSON line of
milliseconds a layer's call; refuses to run without a TPU.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.native.pallas import quant_matmul  # noqa: E402

L, D, F = 16, 4096, 14336
EQ = "md,df->mf"
MIB = 2**20


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n / L * 1e3


def in_place(x, stack, scales):
    def body(i, acc):
        out = quant_matmul.int8_matmul_fused(EQ, x, stack, scales[i], i)
        return acc + out[:, :128].astype(jnp.float32)

    return jax.lax.fori_loop(0, L, body, jnp.zeros((x.shape[0], 128), jnp.float32))


def sliced(x, stack, scales):
    def body(acc, layer):
        w, s = layer
        out = quant_matmul.int8_matmul_fused(EQ, x, w, s)
        return acc + out[:, :128].astype(jnp.float32), None

    acc = jnp.zeros((x.shape[0], 128), jnp.float32)
    return jax.lax.scan(body, acc, (stack, scales))[0]


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("int8_stack_matmul: no TPU; nothing was run", file=sys.stderr)
        return 2
    out = {"device_kind": jax.devices()[0].device_kind, "layers": L}
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    budget = quant_matmul._VMEM_BUDGET
    for c, n in ((D, F), (F, D)):
        stack = jax.random.randint(keys[0], (L, c, n), -127, 128, jnp.int8)
        scales = jnp.full((L, 1, n), 1e-3, jnp.float32)
        for rows in (32, 256, 1024):
            x = jax.random.normal(keys[1], (rows, c)).astype(jnp.bfloat16)
            cell = out[f"{c}x{n}.rows{rows}"] = {}
            for mib in (12, 6, 3, 1.5):
                quant_matmul._VMEM_BUDGET = int(mib * MIB)
                plan = quant_matmul._plan(EQ, x, jax.ShapeDtypeStruct((c, n), jnp.int8), x.dtype)
                if plan is None:
                    continue
                tiles = "bm{5}.bn{6}.bc{7}".format(*plan)
                if f"in_place.{tiles}" in cell:
                    continue
                # A fresh function object a budget: jit caches by object.
                cell[f"in_place.{tiles}"] = timed(jax.jit(lambda *a: in_place(*a)), x, stack, scales)
            quant_matmul._VMEM_BUDGET = budget
            cell["sliced"] = timed(jax.jit(lambda *a: sliced(*a)), x, stack, scales)
            cell["hbm_floor"] = c * n / 819e9 * 1e3
        del stack
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
