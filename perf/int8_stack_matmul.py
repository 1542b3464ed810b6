"""On the chip: `int8_matmul` over a layer stack, read in place against
sliced a layer at a time, at a decode step's 32 rows and a prefill chunk's
256, and at a chunk's 1024 rows the plan it splits into two row tiles
against one row tile of rows quantized once (Mistral-7B widths: 4096 x
14336 and 14336 x 4096, a stack of 16 layers, 0.94 GB a matrix).

    chiprun -- python3 perf/int8_stack_matmul.py

A scan over the layers calls the kernel once a layer, as the model does,
on an activation that depends on the previous layer's output, so a
quantization pass outside the kernel runs every layer and is timed with it.
``in_place`` closes over the stack and hands the kernel the layer index;
``sliced`` scans over the stack, so XLA copies a layer out before each call.
At 32 and 256 rows ``in_place`` is timed at several VMEM budgets (the tiles
the plan picks are printed): the module's own first. At 1024 rows
``split_rows`` is the plan of two row tiles with the quantization in the
kernel, ``one_row_tile`` the module's plan over `quantize_act`'s rows, and
``one_row_tile.bn<n>.bc<c>`` that plan at other tiles. Prints one JSON line
of milliseconds a layer's call, with the int8 peak's time for the call;
refuses to run without a TPU.
"""

import contextlib
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
INT8_PEAK = 394e12  # TPU v5e, operations/s


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n / L * 1e3


def _next_input(x, acc):
    # The next layer's activation depends on this one's output: nothing
    # computed from it can be hoisted out of the loop.
    return x + (acc[:, :1] * 1e-6).astype(x.dtype)


def in_place(x, stack, scales):
    def body(i, acc):
        out = quant_matmul.int8_matmul_fused(EQ, _next_input(x, acc), stack, scales[i], i)
        return acc + out[:, :128].astype(jnp.float32)

    return jax.lax.fori_loop(0, L, body, jnp.zeros((x.shape[0], 128), jnp.float32))


def sliced(x, stack, scales):
    def body(acc, layer):
        w, s = layer
        out = quant_matmul.int8_matmul_fused(EQ, _next_input(x, acc), w, s)
        return acc + out[:, :128].astype(jnp.float32), None

    acc = jnp.zeros((x.shape[0], 128), jnp.float32)
    return jax.lax.scan(body, acc, (stack, scales))[0]


@contextlib.contextmanager
def rows_plan(tiles=None, off=False):
    """The module's one-row-tile plan, turned off or at other ``(bn, bc)``
    tiles, while the block lasts."""
    plan_fn = quant_matmul.one_row_tile_plan

    def plan(eq, x, w):
        p = None if off else plan_fn(eq, x, w)
        return p if p is None or tiles is None else p[:6] + tiles + p[8:]

    quant_matmul.one_row_tile_plan = plan
    try:
        yield
    finally:
        quant_matmul.one_row_tile_plan = plan_fn


def rows_variants(c):
    """``(name, rows_plan kwargs)`` timed at 1024 rows for a contraction of
    ``c``: the module's own plans first."""
    yield "split_rows", dict(off=True)
    yield "one_row_tile", {}
    for bn, bc in ((512, 2048), (256, 2048), (256, 3584 if c > 4096 else 4096)):
        yield f"one_row_tile.bn{bn}.bc{bc}", dict(tiles=(bn, bc))


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
        w = jax.ShapeDtypeStruct((c, n), jnp.int8)
        for rows in (32, 256, 1024):
            x = jax.random.normal(keys[1], (rows, c)).astype(jnp.bfloat16)
            cell = out[f"{c}x{n}.rows{rows}"] = {}
            if rows == 1024:
                for name, kwargs in rows_variants(c):
                    with rows_plan(**kwargs):
                        plan = quant_matmul.one_row_tile_plan(EQ, x, w) or quant_matmul._plan(EQ, x, w, x.dtype)
                        tiles = "bm{5}.bn{6}.bc{7}".format(*plan)
                        try:
                            cell[f"{name}.{tiles}"] = timed(jax.jit(lambda *a: in_place(*a)), x, stack, scales)
                        except Exception as e:  # a tiling the compiler refuses
                            cell[f"{name}.{tiles}"] = f"{type(e).__name__}: {str(e)[:160]}"
                cell["int8_peak"] = 2 * rows * c * n / INT8_PEAK * 1e3
            else:
                for mib in (12, 6, 3, 1.5):
                    quant_matmul._VMEM_BUDGET = int(mib * MIB)
                    plan = quant_matmul._plan(EQ, x, w, x.dtype)
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
