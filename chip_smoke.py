"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the widths of `LlamaConfig.llama3_8b` (d_model 4096, 32/8 heads of 128,
d_ff 14336, vocab 128256) with random weights made from ``--seed``:

- *train*: `Accelerator.create_train_state` / `make_train_step`, flash
  attention + remat + bf16 weights + adafactor, a few steps on one repeated
  batch; the loss must be finite and fall;
- *serve*: `serving.Engine` as `atx serve` builds it, int8 block weights on
  the int8 MXU path (`ops/int8.py`), a handful of requests of mixed lengths;
  every request returns its whole token budget, and the greedy tokens of one
  request are the argmax of a cache-free reference forward and equal
  `generation.Generator`'s for the same prompt (up to a tie in bf16);
- *which path ran*: the `tpu_custom_call` count of the compiled train step
  and decode step, `kernel_status()`, the native host loader, peak device
  memory and the compile-cache directory; and each main-path kernel against
  the reference lowering it replaces, on a small input.

``--chips 4`` runs instead — and only — the same train step sharded
`MeshConfig(fsdp=2, tensor=2)` over four chips against its one-device loss.

It measures the chip or nothing: with no TPU it exits non-zero before any
phase, any phase that raises ends the run non-zero, and the last line of
standard output is one JSON object, ``{"ok": true, "device": {...}}``, with
the device as JAX reports it. One process; JAX is touched once. The CPU
rehearsal is `tests/test_chip_compile.py`, which imports the phase functions
at tiny sizes.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time

# What one 16 GB v5e chip forces (AOT `memory_analysis()` of the step against
# the described chip, jax 0.9.0 / libtpu 0.0.34): the two 128256 x 4096
# embedding tables are 1.05 B parameters before the first block, and bf16
# weights + bf16 gradients + the remat-saved activations of 8 blocks at 4096
# tokens come to 13.75 GiB of the 15.75 GiB the chip gives a program
# (12 blocks: 16.2 GiB; 8 blocks at 8192 tokens: 16.5 GiB).
TRAIN_CUTS = {"n_layers": 8, "seq_len": 4096, "batch_size": 1}
TRAIN_STEPS = 4
# Four chips run what one chip can also hold (the comparison is made on one
# device in the same run); the batch is 2 because data x fsdp = 2 shards it.
SHARDED_CUTS = {"n_layers": 8, "seq_len": 2048, "batch_size": 2}
# Relative tolerance between the one-device and the sharded loss: bf16
# matmul outputs summed in another order across the tensor axis.
SHARDED_RTOL = 2e-2
# (prompt tokens, new tokens) per request; the first one is also run alone
# through `Generator`. Its prompt fills one prefill bucket and prompt + budget
# fill the slot, so engine and generator see the same shapes per row.
SERVE_REQUESTS = ((128, 128), (24, 40), (57, 64), (200, 48), (90, 32), (16, 24))
SERVE_ENGINE = {"slots": 4, "buckets": (64, 128), "max_len": 256}
# A served token may fall this far short of the reference's top logit,
# relative to it, and still count as its argmax (the top two of 128k random
# logits are ~5% apart on average, often closer): the worst of a request's
# 128 tokens, and their mean. The worst is a lottery of near-ties and guards
# against a wrong token (which reads ~1); the mean is what tells a precision
# from the next. Both lie between two readings of this check at this config,
# through `serve_phase`'s own raise (`perf/smoke_argmax_limit.py`; my chip
# runs, PR 35). Sound, over seeds 0-19 with a chunk's attention through
# `flash_prefill`: worst 0.013-0.049, mean 0.0003-0.0023; over seeds 0-15
# with it sliced as before PR 35: worst 0.017-0.047, mean 0.0003-0.0019 (the
# 2^-5 that stood here alone refused 11 of the 20 and 6 of the 16). One
# precision down, every matrix of the engine's weights on float8_e4m3's grid
# and the references on the weights as made, over seeds 0-14: worst
# 0.063-0.131, mean 0.0046-0.0155, refused 15 of 15.
ARGMAX_RTOL = 2.0**-4
ARGMAX_MEAN_RTOL = 0.0033

_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(?:-start)?\("
)


def _say(phase: str, **fields) -> None:
    print(f"[chip_smoke] {phase}: " + json.dumps(fields, default=str), flush=True)


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _run_steps(step, state, batch, steps: int):
    """``steps`` steps on the repeated batch: (state, losses, seconds per
    step, seconds `block_until_ready` held the last step, seconds the scalar
    fetch after it took)."""
    import jax

    losses, seconds = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        t1 = time.perf_counter()
        jax.block_until_ready(metrics["loss"])
        t2 = time.perf_counter()
        losses.append(float(metrics["loss"]))
        t3 = time.perf_counter()
        seconds.append(t3 - t0)
    return state, losses, seconds, t2 - t1, t3 - t2


def _check_losses(losses) -> None:
    import math

    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite training loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on a repeated batch: {losses}")


def _train(config, mesh_config, *, batch_size: int, seq_len: int, steps: int, seed: int, **sharding):
    """A few optimizer steps through `Accelerator` on ``mesh_config``'s
    devices, on one repeated batch of random tokens. Returns the result
    fields (among them the bytes each device holds of one large parameter)
    and the compiled step's text."""
    import jax
    import jax.numpy as jnp
    import optax

    import accelerate_tpu as atx
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel.mesh import batch_sharding
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state()
    acc = atx.Accelerator(
        mixed_precision="bf16", seed=seed, max_grad_norm=1.0, mesh_config=mesh_config, **sharding
    )
    # bf16 weights + adafactor: the recipe that trains the repo's largest
    # model on one chip (fp32 masters + adam moments would be 16 bytes a
    # parameter). The rate is large enough to survive bf16 rounding.
    state = acc.create_train_state(
        lambda r: llama.init(r, config, dtype=jnp.bfloat16), optax.adafactor(1e-2)
    )
    step = acc.make_train_step(lambda p, b, r: llama.loss_fn(p, b, config, r))
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch_size, seq_len), 0, config.vocab_size, jnp.int32
    )
    batch = jax.device_put({"input_ids": tokens}, batch_sharding(acc.mesh))
    # Compile first, through the lowering the step exposes: its seconds are
    # compile seconds (or a cache read) and its text says which path runs.
    t0 = time.perf_counter()
    text = step.lower(state, batch).compile().as_text()
    compile_s = time.perf_counter() - t0
    w_gate = state.params["blocks"]["mlp"]["w_gate"]  # donated by the first step
    placement = {
        "w_gate_bytes": int(w_gate.nbytes),
        "w_gate_shard_bytes": {
            str(s.device): int(s.data.nbytes) for s in w_gate.addressable_shards
        },
    }
    state, losses, seconds, block_s, fetch_s = _run_steps(step, state, batch, steps)
    _check_losses(losses)
    out = {
        "params": sum(x.size for x in jax.tree.leaves(state.params)),
        "tokens_per_step": batch_size * seq_len,
        "losses": [round(x, 4) for x in losses],
        "compile_s": round(compile_s, 2),
        "step_s": [round(x, 3) for x in seconds],
        "block_until_ready_s": round(block_s, 3),
        "fetch_after_block_s": round(fetch_s, 4),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        **placement,
    }
    state, batch, w_gate = acc.free_memory(state, batch, w_gate)
    return out, text


def train_phase(config, *, seed: int, **size) -> dict:
    """The train step on one device (``size``: batch_size, seq_len, steps)."""
    import jax

    import accelerate_tpu as atx

    device = jax.devices()[0]
    out, _ = _train(config, atx.MeshConfig(devices=[device]), seed=seed, **size)
    return {**out, "peak_bytes_in_use": _peak_bytes(device)}


def init_int8_params(rng, config):
    """Llama params with int8 block weights (`utils.quantization` nodes),
    made on the device one layer at a time: the bf16 copy of the blocks,
    twice the int8 bytes, never exists. Embeddings, head and norms stay
    bf16, as `load_pretrained(quantize_bits=8)` leaves them."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import llama
    from accelerate_tpu.utils.quantization import quantize_pytree

    def build(rng):
        k_top, k_blocks = jax.random.split(rng)
        # Only the unstacked leaves are kept: the blocks of this init are
        # dead code the compiler drops.
        top = {
            k: v
            for k, v in llama.init(k_top, config, jnp.bfloat16).items()
            if k != "blocks"
        }

        def one_layer(key):
            # No stack axis inside one layer: scales per output channel.
            return quantize_pytree(
                llama.init_block(key, config, jnp.bfloat16),
                stack_dim_patterns=(("", 0),),
            )

        top["blocks"] = jax.lax.map(one_layer, jax.random.split(k_blocks, config.n_layers))
        return top

    return jax.jit(build)(rng)


def kernel_parity_phase(*, seq_len: int, cache_len: int, head_dim: int, seed: int) -> dict:
    """Each main-path kernel against the reference lowering it replaces, on
    a small input, through the entry points the models call: the largest
    error per kernel, relative to the reference's largest value. Where the
    dispatcher resolves a kernel to its fallback (off the chip, or switched
    off) both sides are the reference and the error is 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.layers import cache_append, cached_attention, dot_product_attention
    from accelerate_tpu.native.pallas.dispatch import force_kernels
    from accelerate_tpu.ops.flash_attention import flash_attention
    from accelerate_tpu.ops.int8 import int8_einsum, quantize_act

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    bf16 = jnp.bfloat16

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(bf16)

    def err(got, reference):
        f32 = lambda tree: [x.astype(jnp.float32) for x in jax.tree.leaves(tree)]
        return max(
            float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y)))
            for x, y in zip(f32(got), f32(reference))
        )

    def both(fn, *args):
        kernel = jax.jit(lambda *a: fn(*a))(*args)
        with force_kernels("off"):
            reference = jax.jit(lambda *a: fn(*a))(*args)
        return err(kernel, reference)

    heads, kv_heads = 4, 2
    q, k, v = (normal(1, seq_len, h, head_dim) for h in (heads, kv_heads, kv_heads))
    w = normal(1, seq_len, heads, head_dim)  # cotangent: every output matters

    def attention_grads(attend):
        loss = lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)

    flash = attention_grads(lambda q, k, v: flash_attention(q, k, v, causal=True))
    dot = attention_grads(lambda q, k, v: dot_product_attention(q, k, v, causal=True))
    out = {"flash_fwd_bwd": err(flash, dot)}

    # Decode: new rows appended to layer 1 of a two-layer stacked cache at
    # one cursor a slot, then attended in place (kernel) or sliced (off).
    slots, kv_heads = 4, 2
    dq, dk, dv = normal(slots, 1, 8, head_dim), normal(slots, cache_len, kv_heads, head_dim), normal(slots, cache_len, kv_heads, head_dim)
    lengths = jnp.asarray(np.linspace(1, cache_len, slots).astype(np.int32))
    mask = (jnp.arange(cache_len)[None, :] < lengths[:, None])[:, None, :]
    zeros = jnp.zeros((slots,), jnp.int32)

    def decode(q, k, v, kv):
        kv = cache_append(kv, 1, k, v, zeros)
        return cached_attention(q, kv, 1, mask=mask, lengths=lengths)

    stack = lambda *tail, dtype=bf16: jnp.zeros((2, slots, cache_len) + tail, dtype)
    flat = kv_heads * head_dim
    out["flash_decode"] = both(decode, dq, dk, dv, {"k": stack(flat), "v": stack(flat)})
    int8_cache = {
        "k": stack(flat, dtype=jnp.int8), "v": stack(flat, dtype=jnp.int8),
        "k_scale": stack(kv_heads), "v_scale": stack(kv_heads),
    }
    out["flash_decode_int8_kv"] = both(decode, dq, dk, dv, int8_cache)

    # A prefill chunk: a quarter of a slot's rows written into layer 1 of a
    # stack that holds other rows, at cursors that sit on no block edge.
    rows = cache_len // 4
    starts = np.linspace(0, cache_len - rows, slots).astype(np.int32)
    starts[1:-1] -= 3
    starts = jnp.asarray(starts)
    cq, ck, cv = normal(slots, rows, 8, head_dim), normal(slots, rows, kv_heads, head_dim), normal(slots, rows, kv_heads, head_dim)

    def chunk(q, k, v, kv):
        kv = cache_append(kv, 1, k, v, starts)
        return cached_attention(q, kv, 1, start=starts)

    held = {"k": normal(2, slots, cache_len, flat), "v": normal(2, slots, cache_len, flat)}
    out["flash_prefill"] = both(chunk, cq, ck, cv, held)

    x = normal(2, 8, 4 * head_dim)
    wq, w_scale = quantize_act(normal(4 * head_dim, 2, head_dim), (0,))
    out["int8_matmul"] = both(lambda x: int8_einsum("bsd,dhk->bshk", x, wq, w_scale), x)

    # The gated delta rule at the published head widths (96 x 192): a 128-row
    # chunk (the kernel holds the chunk-to-chunk state pass) and one token a
    # row on layer 1 of a two-layer state stack, two of four rows decoding.
    from accelerate_tpu.models.olmo_hybrid import decode_state
    from accelerate_tpu.ops import gated_delta

    def f32(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32)

    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    H, dk, dvl, rows = 4, 96, 192, 128
    rule = (
        unit(f32(1, rows, H, dk)) * dk**-0.5, unit(f32(1, rows, H, dk)), f32(1, rows, H, dvl),
        -0.2 * jnp.abs(f32(1, rows, H)), 2.0 * jax.nn.sigmoid(f32(1, rows, H)),
    )
    out["gdn_chunk"] = both(gated_delta.chunk_gated_delta, *rule, f32(1, H, dk, dvl))
    one = (
        unit(f32(slots, 1, H, dk)) * dk**-0.5, unit(f32(slots, 1, H, dk)), f32(slots, 1, H, dvl),
        -0.2 * jnp.abs(f32(slots, 1, H)), 2.0 * jax.nn.sigmoid(f32(slots, 1, H)),
    )
    decoding = jnp.asarray([True, False, True, False])
    out["gdn_decode"] = both(
        lambda *a: decode_state(a[:5], a[5], 1, decoding)[:2], *one, f32(2, slots, H, dk, dvl)
    )
    return {name: round(value, 7) for name, value in out.items()}


# bf16 results: a few bf16 ulps (2^-8 each) of the largest value. The delta
# rule's kernels work on a float32 state against float32 lowerings at
# `HIGHEST`: one bf16 pass over the state reads 2e-3 here.
PARITY_RTOL = 0.03
F32_PARITY_RTOL = 1e-4


def check_parity(errors: dict) -> None:
    allowed = {k: F32_PARITY_RTOL if k.startswith("gdn_") else PARITY_RTOL for k in errors}
    bad = {k: v for k, v in errors.items() if not v <= allowed[k]}
    if bad:
        raise RuntimeError(
            f"kernels disagree with their reference lowering: {bad} (allowed {allowed})"
        )


def serve_phase(config, *, requests, engine_kwargs: dict, seed: int) -> dict:
    """Mixed-length requests through `serving.Engine` on int8 weights, one of
    them checked against `Generator`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig, Generator
    from accelerate_tpu.models import llama
    from accelerate_tpu.ops.int8 import with_int8_compute
    from accelerate_tpu.utils.quantization import quantized_nbytes

    device = jax.devices()[0]
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_int8_params(jax.random.PRNGKey(seed), config))
    init_s = time.perf_counter() - t0

    apply_fn = with_int8_compute(lambda p, t, c: llama.forward_with_cache(p, t, c, config))

    def init_cache_fn(batch, max_len):
        return llama.init_cache(config, batch, max_len)

    engine = serving.Engine(apply_fn, init_cache_fn, params, GenerationConfig(), **engine_kwargs)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, config.vocab_size, (n,)).astype(np.int32) for n, _ in requests]

    def trace():
        return [
            serving.Request(prompt=p, max_new_tokens=new, rid=i, seed=i)
            for i, (p, (_, new)) in enumerate(zip(prompts, requests))
        ]

    def serve_once():
        t0 = time.perf_counter()
        done = {c.rid: c for c in engine.serve(trace())}
        return done, time.perf_counter() - t0

    first, first_s = serve_once()  # compiles decode + one prefill per bucket
    # The same trace again, compiled: now every prompt is in the prefix cache,
    # so all but its last token is copied, not computed. In bf16 that is
    # another rounding of the same math, and near-ties among 128k random
    # logits may flip: where each request first differs is reported, and
    # only the budgets are held to.
    again, again_s = serve_once()
    for rid, (_, new) in enumerate(requests):
        for c in (first[rid], again[rid]):
            if c.n_new != new or c.finish_reason != "length":
                raise RuntimeError(
                    f"request {rid} returned {c.n_new}/{new} tokens ({c.finish_reason})"
                )
            if c.tokens.min() < 0 or c.tokens.max() >= config.vocab_size:
                raise RuntimeError(f"request {rid} produced tokens outside the vocabulary")
    differs = [first[rid].tokens != again[rid].tokens for rid in range(len(requests))]
    again_first_differs = [int(np.argmax(d)) if d.any() else None for d in differs]

    # Reference 1: the same prompt alone through the fixed-batch generator.
    n_prompt, n_new = requests[0]
    served = first[0].tokens
    generator = Generator(apply_fn, init_cache_fn, GenerationConfig(max_new_tokens=n_new))
    t0 = time.perf_counter()
    solo = np.asarray(generator(params, jnp.asarray(prompts[0][None])))[0, n_prompt:]
    solo_s = time.perf_counter() - t0
    equal = n_new if np.array_equal(served, solo) else int(np.argmax(served != solo))

    # Reference 2: one cache-free forward over prompt + served tokens. Every
    # served token must be the argmax of the logits before it — or tie with
    # it: engine (a batch of slots), generator (one row) and this forward
    # (one pass) round the same bf16 math at different points, and among
    # 128k logits of a random model the top two are often one ulp apart. So
    # where engine and generator part, both their tokens must tie here too.
    forward = jax.jit(with_int8_compute(lambda p, t: llama.forward(p, t, config)))
    teacher = jnp.asarray(np.concatenate([prompts[0], served])[None])
    # Row i holds the logits after prompt + served[:i] (the last row, after
    # the last served token, predicts nothing that was served).
    logits = forward(params, teacher)[0, n_prompt - 1 : -1].astype(jnp.float32)
    top = jnp.max(logits, axis=-1)

    def short_of_top(tokens):
        chosen = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], axis=-1)[:, 0]
        return np.asarray((top - chosen) / jnp.abs(top))

    gap = short_of_top(served)
    if gap.max() > ARGMAX_RTOL or gap.mean() > ARGMAX_MEAN_RTOL:
        at = int(np.argmax(gap))
        raise RuntimeError(
            f"served tokens are not the reference argmax: token {at} of {n_new} falls "
            f"{gap[at]:.3f} of the top logit short of it (allowed {ARGMAX_RTOL}), the {n_new} "
            f"fall {gap.mean():.4f} short on average (allowed {ARGMAX_MEAN_RTOL})"
        )
    if equal < n_new:
        solo_gap = float(short_of_top(np.where(np.arange(n_new) == equal, solo, served))[equal])
        if solo_gap > ARGMAX_RTOL:
            raise RuntimeError(
                f"engine and Generator disagree from token {equal} of {n_new} and it is "
                f"no tie: {served[equal]} vs {solo[equal]}, {solo_gap:.3f} of the top logit apart"
            )

    # Which path the decode step takes: lower the engine's own jitted step.
    n = engine.n_slots
    kv = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), engine._kv)
    t0 = time.perf_counter()
    decode_text = engine._decode.lower(
        params,
        jax.device_put(np.zeros((n,), np.int32), device),
        np.zeros((n,), np.int32),
        kv,
        np.zeros((n,), np.uint32),
        np.zeros((n,), np.int32),
    ).compile().as_text()
    decode_compile_s = time.perf_counter() - t0
    stats = dict(engine.stats)
    return {
        "params": config.param_count(),
        "weight_bytes": quantized_nbytes(params),
        "init_s": round(init_s, 2),
        "serve_first_s": round(first_s, 2),
        "serve_again_s": round(again_s, 2),
        "generator_s": round(solo_s, 2),
        "requests": len(requests),
        "generated_tokens": sum(new for _, new in requests),
        "decode_steps": stats["decode_steps"],
        "prefill_chunks": stats["prefill_chunks"],
        "prefix_hits": stats["prefix_hits"],
        "again_first_differs_at": again_first_differs,
        "decode_compiles": engine._decode._cache_size(),
        "prefill_compiles": engine._prefill._cache_size(),
        "equal_to_generator": f"{equal} of {n_new}" + ("" if equal == n_new else ", then a tie"),
        "reference_argmax_or_tie": f"{n_new} of {n_new}",
        "worst_short_of_top_logit": round(float(gap.max()), 5),
        "mean_short_of_top_logit": round(float(gap.mean()), 5),
        "decode_compile_s": round(decode_compile_s, 2),
        "decode_tpu_custom_calls": decode_text.count("tpu_custom_call"),
        "peak_bytes_in_use": _peak_bytes(device),
    }


def sharded_phase(config, *, seed: int, devices, **size) -> dict:
    """The train step under fsdp=2 x tensor=2 on ``devices`` against the
    same step on the first device alone (`train_phase`): same seed, same
    batch, same size."""
    import accelerate_tpu as atx
    from accelerate_tpu.parallel.tp import get_tp_plan

    one = train_phase(config, seed=seed, **size)
    out, text = _train(
        config,
        atx.MeshConfig(fsdp=2, tensor=2, devices=list(devices)),
        seed=seed,
        sharding_rules=get_tp_plan("llama"),
        strategy="HYBRID",
        **size,
    )
    shard_bytes = out["w_gate_shard_bytes"]
    if (
        len(shard_bytes) < len(devices)
        or len(set(shard_bytes.values())) != 1
        or sum(shard_bytes.values()) != out["w_gate_bytes"]
    ):
        raise RuntimeError(
            f"w_gate ({out['w_gate_bytes']} bytes) is not split evenly over "
            f"{len(devices)} devices: {shard_bytes}"
        )
    collectives = sorted(set(_COLLECTIVE.findall(text)))
    if not collectives:
        raise RuntimeError("the sharded step compiled with no collective")
    for a, b in zip(one["losses"], out["losses"]):
        if abs(a - b) > SHARDED_RTOL * abs(a):
            raise RuntimeError(
                f"sharded loss {b} differs from the one-device loss {a} by more "
                f"than {SHARDED_RTOL:.0%}: {one['losses']} vs {out['losses']}"
            )
    return {
        "mesh": {"fsdp": 2, "tensor": 2},
        "one_device_losses": one["losses"],
        "sharded_losses": out["losses"],
        "rtol": SHARDED_RTOL,
        "w_gate_bytes": out["w_gate_bytes"],
        "w_gate_shard_bytes": shard_bytes,
        "collectives": collectives,
        "tpu_custom_calls": out["tpu_custom_calls"],
        "compile_s": out["compile_s"],
        "one_device_compile_s": one["compile_s"],
        "one_device_step_s": one["step_s"],
        "sharded_step_s": out["step_s"],
        "peak_bytes_in_use": {str(d): _peak_bytes(d) for d in devices},
    }


def _train_config(cuts: dict):
    from accelerate_tpu.models import llama

    return llama.LlamaConfig.llama3_8b(
        n_layers=cuts["n_layers"],
        max_seq_len=cuts["seq_len"],
        remat=True,
        remat_policy="attn_and_outputs",
        attention_impl="flash",
        loss_chunk_size=512,
    )


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU (JAX reports {devices[0].platform!r}); nothing was run",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs {args.chips} devices, JAX reports {len(devices)}",
            file=sys.stderr,
        )
        return 2

    from accelerate_tpu import native
    from accelerate_tpu.models import llama
    from accelerate_tpu.native.pallas import kernel_status
    from accelerate_tpu.state import configure_compile_cache

    t_start = time.perf_counter()
    cache_dir = configure_compile_cache()
    kernels = {row["kernel"]: row["mode"] for row in kernel_status()}
    _say(
        "start",
        device_kind=devices[0].device_kind,
        devices=len(devices),
        jax=jax.__version__,
        compile_cache_dir=cache_dir,
        kernel_status=kernels,
        native_host_loader="loaded" if native.native_available() else f"not loaded: {native.native_error()}",
    )
    full = llama.LlamaConfig.llama3_8b()

    def say_cuts(cuts: dict, why: str) -> None:
        _say(
            "cut",
            n_layers=f"{cuts['n_layers']} of {full.n_layers}",
            seq_len=f"{cuts['seq_len']} of {full.max_seq_len}",
            batch_size=cuts["batch_size"],
            why=why,
        )

    if args.chips == 4:
        cuts = SHARDED_CUTS
        say_cuts(cuts, "the one-device comparison must fit one 16 GB chip")
        out = sharded_phase(
            _train_config(cuts), batch_size=cuts["batch_size"], seq_len=cuts["seq_len"],
            steps=TRAIN_STEPS, seed=args.seed, devices=devices[:4],
        )
        _say("sharded", **out)
        if not out["tpu_custom_calls"]:
            raise RuntimeError("the sharded train step compiled with no Pallas kernel")
    else:
        cuts = TRAIN_CUTS
        say_cuts(
            cuts,
            "bf16 weights + gradients + saved activations on one 16 GB chip; "
            "widths and vocabulary uncut",
        )
        errors = kernel_parity_phase(
            seq_len=cuts["seq_len"], cache_len=SERVE_ENGINE["max_len"],
            head_dim=full.resolved_head_dim, seed=args.seed,
        )
        _say("kernel_parity", error_relative_to_largest_value=errors, allowed=PARITY_RTOL)
        check_parity(errors)
        out = train_phase(
            _train_config(cuts), batch_size=cuts["batch_size"], seq_len=cuts["seq_len"],
            steps=TRAIN_STEPS, seed=args.seed,
        )
        _say("train", **out)
        if not out["tpu_custom_calls"]:
            raise RuntimeError("the train step compiled with no Pallas kernel (flash attention)")

        serve_config = llama.LlamaConfig.llama3_8b(max_seq_len=SERVE_ENGINE["max_len"])
        _say("cut", n_layers=f"{serve_config.n_layers} of {full.n_layers}",
             max_len=f"{SERVE_ENGINE['max_len']} of {full.max_seq_len}",
             why="none in depth (int8 blocks fit); slot length sized to the requests")
        out = serve_phase(
            serve_config, requests=SERVE_REQUESTS, engine_kwargs=SERVE_ENGINE, seed=args.seed
        )
        _say("serve", **out)
        if kernels["decode_attn"] == "compiled" and not out["decode_tpu_custom_calls"]:
            raise RuntimeError("decode_attn reports compiled but the decode step holds no Pallas kernel")

    _say("done", seconds=round(time.perf_counter() - t_start, 1), claim=None)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": devices[0].platform,
                    "kind": devices[0].device_kind,
                    "count": len(devices),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
