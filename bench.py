"""Headline benchmark: Llama training-step MFU on the local chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
The reference publishes no training-throughput numbers (BASELINE.md — its perf
story defers to torch/NCCL); the driver-defined north star is >=45% MFU, so
``vs_baseline`` is value / 0.45.

Trains a ~450M-param Llama (bf16 compute, fp32 master params + adam
moments, remat) at seq 2048, then the extra phases. It measures the chip:
with no TPU attached it runs nothing and exits non-zero, a device kind with
no entry in the peaks table is an error, and a phase that raised makes the
exit code non-zero after the JSON line is printed.
"""

from __future__ import annotations

import json
import signal
import sys
import time

import jax
import numpy as np
import jax.numpy as jnp

# Partial results accumulate here; a timeout kill (SIGTERM) still emits one
# valid JSON line with whatever finished instead of losing the whole run
# (the 8B big-model phase makes the full bench ~20+ min).
_RESULT: dict = {}


def _emit_partial(signum, frame):  # pragma: no cover - signal path
    # One-shot: disarm first so a signal racing the normal final print can
    # never produce a second JSON line (the output contract is ONE line).
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    try:
        if _RESULT:
            _RESULT.setdefault("partial", True)
            print(json.dumps(_RESULT), flush=True)
    finally:
        # sys.exit in finally: even a BrokenPipeError from the print must
        # not fall back into the interrupted frame's `except Exception`
        # (which would swallow the shutdown and keep the bench running).
        sys.exit(1)


# bf16 peak FLOPs per chip by device kind (dense matmul).
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def _phase_snapshot(phase: str) -> None:
    """Drop a per-phase registry snapshot under ATX_METRICS_DIR (no-op when
    unset): `<dir>/<phase>/metrics_0.json`, the same exchange format the
    fleet /metrics endpoint merges — post-hoc phase attribution without
    parsing the JSON line (docs/observability.md)."""
    import os

    root = os.environ.get("ATX_METRICS_DIR", "")
    if not root:
        return
    try:
        from accelerate_tpu import telemetry

        telemetry.write_snapshot(os.path.join(root, phase), process_index=0)
    except Exception:
        pass  # telemetry must never sink a bench run


def _peak_flops(device: jax.Device) -> float:
    kind = device.device_kind
    for name, flops in _PEAK_FLOPS.items():
        if kind.startswith(name) or name.startswith(kind):
            return flops
    raise ValueError(
        f"no peak FLOP/s for device kind {kind!r}: add it to _PEAK_FLOPS"
    )


def main() -> int:
    import optax

    import accelerate_tpu as atx
    from accelerate_tpu.models import llama
    from accelerate_tpu.state import configure_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(
            f"bench.py measures the chip and found none (platform "
            f"{device.platform!r}, kind {device.device_kind!r}): nothing was run",
            file=sys.stderr,
        )
        return 2
    peak = _peak_flops(device)
    configure_compile_cache()
    # Installed here, not at import: tests import this module for
    # `compare_results`, and a process-wide SIGTERM handler is not theirs.
    signal.signal(signal.SIGTERM, _emit_partial)
    # head_dim 128 (not 64): the MXU contracts 128 lanes per pass, so
    # h=64 attention dots run at half utilization — measured 37 vs 65
    # TF/s on v5e for the same FLOPs. Param count is unchanged.
    config = llama.LlamaConfig(
        vocab_size=32000,
        d_model=1024,
        n_layers=24,
        num_heads=8,
        num_kv_heads=4,
        head_dim=128,
        d_ff=4096,
        max_seq_len=2048,
        remat=True,
        # Measured on v5e: attn_and_outputs 448 ms/step vs block_outputs
        # 458 ms (saving the attention outputs skips the most expensive
        # recompute); "dots"/no-remat exceed HBM at this size.
        remat_policy="attn_and_outputs",
        attention_impl="flash",
    )
    batch_size, seq = 8, 2048
    steps, warmup = 10, 3

    acc = atx.Accelerator(mixed_precision="bf16", seed=0, max_grad_norm=1.0)
    state = acc.create_train_state(lambda r: llama.init(r, config), optax.adamw(3e-4))
    step = acc.make_train_step(lambda p, b, r: llama.loss_fn(p, b, config, r))
    batch = {
        "input_ids": jax.random.randint(
            jax.random.PRNGKey(1), (batch_size, seq), 0, config.vocab_size, jnp.int32
        )
    }
    batch = jax.device_put(batch)

    state, metrics, dt, fetch_latency = _timed_steps(step, state, batch, steps, warmup)

    tokens_per_step = batch_size * (seq - 1)  # loss_fn shifts by one
    tokens_per_sec = tokens_per_step * steps / dt
    n_params = config.param_count()
    # Training FLOPs/token: 6N for matmuls + causal attention term (fwd+bwd).
    attn_flops = 6.0 * config.n_layers * config.d_model * seq  # 12*L*D*S/2 (causal)
    flops_per_token = 6.0 * n_params + attn_flops
    model_flops_per_sec = tokens_per_sec * flops_per_token
    mfu = model_flops_per_sec / peak

    # Free the Llama state/opt buffers before the BERT measurement — both
    # would not fit HBM together.
    final_loss = round(float(metrics["loss"]), 4)
    _RESULT.update(
        {
            "metric": "llama_train_mfu",
            "value": round(mfu, 4),
            "unit": "MFU",
            "vs_baseline": round(mfu / 0.45, 4),
            "tokens_per_sec": round(tokens_per_sec, 1),
            "step_time_ms": round(1000 * dt / steps, 2),
            "params": n_params,
            "device": getattr(device, "device_kind", str(device)),
            "loss": final_loss,
        }
    )
    # Runtime-telemetry view of the same loop (ATX_METRICS, default on):
    # the jitted call's host wall time exposes a host-bound loop the external
    # wall clock can't see, and train_hfu is XLA's own cost analysis of the
    # compiled step (recomputed operations included) beside the hand-computed
    # MFU above.
    stats = getattr(step, "step_stats", None)
    if stats is not None:
        latest = stats.latest()
        _RESULT["train_dispatch_ms"] = round(latest["train_dispatch_ms"], 2)
        _RESULT["train_hfu"] = round(latest["train_hfu"], 4)
        _RESULT["train_compiles"] = int(latest["train_compiles"])
    try:
        # Static twin of the measured series (docs/performance.md, "perf
        # campaign"): the ATX601 roofline over the SAME compiled step, so
        # `--compare` can tell "the program got worse" (bound moved) from
        # "the run got slower" (bound unchanged, measured MFU dropped).
        _RESULT.update(_static_perf_series(step, state, batch, config))
    except Exception as e:
        _RESULT["static_perf_error"] = f"{type(e).__name__}: {e}"[:200]
    _phase_snapshot("train")
    state, batch, metrics = acc.free_memory(state, batch, metrics)
    extra_benches = [
        ("bert", lambda: _bench_bert(fetch_latency)),
        # The engine-vs-blocking comparison is the before/after for the
        # whole transfer-bound family (bigmodel_8b_load_s,
        # hostoffload_adamw_mfu, overram decode).
        ("transfer", _bench_transfer),
        ("longctx", _bench_long_context),
        ("generate", lambda: _bench_generate(config)),
        ("serve", lambda: _bench_serve(config)),
        ("specdecode", lambda: _bench_specdecode(config)),
        ("int8kv", lambda: _bench_int8_kv(config)),
        ("kernels", lambda: _bench_kernels(config)),
        ("int8mm", _bench_int8_matmul),
        ("fp8", _bench_fp8),
        ("llama2b", lambda: _bench_llama2b(fetch_latency)),
        ("hostoffload", lambda: _bench_hostoffload_adamw(fetch_latency)),
        ("vit", lambda: _bench_vit(fetch_latency)),
        ("bigmodel", _bench_bigmodel),
        ("overram", _bench_overram),
    ]
    for name, fn in extra_benches:
        try:
            _RESULT.update(fn())
        except Exception as e:  # the line keeps what finished; the exit code says a phase did not
            _RESULT[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
        _phase_snapshot(name)

    signal.signal(signal.SIGTERM, signal.SIG_DFL)  # past the point of partials
    print(json.dumps(_RESULT))
    failed = sorted(k for k in _RESULT if k.endswith("_error"))
    if failed:
        print(f"bench.py: phases raised: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


def _static_perf_series(step, state, batch, config) -> dict:
    """ATX601/ATX70x statically-derived series next to the measured ones:
    lower + compile the already-built train step (no extra steps run),
    bound it against the local chip's roofline spec, sweep the scheduled
    HLO for the peak-HBM timeline, and solve the serving capacity planner
    for this config on this chip. Emitted per run so `bench.py --compare`
    ratchets them alongside the measured MFU."""
    from accelerate_tpu.analysis import capacity, memory, roofline
    from accelerate_tpu.models import llama

    text = step.lower(state, batch).compile().as_text()
    spec = roofline.chip_spec_for()
    res = roofline.analyze_hlo(text, spec)
    exposed = roofline.find_exposed_collectives(text, spec)
    out = {
        "train_static_mfu_bound": round(res.static_mfu_bound, 4),
        "train_exposed_comms_mib": round(sum(e.bytes for e in exposed) / 2**20, 3),
        "train_padding_waste_frac": round(res.padding_waste_fraction, 4),
    }
    try:
        timeline = memory.build_timeline(text)
        out["train_peak_hbm_mib"] = round(timeline.peak_bytes / 2**20, 1)
    except Exception:
        pass  # the roofline series above still land
    try:
        # Serving twin: one abstract KV slot of this config + the bf16
        # weights it would serve with — the planner needs only byte counts.
        slot_kv = jax.eval_shape(lambda: llama.init_cache(config, 1, config.max_seq_len))
        weights = 2 * config.param_count()  # bf16 serving weights
        plan = capacity.plan_capacity(
            chip=spec,
            weights_bytes=weights,
            kv_bytes_per_slot=capacity.tree_bytes(slot_kv),
            n_slots=1,
            max_len=config.max_seq_len,
        )
        out["serve_static_max_slots"] = int(plan.max_slots)
    except Exception:
        pass
    return out


def _timed_steps(step, state, batch, steps: int, warmup: int, fetch_latency: float | None = None):
    """Warm up, then time `steps` train steps.

    The barrier is a device->host scalar fetch; its round trip is measured
    once and subtracted from the timed loop.
    Returns (state, metrics, dt_seconds, fetch_latency).
    """
    for _ in range(warmup):
        state, metrics = step(state, batch)
    float(metrics["loss"])
    if fetch_latency is None:
        t0 = time.perf_counter()
        float(metrics["loss"])
        fetch_latency = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    float(metrics["loss"])
    dt = max(time.perf_counter() - t0 - fetch_latency, 1e-9)
    return state, metrics, dt, fetch_latency


def _bench_transfer() -> dict:
    """H2D roofline, blocking vs the async chunked engine
    (`parallel/transfer.py`): the same host buffer moved once as a single
    whole-leaf `jax.device_put` (the pre-engine code path) and once through
    `TransferEngine.put` (chunks issued concurrently from the worker pool).
    `transfer_mib_s` over `transfer_blocking_mib_s` is the
    dispatch-serialization win every transfer-bound path (8B load, over-RAM
    decode, disk-offloaded AdamW) inherits."""
    from accelerate_tpu.parallel.transfer import TransferEngine

    n_mib = 256
    x = np.empty((n_mib, 1 << 20), np.int8)
    x[:] = np.arange(n_mib, dtype=np.int8)[:, None]

    def barrier(d) -> None:
        float(jnp.sum(d[0, :8].astype(jnp.float32)))  # scalar fetch = barrier

    # Warm both paths (compile the engine's fold, open the link).
    barrier(jax.device_put(x[:1]))
    engine = TransferEngine()
    barrier(engine.put(x[:2]).result())

    def timed(fn) -> float:
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            barrier(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    dt_block = timed(lambda: jax.device_put(x))
    dt_engine = timed(lambda: engine.put(x).result())
    return {
        "transfer_mib_s": round(n_mib / dt_engine, 1),
        "transfer_blocking_mib_s": round(n_mib / dt_block, 1),
        "transfer_speedup": round(dt_block / dt_engine, 3),
        "transfer_chunk_mib": engine.chunk_bytes >> 20,
        "transfer_workers": engine.workers,
    }


def _bench_long_context() -> dict:
    """Flash-attention fwd+bwd throughput at 32k context (the blocked-KV
    kernel path; the resident-KV path cannot compile at this length)."""
    from accelerate_tpu.ops.flash_attention import flash_attention

    B, S, H, K, h = 1, 32768, 8, 4, 128
    k0 = jax.random.PRNGKey(9)
    q = jax.random.normal(k0, (B, S, H, h), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    g = step(q, k, v)
    float(jnp.sum(g[0].astype(jnp.float32)))  # barrier
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        g = step(q, k, v)
    float(jnp.sum(g[0].astype(jnp.float32)))
    dt = (time.perf_counter() - t0) / reps
    # fwd 4*B*H*S^2*h/2 (causal) + bwd 2.5x fwd
    flops = 3.5 * 4 * B * H * S * S * h / 2
    return {
        "longctx_seq": S,
        "longctx_step_ms": round(dt * 1000, 1),
        "longctx_tflops": round(flops / dt / 1e12, 1),
    }


def _bench_fp8() -> dict:
    """fp8-vs-bf16 matmul microbench: measures whether THIS
    chip's MXU gives fp8 a real speedup, or only upcasts (v5e). The config
    Q&A points users at this field before they pick fp8."""
    from accelerate_tpu.ops import fp8 as _fp8

    N = 4096
    k0 = jax.random.PRNGKey(11)
    x = jax.random.normal(k0, (N, N), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(k0, 1), (N, N), jnp.bfloat16)

    def bf16_mm(x, w):
        return _fp8.matmul_einsum("ij,jk->ik", x, w)

    def fp8_mm(x, w):
        with _fp8.fp8_matmuls(True):
            return _fp8.matmul_einsum("ij,jk->ik", x, w)

    def timed(jitted) -> float:
        out = jitted(x, w)
        float(jnp.sum(out.astype(jnp.float32)))  # warm + barrier
        reps = 20
        t0 = time.perf_counter()
        for _ in range(reps):
            out = jitted(x, w)
        float(jnp.sum(out.astype(jnp.float32)))
        return (time.perf_counter() - t0) / reps

    bf16_jit, fp8_jit = jax.jit(bf16_mm), jax.jit(fp8_mm)
    dt_bf16 = min(timed(bf16_jit) for _ in range(2))
    dt_fp8 = min(timed(fp8_jit) for _ in range(2))
    flops = 2.0 * N * N * N
    # Feed the launcher's lose-lose gate (launch refuses fp8 on device kinds
    # with measured speedup <= 1 unless --force_fp8).
    try:
        from accelerate_tpu.utils import fp8_telemetry

        fp8_telemetry.record(jax.devices()[0].device_kind, dt_bf16 / dt_fp8)
    except Exception:
        pass
    return {
        "bf16_matmul_tflops": round(flops / dt_bf16 / 1e12, 1),
        "fp8_matmul_tflops": round(flops / dt_fp8 / 1e12, 1),
        # > 1.0 means fp8 actually pays on this chip.
        "fp8_matmul_speedup": round(dt_bf16 / dt_fp8, 3),
    }


def _bench_int8_matmul() -> dict:
    """int8×int8→int32 vs bf16 MXU rate (`ops/int8.py`).

    The v5e's int8 MXU runs ~2× the bf16 rate; this is the lever fp8
    cannot pull on this chip (no native fp8 MXU).
    Times a jitted fori_loop at two iteration counts and divides the
    MARGINAL times, so the fixed per-execution latency cancels."""
    N, NB = 4096, 4
    kx, kw = jax.random.split(jax.random.PRNGKey(13))
    x8 = jax.random.randint(kx, (N, N), -127, 127, jnp.int8)
    w8s = jax.random.randint(kw, (NB, N, N), -127, 127, jnp.int8)
    xb = jax.random.normal(kx, (N, N), jnp.bfloat16)
    wbs = jax.random.normal(kw, (NB, N, N), jnp.bfloat16)

    def make(dtype_out, iters):
        @jax.jit
        def loop(a, bs):
            def body(i, acc):
                # Loop-variant operand: the dot cannot be hoisted.
                bb = jax.lax.dynamic_index_in_dim(bs, i % NB, 0, keepdims=False)
                return acc + jax.lax.dot(a, bb, preferred_element_type=dtype_out)
            return jnp.sum(
                jax.lax.fori_loop(0, iters, body, jnp.zeros((N, N), dtype_out))
            )
        return loop

    def run(fn, a, b, reps=3):
        float(fn(a, b))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(fn(a, b))  # scalar fetch = the only reliable barrier here
            best = min(best, time.perf_counter() - t0)
        return best

    small, big = 16, 96
    marginal = {}
    for name, xv, wv, dt_ in (("bf16", xb, wbs, jnp.float32), ("int8", x8, w8s, jnp.int32)):
        t_small = run(make(dt_, small), xv, wv)
        t_big = run(make(dt_, big), xv, wv)
        marginal[name] = max(t_big - t_small, 1e-9) / (big - small)
    flops = 2.0 * N * N * N
    return {
        "int8_matmul_tops": round(flops / marginal["int8"] / 1e12, 1),
        "int8_mxu_bf16_tflops": round(flops / marginal["bf16"] / 1e12, 1),
        # > 1.0 means the int8 MXU path pays on this chip (v5e: ~1.9).
        "int8_matmul_speedup": round(marginal["bf16"] / marginal["int8"], 3),
    }


def _bench_generate(config) -> dict:
    """KV-cache decode throughput on the headline model (the
    big-model-inference `generate()` config BASELINE.md tracks): bf16
    params, batch 8, prefill 128, steady-state decode tokens/sec.

    Timed as the DIFFERENCE between a long and a short generation, which
    cancels the prefill forward and the device->host fetch round trip from
    the measurement (the same concern `_timed_steps` handles; only the extra
    decode steps remain)."""
    import dataclasses

    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import llama

    gen_config = dataclasses.replace(
        config,
        remat=False,
        attention_impl="dot",  # decode T=1 steps; flash needs block-sized S
    )
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        llama.init(jax.random.PRNGKey(3), gen_config),
    )
    B, prompt_len = 8, 128
    short, long = 16, 80
    prompt = jax.random.randint(
        jax.random.PRNGKey(4), (B, prompt_len), 0, gen_config.vocab_size, jnp.int32
    )
    gcfg_short = GenerationConfig(max_new_tokens=short)
    gcfg_long = GenerationConfig(max_new_tokens=long)

    def run(gcfg) -> float:
        t0 = time.perf_counter()
        out = llama.generate(params, prompt, gen_config, generation_config=gcfg)
        int(out[0, -1])  # fetch barrier
        return time.perf_counter() - t0

    run(gcfg_short), run(gcfg_long)  # compile both loop lengths
    dt_short = min(run(gcfg_short) for _ in range(2))
    dt_long = min(run(gcfg_long) for _ in range(2))
    decode_dt = max(dt_long - dt_short, 1e-9)
    n_tokens = long - short
    return {
        "decode_tokens_per_sec": round(B * n_tokens / decode_dt, 1),
        "decode_ms_per_token": round(1000 * decode_dt / n_tokens, 3),
    }


def _bench_int8_kv(config) -> dict:
    """int8 KV cache at long context (beyond-reference: per-token-scale
    quantized cache, `models/llama.py:init_cache`): at 16k context the
    bf16 cache (~1.6 GiB) outweighs the 443M model's weights ~2:1, so
    halving cache bytes moves the B=1 decode roofline directly. Prefill
    runs in 2k chunks (the dot-attention score block stays bounded), then
    a timed single-token decode loop."""
    import dataclasses

    from accelerate_tpu.models import llama

    S_ctx, chunk, decode_n = 16384, 2048, 48
    gen_config = dataclasses.replace(
        config, remat=False, attention_impl="dot", max_seq_len=S_ctx + 128
    )
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), llama.init(jax.random.PRNGKey(3), gen_config)
    )
    prompt = jax.random.randint(
        jax.random.PRNGKey(6), (1, S_ctx), 0, gen_config.vocab_size, jnp.int32
    )

    # One jitted callable serves prefill chunks and 1-token decode: jit
    # specializes per input shape anyway.
    step_fn = jax.jit(
        lambda p, t, c: llama.forward_with_cache(p, t, c, gen_config),
        donate_argnums=(2,),
    )
    prefill = decode = step_fn

    out = {}
    rates = {}
    for label, dt in (("bf16", jnp.bfloat16), ("int8", jnp.int8)):
        cache = llama.init_cache(gen_config, 1, S_ctx + 128, dtype=dt)
        for i in range(S_ctx // chunk):
            logits, cache = prefill(params, prompt[:, i * chunk:(i + 1) * chunk], cache)
        tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        for _ in range(4):  # compile + warm
            logits, cache = decode(params, tok, cache)
        int(jnp.argmax(logits[0, -1]))  # sync
        t0 = time.perf_counter()
        for _ in range(decode_n):
            logits, cache = decode(params, tok, cache)
        int(jnp.argmax(logits[0, -1]))  # fetch barrier
        dt_total = time.perf_counter() - t0
        rates[label] = decode_n / dt_total
        out[f"kv16k_decode_{label}_tokens_per_sec"] = round(rates[label], 1)
    out["kv16k_int8_speedup"] = round(rates["int8"] / rates["bf16"], 3)

    # Same int8 cache, flash-decode kernel pinned OFF, fresh function object
    # (fresh jit cache): isolates the kernel's contribution at 16k context.
    # The loop above runs under the default knobs (kernel on where TPU +
    # pallas), so rates["int8"] / off_rate is the on/off delta.
    from accelerate_tpu.native.pallas import force_kernels

    with force_kernels("off"):
        decode_off = jax.jit(
            lambda p, t, c: llama.forward_with_cache(p, t, c, gen_config),
            donate_argnums=(2,),
        )
        for _ in range(4):  # compile + warm
            logits, cache = decode_off(params, tok, cache)
        int(jnp.argmax(logits[0, -1]))
        t0 = time.perf_counter()
        for _ in range(decode_n):
            logits, cache = decode_off(params, tok, cache)
        int(jnp.argmax(logits[0, -1]))
        off_rate = decode_n / (time.perf_counter() - t0)
    out["kv16k_decode_int8_off_tokens_per_sec"] = round(off_rate, 1)
    out["kv16k_decode_kernel_speedup"] = round(rates["int8"] / off_rate, 3)
    return out


def _bench_kernels(config) -> dict:
    """Pallas kernel tier on/off deltas (`native/pallas/`): each hot path
    timed under ``force_kernels("on")`` vs ``"off"`` with fresh function
    objects per mode (the mode is read at trace time, so each gets its own
    jit cache). On CPU "on" resolves to the fallback and the ratios sit at
    ~1.0; on TPU these are the tier's headline numbers."""
    import dataclasses

    from accelerate_tpu.models import llama
    from accelerate_tpu.native.pallas import force_kernels
    from accelerate_tpu.ops import fp8 as _fp8
    from accelerate_tpu.parallel import host_offload

    out = {}

    # --- flash-decode attention: B=8 steady-state decode, on vs off.
    gen_config = dataclasses.replace(config, remat=False, attention_impl="dot")
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        llama.init(jax.random.PRNGKey(3), gen_config),
    )
    B, prompt_len, decode_n = 8, 256, 48
    prompt = jax.random.randint(
        jax.random.PRNGKey(4), (B, prompt_len), 0, gen_config.vocab_size, jnp.int32
    )

    def run_decode(mode: str) -> float:
        with force_kernels(mode):
            step = jax.jit(
                lambda p, t, c: llama.forward_with_cache(p, t, c, gen_config),
                donate_argnums=(2,),
            )
            cache = llama.init_cache(gen_config, B, prompt_len + decode_n + 8)
            logits, cache = step(params, prompt, cache)
            tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
            for _ in range(4):
                logits, cache = step(params, tok, cache)
            int(jnp.argmax(logits[0, -1]))  # sync
            t0 = time.perf_counter()
            for _ in range(decode_n):
                logits, cache = step(params, tok, cache)
            int(jnp.argmax(logits[0, -1]))  # fetch barrier
            return decode_n * B / (time.perf_counter() - t0)

    tps_on = run_decode("on")
    tps_off = run_decode("off")
    out["decode_kernel_tokens_per_sec"] = round(tps_on, 1)
    out["decode_kernel_off_tokens_per_sec"] = round(tps_off, 1)
    out["decode_kernel_speedup"] = round(tps_on / tps_off, 3)

    # --- fp8 contraction kernel: the 1.004 fp8_matmul_speedup target.
    N = 4096
    k0 = jax.random.PRNGKey(11)
    x = jax.random.normal(k0, (N, N), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(k0, 1), (N, N), jnp.bfloat16)

    def run_fp8(mode: str) -> float:
        with force_kernels(mode):

            def mm(x, w):
                with _fp8.fp8_matmuls(True):
                    return _fp8.matmul_einsum("ij,jk->ik", x, w)

            jitted = jax.jit(mm)
            o = jitted(x, w)
            float(jnp.sum(o.astype(jnp.float32)))  # warm + barrier
            reps = 20
            t0 = time.perf_counter()
            for _ in range(reps):
                o = jitted(x, w)
            float(jnp.sum(o.astype(jnp.float32)))
            return (time.perf_counter() - t0) / reps

    dt_off = min(run_fp8("off") for _ in range(2))
    dt_on = min(run_fp8("on") for _ in range(2))
    out["fp8_kernel_matmul_speedup"] = round(dt_off / dt_on, 3)

    # --- fused AdamW: one big leaf's worth of update, on vs off.
    n = 8 * 1024 * 1024
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    g, mu, nu, p = (
        jax.random.normal(k, (n,), jnp.float32) * s
        for k, s in zip(keys, (1e-3, 1e-3, 1e-6, 1.0))
    )
    nu = jnp.abs(nu)

    def run_adamw(mode: str) -> float:
        with force_kernels(mode):
            step = jax.jit(
                lambda g, mu, nu, p: host_offload._adamw_slice(
                    g, mu, nu, p, jnp.ones(()), 1e-4, 0.9, 0.999, 1e-8, 1e-4
                )
            )
            u, m2, n2 = step(g, mu, nu, p)
            float(jnp.sum(u))  # warm + barrier
            reps = 20
            t0 = time.perf_counter()
            for _ in range(reps):
                u, m2, n2 = step(g, mu, nu, p)
            float(jnp.sum(u))
            return (time.perf_counter() - t0) / reps

    ms_on = min(run_adamw("on") for _ in range(2)) * 1000
    ms_off = min(run_adamw("off") for _ in range(2)) * 1000
    out["fused_adamw_step_ms"] = round(ms_on, 3)
    out["fused_adamw_off_step_ms"] = round(ms_off, 3)
    out["fused_adamw_speedup"] = round(ms_off / max(ms_on, 1e-9), 3)
    return out


def _bench_serve(config) -> dict:
    """Continuous-batching serving engine (`serving.Engine`,
    docs/serving.md) on the headline decode model: a trace of 48
    mixed-length requests (prompts 32/64/128, budgets 24/48) served through
    the slot pool, vs the same request set run SEQUENTIALLY through batch-1
    `generate()` — the fixed-batch workflow the engine replaces. The
    ISSUE-3 acceptance bar is `serve_vs_b1_speedup >= 3`. Then a second
    pass replays Poisson arrivals at ~70% of the measured capacity on the
    wall clock for honest p50/p99 request + TTFT latency. Finally a
    shared-prefix trace (two 128-token system prompts) is served with the
    prefix cache on vs off: `serve_prefix_hit_rate`/`serve_prefill_saved`
    quantify the radix-tree KV reuse and the TTFT p50 pair shows the
    time-to-first-token win (ISSUE-6). A last router phase replays the
    same traces through the multi-replica front-end (`serving.Router`) at
    replicas=2 vs 1 (`serve_router_scaling_efficiency`, TTFT p99) and
    with prefix-affinity routing on vs off
    (`serve_router_affinity_hit_delta`) — judge the scaling on TPU
    (ISSUE-8)."""
    import dataclasses

    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig, Generator
    from accelerate_tpu.models import llama

    gen_config = dataclasses.replace(
        config, remat=False, attention_impl="dot", max_seq_len=512
    )
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        llama.init(jax.random.PRNGKey(3), gen_config),
    )
    apply_fn = lambda p, t, c: llama.forward_with_cache(p, t, c, gen_config)
    init_cache_fn = lambda b, m: llama.init_cache(gen_config, b, m)

    # Request mix from a small set of (prompt, budget) pairs so the b1
    # BASELINE compiles a bounded number of (shape, cache) specializations;
    # the engine itself needs no such care (that is the point: one decode
    # compile + one prefill compile per bucket, whatever the mix).
    prompt_lens, budgets, buckets = (32, 64, 128), (24, 48), (32, 64, 128)
    n_requests = 48
    rng = np.random.RandomState(7)
    arrivals = np.cumsum(rng.exponential(1.0, n_requests))  # rescaled later
    trace = [
        serving.Request(
            prompt=rng.randint(0, gen_config.vocab_size, (int(rng.choice(prompt_lens)),)).astype(np.int32),
            max_new_tokens=int(rng.choice(budgets)),
            rid=i,
            seed=i,
            arrival=float(arrivals[i]),
        )
        for i in range(n_requests)
    ]

    def fresh_engine(prefix_cache: bool = False, max_len: int | None = None):
        return serving.Engine(
            apply_fn,
            init_cache_fn,
            params,
            GenerationConfig(),
            buckets=buckets,
            max_len=max_len or (max(prompt_lens) + max(budgets)),
            decode_block=8,
            prefix_cache=prefix_cache,
            prefix_cache_rows=8 if prefix_cache else None,
        )

    engine = fresh_engine()
    # Warm every compile the trace will hit: one request per bucket.
    engine.serve(
        serving.Request(
            prompt=rng.randint(0, gen_config.vocab_size, (S,)).astype(np.int32),
            max_new_tokens=2,
            rid=1000 + S,
        )
        for S in prompt_lens
    )
    t0 = time.perf_counter()
    completions = engine.serve(trace)
    serve_wall = max(time.perf_counter() - t0, 1e-9)
    total_new = sum(c.n_new for c in completions)
    serve_tps = total_new / serve_wall

    # Sequential b1 baseline over a 12-request subset covering every
    # (prompt, budget) pair; first pass compiles, second is timed.
    subset = trace[:12]
    gens: dict[int, Generator] = {}
    for timed in (False, True):
        t0 = time.perf_counter()
        for r in subset:
            g = gens.setdefault(
                r.max_new_tokens, Generator(
                    apply_fn, init_cache_fn,
                    GenerationConfig(max_new_tokens=r.max_new_tokens),
                )
            )
            out = g(params, jnp.asarray(r.prompt[None]))
            int(out[0, -1])  # fetch barrier
        if timed:
            b1_wall = max(time.perf_counter() - t0, 1e-9)
    b1_tps = sum(r.max_new_tokens for r in subset) / b1_wall

    # Latency pass: Poisson arrivals at ~70% of measured capacity, wall
    # clock honoured, so p50/p99 include real queueing.
    rate = 0.7 * n_requests / serve_wall
    lat_engine = fresh_engine()
    lat_trace = [
        dataclasses.replace(r, arrival=float(a / arrivals[-1] * n_requests / rate))
        for r, a in zip(trace, arrivals)
    ]
    lat = lat_engine.serve(lat_trace, realtime=True)
    lat_ms = sorted(1e3 * (c.finished_at - c.submitted_at) for c in lat)
    ttft_ms = sorted(1e3 * (c.first_token_at - c.submitted_at) for c in lat)
    pick = lambda xs, q: xs[min(len(xs) - 1, int(q * len(xs)))]

    # Prefix-cache phase: 32 requests behind two 128-token system prompts
    # with short unique tails, replayed (as-fast-as-possible) through a
    # cache-on and a cache-off engine. TTFT here is the queue+prefill time
    # per request; with ~94% of each prompt's prefill skipped on a hit the
    # cache-on engine should cut it well below the cache-off run.
    prefix_trace = serving.shared_prefix_trace(
        32,
        1e9,  # all requests queued up-front: measures prefill work, not arrivals
        vocab_size=gen_config.vocab_size,
        n_prefixes=2,
        prefix_len=128,
        tail_lens=(8, 32),
        new_tokens=(8, 24),
        seed=11,
    )
    prefix_max_len = 128 + 32 + 24
    prefix_results = {}
    for label, on in (("prefix", True), ("nocache", False)):
        eng = fresh_engine(prefix_cache=on, max_len=prefix_max_len)
        # Warm compiles (prefill buckets + decode) outside the timed pass.
        eng.serve(
            serving.Request(
                prompt=rng.randint(0, gen_config.vocab_size, (S,)).astype(np.int32),
                max_new_tokens=2,
                rid=2000 + S,
            )
            for S in buckets
        )
        done = eng.serve(prefix_trace)
        tt = sorted(1e3 * (c.first_token_at - c.submitted_at) for c in done)
        prefix_results[label] = (eng, pick(tt, 0.50), pick(tt, 0.99))
    prefix_eng = prefix_results["prefix"][0]
    pm = prefix_eng.prefix_metrics()

    # Router phase (ISSUE-8): the same Poisson trace through the
    # multi-replica front-end at replicas=1 vs replicas=2 for aggregate
    # tokens/sec + TTFT p99 scaling (each replica engine is warmed
    # separately; on a shared-CPU host the two replica loops contend for
    # the same cores, so judge `serve_router_scaling_efficiency` on TPU —
    # this lane smoke-checks the path). Then the shared-prefix trace with
    # prefix-affinity routing on vs off: the fleet hit-rate delta is what
    # cache-aware placement buys over pure least-loaded.
    def warm_router_engines(n: int, **kw) -> list:
        engines = []
        for _ in range(n):
            e = fresh_engine(**kw)
            e.serve(
                serving.Request(
                    prompt=rng.randint(
                        0, gen_config.vocab_size, (S,)
                    ).astype(np.int32),
                    max_new_tokens=2,
                    rid=3000 + S,
                )
                for S in buckets
            )
            engines.append(e)
        return engines

    router_tps, router_ttft_p99 = {}, {}
    for n_rep in (1, 2):
        with serving.Router(warm_router_engines(n_rep)) as router:
            t0 = time.perf_counter()
            done = router.serve([dataclasses.replace(r) for r in trace])
            wall = max(time.perf_counter() - t0, 1e-9)
        router_tps[n_rep] = sum(c.n_new for c in done) / wall
        tt = sorted(1e3 * (c.first_token_at - c.submitted_at) for c in done)
        router_ttft_p99[n_rep] = pick(tt, 0.99)

    affinity_hit_rate = {}
    for label, policy in (("affinity", "prefix"), ("noaffinity", "least-loaded")):
        engines = warm_router_engines(
            2, prefix_cache=True, max_len=prefix_max_len
        )
        with serving.Router(engines, affinity=policy) as router:
            router.serve([dataclasses.replace(r) for r in prefix_trace])
        hits = sum(e.stats["prefix_hits"] for e in engines)
        lookups = sum(e.prefix_cache.stats["lookups"] for e in engines)
        affinity_hit_rate[label] = hits / max(lookups, 1)

    return {
        "serve_requests": n_requests,
        "serve_tokens_per_sec": round(serve_tps, 1),
        "serve_b1_tokens_per_sec": round(b1_tps, 1),
        "serve_vs_b1_speedup": round(serve_tps / b1_tps, 2),
        "serve_p50_ms": round(pick(lat_ms, 0.50), 1),
        "serve_p99_ms": round(pick(lat_ms, 0.99), 1),
        "serve_ttft_p50_ms": round(pick(ttft_ms, 0.50), 1),
        "serve_ttft_p99_ms": round(pick(ttft_ms, 0.99), 1),
        "serve_slots": engine.n_slots,
        "serve_occupancy": round(
            engine.stats["decode_slot_steps"]
            / max(engine.stats["decode_steps"] * engine.n_slots, 1),
            3,
        ),
        "serve_prefill_compiles": engine._prefill._cache_size(),
        "serve_decode_compiles": engine._decode._cache_size(),
        "serve_prefix_hit_rate": round(pm["prefix_hit_rate"], 3),
        "serve_prefill_tokens_saved": pm["prefill_tokens_saved"],
        "serve_prefill_saved_frac": round(pm["prefill_saved_frac"], 3),
        "serve_prefix_copy_compiles": pm["prefix_copy_compiles"],
        "serve_prefix_ttft_p50_ms": round(prefix_results["prefix"][1], 1),
        "serve_nocache_ttft_p50_ms": round(prefix_results["nocache"][1], 1),
        "serve_prefix_ttft_speedup": round(
            prefix_results["nocache"][1] / max(prefix_results["prefix"][1], 1e-9), 2
        ),
        "serve_router_r1_tokens_per_sec": round(router_tps[1], 1),
        "serve_router_r2_tokens_per_sec": round(router_tps[2], 1),
        "serve_router_scaling_efficiency": round(
            router_tps[2] / max(2 * router_tps[1], 1e-9), 3
        ),
        "serve_router_r1_ttft_p99_ms": round(router_ttft_p99[1], 1),
        "serve_router_r2_ttft_p99_ms": round(router_ttft_p99[2], 1),
        "serve_router_affinity_hit_rate": round(affinity_hit_rate["affinity"], 3),
        "serve_router_noaffinity_hit_rate": round(
            affinity_hit_rate["noaffinity"], 3
        ),
        "serve_router_affinity_hit_delta": round(
            affinity_hit_rate["affinity"] - affinity_hit_rate["noaffinity"], 3
        ),
    }


def _train_affine_lm(params, cfg, steps, *, task_vocab=256, lr=1e-3, seed=0):
    """Briefly train an LM on a fixed affine next-token chain
    (x_{t+1} = (3x_t + 7) mod task_vocab): a memorizable synthetic task
    both the spec-decode target and its small draft learn in O(100) tiny
    steps, so their argmax streams CORRELATE — the fix for the meaningless
    `specdecode_accept_rate 0.0` that random weights produced (a
    layer-prefix of random weights shares no distribution with its
    target; the accept MATH was verified aligned, see
    tests/test_speculative.py::TestAcceptRateRegression)."""
    import optax

    from accelerate_tpu.models import llama

    tx = optax.adamw(lr)
    opt = tx.init(params)

    @jax.jit
    def train_step(params, opt, batch):
        loss, g = jax.value_and_grad(
            lambda p: llama.loss_fn(p, {"input_ids": batch}, cfg)
        )(params)
        upd, opt = tx.update(g, opt, params)
        return optax.apply_updates(params, upd), opt, loss

    rng = np.random.RandomState(seed)
    for _ in range(steps):
        params, opt, loss = train_step(
            params, opt, jnp.asarray(_affine_chain(rng, 16, 64, task_vocab))
        )
    return params, float(loss)


def _affine_chain(rng, B, S, task_vocab=256):
    x = rng.randint(0, task_vocab, (B, 1))
    xs = [x]
    for _ in range(S - 1):
        xs.append((3 * xs[-1] + 7) % task_vocab)
    return np.concatenate(xs, axis=1).astype(np.int32)


def _bench_specdecode(config) -> dict:
    """Speculative decoding at B=1 (the latency regime the reference's
    big-model tables report, `benchmarks/big_model_inference/README.md`):
    target = the headline decode model, draft = a separately-initialized
    2-layer model. Both are briefly trained on the same synthetic affine
    chain (`_train_affine_lm`) so their greedy streams CORRELATE and the
    accept rate measures the mechanism rather than the entropy of random
    weights (an accept rate of 0.0 with random weights was the latter);
    the accept comparison itself was verified aligned
    (tests/test_speculative.py::TestAcceptRateRegression). Greedy, so the
    output is bit-identical to vanilla decoding by construction.

    Also reports the self-draft run (accept == 1 by construction) as the
    mechanism ceiling."""
    import dataclasses
    import os

    from accelerate_tpu.generation import GenerationConfig, Generator
    from accelerate_tpu.models import llama
    from accelerate_tpu.speculative import SpeculativeGenerator

    tcfg = dataclasses.replace(config, remat=False, attention_impl="dot")
    dcfg = dataclasses.replace(tcfg, n_layers=2)
    train_steps = int(os.environ.get("ATX_BENCH_SPEC_TRAIN_STEPS", "150"))
    t0 = time.perf_counter()
    tparams_f32, t_loss = _train_affine_lm(
        llama.init(jax.random.PRNGKey(3), tcfg), tcfg, train_steps
    )
    dparams_f32, d_loss = _train_affine_lm(
        llama.init(jax.random.PRNGKey(5), dcfg), dcfg, train_steps
    )
    train_s = time.perf_counter() - t0
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), tparams_f32)
    draft_params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), dparams_f32)
    del tparams_f32, dparams_f32
    prompt = jnp.asarray(_affine_chain(np.random.RandomState(4), 1, 128))
    short, long = 16, 80
    n_tokens = long - short

    def t_pair(cfg):
        return (
            lambda p, t, c: llama.forward_with_cache(p, t, c, cfg),
            lambda b, m: llama.init_cache(cfg, b, m),
        )

    ta, tc = t_pair(tcfg)
    da, dc = t_pair(dcfg)

    def run(gen, *args) -> float:
        t0 = time.perf_counter()
        out = gen(*args, prompt)
        int(out[0, -1])
        return time.perf_counter() - t0

    out = {
        "specdecode_train_s": round(train_s, 1),
        "specdecode_task_loss": round(t_loss, 4),
        "specdecode_draft_task_loss": round(d_loss, 4),
    }
    # Vanilla B=1 decode as the speedup denominator (the B=8 headline
    # number amortizes per-step overhead differently).
    van_s = Generator(ta, tc, GenerationConfig(max_new_tokens=short))
    van_l = Generator(ta, tc, GenerationConfig(max_new_tokens=long))
    run(van_s, params), run(van_l, params)  # compile
    base_dt = max(
        min(run(van_l, params) for _ in range(2))
        - min(run(van_s, params) for _ in range(2)),
        1e-9,
    )
    out["decode_b1_tokens_per_sec"] = round(n_tokens / base_dt, 1)
    for label, dp in (("specdecode", draft_params), ("specdecode_selfdraft", None)):
        d_apply, d_cache, d_params = (da, dc, dp) if dp is not None else (ta, tc, params)
        spec = SpeculativeGenerator(
            ta, tc, d_apply, d_cache, GenerationConfig(max_new_tokens=long), draft_tokens=4
        )

        cache_cap = prompt.shape[1] + long + 2 * (4 + 1)

        def srun(n) -> float:
            t0 = time.perf_counter()
            o = spec(params, d_params, prompt, max_new_tokens=n, cache_len=cache_cap)
            int(o[0, -1])
            return time.perf_counter() - t0

        srun(short), srun(long)  # compile prefill + spec_step once
        dt = max(
            min(srun(long) for _ in range(2)) - min(srun(short) for _ in range(2)),
            1e-9,
        )
        out[f"{label}_tokens_per_sec"] = round(n_tokens / dt, 1)
        out[f"{label}_speedup"] = round(base_dt / dt, 3)
        if dp is not None:
            out["specdecode_accept_rate"] = round(spec.last_accept_rate, 3)

    # Batched self-draft (acceptance 1 by construction): with PER-ROW cache
    # commits each row advances independently, so B=4 throughput must scale
    # ~4x over the B=1 self-draft number —
    # under the old min-commit scheme one slow row throttled the batch.
    B4 = 4
    prompt4 = jnp.tile(prompt, (B4, 1))
    spec4 = SpeculativeGenerator(
        ta, tc, ta, tc, GenerationConfig(max_new_tokens=long), draft_tokens=4
    )
    cache_cap = prompt.shape[1] + long + 2 * (4 + 1)

    def b4run(n) -> float:
        t0 = time.perf_counter()
        o = spec4(params, params, prompt4, max_new_tokens=n, cache_len=cache_cap)
        int(o[0, -1])
        return time.perf_counter() - t0

    b4run(short), b4run(long)
    dt4 = max(
        min(b4run(long) for _ in range(2)) - min(b4run(short) for _ in range(2)),
        1e-9,
    )
    out["specdecode_b4_selfdraft_tokens_per_sec"] = round(B4 * n_tokens / dt4, 1)
    return out


def _bench_llama2b(fetch_latency: float) -> dict:
    """Largest *trainable* llama on one chip: 1.64B params,
    seq 4096, flash + remat. bf16 weights + adafactor are how 2B-class
    models train on a 16 GiB chip (fp32 master + adam moments alone would
    need 20+ GiB); measured on v5e: L=24/attn_and_outputs/batch 2 is the
    MFU-optimal fit (L=26 or batch 4 exceed HBM, block_outputs loses ~8
    MFU points to recompute). Evidence the headline MFU survives 8B-class
    arithmetic intensity."""
    import optax

    import accelerate_tpu as atx
    from accelerate_tpu.models import llama
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state()
    config = llama.LlamaConfig(
        vocab_size=32000,
        d_model=2048,
        n_layers=24,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
        d_ff=8192,
        max_seq_len=4096,
        remat=True,
        remat_policy="attn_and_outputs",
        attention_impl="flash",
        loss_chunk_size=512,
    )
    batch_size, seq, steps, warmup = 2, 4096, 8, 2
    acc = atx.Accelerator(mixed_precision="bf16", seed=0, max_grad_norm=1.0)
    state = acc.create_train_state(
        lambda r: llama.init(r, config, dtype=jnp.bfloat16), optax.adafactor(3e-4)
    )
    step = acc.make_train_step(lambda p, b, r: llama.loss_fn(p, b, config, r))
    batch = jax.device_put(
        {
            "input_ids": jax.random.randint(
                jax.random.PRNGKey(21), (batch_size, seq), 0, config.vocab_size, jnp.int32
            )
        }
    )
    state, metrics, dt, _ = _timed_steps(step, state, batch, steps, warmup, fetch_latency)
    tokens_per_sec = batch_size * (seq - 1) * steps / dt
    flops_per_token = 6.0 * config.param_count() + 6.0 * config.n_layers * config.d_model * seq
    peak = _peak_flops(jax.devices()[0])
    state, batch, metrics = acc.free_memory(state, batch, metrics)
    return {
        "llama2b_params": config.param_count(),
        "llama2b_mfu": round(tokens_per_sec * flops_per_token / peak, 4),
        "llama2b_tokens_per_sec": round(tokens_per_sec, 1),
    }


def _bench_hostoffload_adamw(fetch_latency: float) -> dict:
    """Adam-class fine-tuning past HBM via host-resident
    optimizer state (parallel/host_offload.py). Same 1.64B model as the
    llama2b phase but with adamw — whose fp32 moments (13 GiB) plus bf16
    weights would not leave room for seq-4096 activations in 16 GiB HBM;
    the moments live in pinned host RAM and stream through the update
    inside the compiled step."""
    import optax

    import accelerate_tpu as atx
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel import host_offload
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.dataclasses import FsdpPlugin

    AcceleratorState._reset_state()
    config = llama.LlamaConfig(
        vocab_size=32000,
        d_model=2048,
        n_layers=24,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
        d_ff=8192,
        max_seq_len=4096,
        remat=True,
        remat_policy="attn_and_outputs",
        attention_impl="flash",
        loss_chunk_size=512,
    )
    # batch 1 (vs llama2b's 2): the fp32 backward cotangents of the three
    # big MLP matmuls (4.5 GiB) + the moment working set leave ~batch-1
    # headroom on 16 GiB; batch 2 compiles 0.8 GiB over.
    batch_size, seq, steps, warmup = 1, 4096, 6, 2
    acc = atx.Accelerator(
        mixed_precision="bf16",
        seed=0,
        max_grad_norm=1.0,
        strategy=FsdpPlugin(offload_optimizer=True),
    )
    state = acc.create_train_state(
        lambda r: llama.init(r, config, dtype=jnp.bfloat16),
        # fp32 moments: the adam configuration whose state genuinely cannot
        # share HBM with the activations at this scale (13 GiB of moments).
        atx.host_offloaded_adamw(1e-4, mu_dtype=jnp.float32),
    )
    offloaded = host_offload.HOST_MEMORY_KIND in {
        l.sharding.memory_kind
        for l in jax.tree.leaves(state.opt_state)
        if isinstance(l, jax.Array)
    }
    step = acc.make_train_step(lambda p, b, r: llama.loss_fn(p, b, config, r))
    batch = jax.device_put(
        {
            "input_ids": jax.random.randint(
                jax.random.PRNGKey(23), (batch_size, seq), 0, config.vocab_size, jnp.int32
            )
        }
    )
    state, metrics, dt, _ = _timed_steps(step, state, batch, steps, warmup, fetch_latency)
    tokens_per_sec = batch_size * (seq - 1) * steps / dt
    flops_per_token = 6.0 * config.param_count() + 6.0 * config.n_layers * config.d_model * seq
    peak = _peak_flops(jax.devices()[0])
    state, batch, metrics = acc.free_memory(state, batch, metrics)
    return {
        "hostoffload_adamw_params": config.param_count(),
        "hostoffload_adamw_active": offloaded,
        "hostoffload_adamw_mfu": round(tokens_per_sec * flops_per_token / peak, 4),
        "hostoffload_adamw_tokens_per_sec": round(tokens_per_sec, 1),
    }


def _bench_vit(fetch_latency: float) -> dict:
    """ViT-base data-parallel training samples/sec — the cv_example config
    BASELINE.md tracks."""
    import optax

    import accelerate_tpu as atx
    from accelerate_tpu.models import vit
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state()
    # remat + batch 64: vit-base at batch 128 without remat needs ~25 GiB
    # of activations (fp32 adam moments are small; the 197-token streams
    # are not) — v5e has 16.
    config = vit.ViTConfig.vit_base(remat=True)
    batch_size, steps, warmup = 64, 10, 3
    acc = atx.Accelerator(mixed_precision="bf16", seed=0, max_grad_norm=1.0)
    state = acc.create_train_state(
        lambda r: vit.init(r, config), optax.adamw(3e-4)
    )

    def loss_fn(p, b, r):
        logits = vit.forward(p, b["pixels"], config)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, b["label"][:, None], axis=1))

    step = acc.make_train_step(loss_fn)
    k = jax.random.PRNGKey(31)
    batch = jax.device_put(
        {
            "pixels": jax.random.normal(
                k, (batch_size, config.image_size, config.image_size, 3), jnp.bfloat16
            ),
            "label": jax.random.randint(
                jax.random.fold_in(k, 1), (batch_size,), 0, config.num_classes, jnp.int32
            ),
        }
    )
    state, metrics, dt, _ = _timed_steps(step, state, batch, steps, warmup, fetch_latency)
    state, batch, metrics = acc.free_memory(state, batch, metrics)
    return {"vit_samples_per_sec": round(batch_size * steps / dt, 1)}


# ------------------------------------------------------------- 8B big model
# Llama-3.1-8B-shaped (rope_scaling included — the exact config published
# repos carry; exercises the scaled-frequency ingestion path at bench scale).
_LLAMA3_8B_HF_CONFIG = {
    "model_type": "llama",
    "vocab_size": 128256,
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_hidden_layers": 32,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "max_position_embeddings": 8192,
    "rope_theta": 500000.0,
    "rope_scaling": {
        "rope_type": "llama3",
        "factor": 8.0,
        "low_freq_factor": 1.0,
        "high_freq_factor": 4.0,
        "original_max_position_embeddings": 8192,
    },
    "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
}


def _synth_llama8b_repo(repo: str, cfg: dict | None = None) -> None:
    """Write a Llama-3-8B-shaped HF repo (config.json + sharded fp16
    safetensors, real HF tensor names, ~16 GiB). Values are a tiled random
    block — load/quantize/decode timing is entropy-agnostic, and full-size
    RNG would dominate the one-time synthesis cost."""
    import json as _json
    import os

    import numpy as np
    from safetensors.numpy import save_file

    cfg = cfg or _LLAMA3_8B_HF_CONFIG
    os.makedirs(repo, exist_ok=True)
    with open(os.path.join(repo, "config.json"), "w") as f:
        _json.dump(cfg, f)

    rng = np.random.RandomState(0)
    block = (rng.standard_normal(1 << 20) * 0.02).astype(np.float16)

    def rnd(*shape) -> np.ndarray:
        n = int(np.prod(shape))
        reps = -(-n // block.size)
        return np.tile(block, reps)[:n].reshape(shape)

    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    head_dim = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * head_dim
    weight_map: dict[str, str] = {}

    def dump(fname: str, tensors: dict) -> None:
        save_file(tensors, os.path.join(repo, fname))
        for k in tensors:
            weight_map[k] = fname

    dump(
        "model-embed.safetensors",
        {
            "model.embed_tokens.weight": rnd(cfg["vocab_size"], d),
            "lm_head.weight": rnd(cfg["vocab_size"], d),
            "model.norm.weight": np.ones((d,), np.float16),
        },
    )
    group = 4  # layers per shard file
    for start in range(0, cfg["num_hidden_layers"], group):
        tensors = {}
        for i in range(start, min(start + group, cfg["num_hidden_layers"])):
            L = f"model.layers.{i}."
            tensors[L + "input_layernorm.weight"] = np.ones((d,), np.float16)
            tensors[L + "post_attention_layernorm.weight"] = np.ones((d,), np.float16)
            tensors[L + "self_attn.q_proj.weight"] = rnd(d, d)
            tensors[L + "self_attn.k_proj.weight"] = rnd(kv, d)
            tensors[L + "self_attn.v_proj.weight"] = rnd(kv, d)
            tensors[L + "self_attn.o_proj.weight"] = rnd(d, d)
            tensors[L + "mlp.gate_proj.weight"] = rnd(ff, d)
            tensors[L + "mlp.up_proj.weight"] = rnd(ff, d)
            tensors[L + "mlp.down_proj.weight"] = rnd(d, ff)
        dump(f"model-layers-{start:02d}.safetensors", tensors)
    with open(os.path.join(repo, "model.safetensors.index.json"), "w") as f:
        _json.dump({"metadata": {"total_size": 0}, "weight_map": weight_map}, f)
    with open(os.path.join(repo, ".complete"), "w") as f:
        f.write("ok")


def _bench_bigmodel() -> dict:
    """The flagship big-model path EXECUTED at 8B scale:
    stream a 16 GiB HF-named repo from disk, int8-quantize on the way in
    (only packed weights touch HBM), run batched `generate()` on the one
    chip. Reports wall-clock load+quantize seconds and steady-state decode
    tokens/sec — the numbers the reference publishes for its
    big-model-inference path (`benchmarks/big_model_inference`)."""
    import dataclasses
    import os

    import accelerate_tpu as atx
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import llama
    from accelerate_tpu.state import AcceleratorState

    # The synthetic repo is ~16 GiB on disk and reused across runs. Point
    # ATX_BENCH_CACHE at a disk-backed path if /tmp is tmpfs (RAM-backed).
    cache = os.environ.get("ATX_BENCH_CACHE", "/tmp/atx_bench_cache")
    repo = os.path.join(cache, "llama3_8b_synth")
    if not os.path.exists(os.path.join(repo, ".complete")):
        t0 = time.perf_counter()
        _synth_llama8b_repo(repo)
        synth_s = time.perf_counter() - t0
    else:
        synth_s = 0.0
        # The weights are config-agnostic tiled noise; refresh config.json so
        # a repo cached by an older bench picks up config changes (e.g. the
        # llama-3.1 rope_scaling block) without a 16 GiB re-synthesis.
        with open(os.path.join(repo, "config.json"), "w") as f:
            json.dump(_LLAMA3_8B_HF_CONFIG, f)

    # Raw-read roofline: sequential read of one weight shard, so the load
    # time has an IO baseline to be judged against.
    shard_file = next(
        os.path.join(repo, n) for n in sorted(os.listdir(repo))
        if n.endswith(".safetensors")
    )
    t0 = time.perf_counter()
    read_bytes = 0
    with open(shard_file, "rb", buffering=0) as f:
        while chunk := f.read(1 << 24):
            read_bytes += len(chunk)
    io_mib_s = read_bytes / (time.perf_counter() - t0) / 2**20

    # Host->device link roofline: the load time must be judged against what
    # the link can move. A 1 MiB warm-up does not open the full-size
    # transfer path (a single timed 64 MiB put then pays first-touch
    # allocation and link setup), so measure steady state — full-size
    # warm put, then best-of-3 — and report the chunked TransferEngine
    # (parallel/transfer.py, PR 1) over the same buffer alongside it, since
    # that is the path load_pretrained actually rides.
    from accelerate_tpu.parallel.transfer import TransferEngine

    probe = np.empty(64 * 2**20, np.int8)

    def _put_mib_s(fn) -> float:
        fn().block_until_ready()  # full-size warm: opens the real path
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fn().block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return 64 / best

    blocking_put_mib_s = _put_mib_s(lambda: jax.device_put(probe))
    transfer_engine = TransferEngine()
    engine_put_mib_s = _put_mib_s(lambda: transfer_engine.put(probe).result())
    del probe

    AcceleratorState._reset_state()
    t0 = time.perf_counter()
    loaded = atx.load_pretrained(
        repo,
        mesh=atx.build_mesh(atx.MeshConfig()),
        dtype=jnp.bfloat16,
        quantize_bits=8,
    )
    load_s = time.perf_counter() - t0

    gen_config = dataclasses.replace(
        loaded.config, remat=False, attention_impl="dot", max_seq_len=512
    )
    B, prompt_len = 8, 128
    short, long = 8, 40
    prompt = jax.random.randint(
        jax.random.PRNGKey(5), (B, prompt_len), 0, gen_config.vocab_size, jnp.int32
    )

    def run(n_new: int) -> float:
        t0 = time.perf_counter()
        out = llama.generate(
            loaded.params,
            prompt,
            gen_config,
            generation_config=GenerationConfig(max_new_tokens=n_new),
        )
        int(out[0, -1])  # fetch barrier
        return time.perf_counter() - t0

    run(short), run(long)  # compile both loop lengths
    dt_short = min(run(short) for _ in range(2))
    dt_long = min(run(long) for _ in range(2))
    decode_dt = max(dt_long - dt_short, 1e-9)
    n_tokens = long - short
    out = {
        "bigmodel_8b_params": loaded.config.param_count(),
        "bigmodel_8b_bits": 8,
        "bigmodel_8b_load_s": round(load_s, 1),
        "bigmodel_8b_synth_s": round(synth_s, 1),
        "io_read_mib_s": round(io_mib_s, 1),
        "device_put_mib_s": round(blocking_put_mib_s, 1),
        "device_put_engine_mib_s": round(engine_put_mib_s, 1),
        "bigmodel_8b_decode_tokens_per_sec": round(B * n_tokens / decode_dt, 1),
        "bigmodel_8b_decode_ms_per_token": round(1000 * decode_dt / n_tokens, 2),
    }
    try:
        out.update(_bench_bigmodel_int8_prefill(loaded, gen_config, prompt))
    except Exception as e:
        out["bigmodel_prefill_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        out.update(_bench_bigmodel_specdecode(loaded, gen_config, prompt[:1]))
    except Exception as e:  # never lose the headline load/decode numbers
        out["bigmodel_spec_error"] = f"{type(e).__name__}: {e}"[:200]
    return out


def _bench_bigmodel_int8_prefill(loaded, gen_config, prompt) -> dict:
    """8B prefill on the already-int8-quantized weights: dequantize-first
    (weight-only) vs the int8 MXU path (`ops/int8.py`).
    Prefill at B=8, S=128 is compute-bound — exactly where dequantizing to
    bf16 before the matmul leaves the ~2× int8 MXU rate unused."""
    from accelerate_tpu.models import llama
    from accelerate_tpu.ops.int8 import with_int8_compute

    B, S = prompt.shape
    cache0 = llama.init_cache(gen_config, B, S + 8)

    def fwd(p, t, c):
        return llama.forward_with_cache(p, t, c, gen_config)

    f_deq = jax.jit(fwd)
    # with_int8_compute gives the int8 variant its own function object (and
    # thus its own jit cache entry) AND guarantees every trace happens with
    # the mode on — jax.jit(fwd) twice would silently share one jaxpr.
    f_i8 = jax.jit(with_int8_compute(fwd))
    logits, _ = f_deq(loaded.params, prompt, cache0)
    logits_i8, _ = f_i8(loaded.params, prompt, cache0)

    def timed(f, k=5, reps=3) -> float:
        # k pipelined prefills per scalar fetch amortize the fetch round trip.
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(k):
                lg, _ = f(loaded.params, prompt, cache0)
            float(lg[0, -1, 0])
            best = min(best, time.perf_counter() - t0)
        return best / k

    dt_deq = timed(f_deq)
    dt_i8 = timed(f_i8)
    # Logit drift bound: only activation rounding separates the paths.
    a = jnp.asarray(logits[:, -1, :], jnp.float32)
    b = jnp.asarray(logits_i8[:, -1, :], jnp.float32)
    drift = float(
        jnp.sqrt(jnp.mean((a - b) ** 2))
        / jnp.maximum(jnp.sqrt(jnp.mean(a**2)), 1e-9)
    )
    if drift == 0.0:
        # Identical logits mean the int8 trace silently aliased the bf16
        # one (the jit-cache pitfall) — refuse to report a fake comparison.
        raise RuntimeError("int8 prefill produced bit-identical logits")
    return {
        "prefill_8b_tokens_per_sec": round(B * S / dt_i8, 1),
        "prefill_8b_bf16_tokens_per_sec": round(B * S / dt_deq, 1),
        "prefill_8b_int8_speedup": round(dt_deq / dt_i8, 3),
        "prefill_8b_int8_logit_drift": round(drift, 6),
    }


def _bench_bigmodel_specdecode(loaded, gen_config, prompt) -> dict:
    """Speculative decoding where it actually pays: 8B int8 single-row
    decode is HBM-bandwidth-bound (every token streams all packed
    weights), so a K+1-token verify costs barely more than one decode step.
    Draft = the model's own first-2-layers prefix (zero extra load, shares
    embed/norms/head — quantized leaves slice along the stacked layer axis
    like any other). Greedy, so the stream equals vanilla decoding exactly;
    with the synthetic repo's random weights the accept rate is a FLOOR —
    report the self-consistency ceiling via implied tokens/iteration."""
    import dataclasses

    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import llama
    from accelerate_tpu.speculative import SpeculativeGenerator

    K = 4
    short, long = 8, 40
    n_tokens = long - short
    dcfg = dataclasses.replace(gen_config, n_layers=2)
    draft_params = dict(
        loaded.params,
        blocks=jax.tree.map(lambda x: x[:2], loaded.params["blocks"]),
    )

    def pair(cfg):
        return (
            lambda p, t, c: llama.forward_with_cache(p, t, c, cfg),
            lambda b, m: llama.init_cache(cfg, b, m),
        )

    ta, tc = pair(gen_config)
    da, dc = pair(dcfg)

    def vrun(n):
        # llama.generate caches its Generator per (config, gen_config), so
        # the short/long specializations compile once each.
        t0 = time.perf_counter()
        o = llama.generate(
            loaded.params, prompt, gen_config,
            generation_config=GenerationConfig(max_new_tokens=n),
        )
        int(o[0, -1])
        return time.perf_counter() - t0

    spec = SpeculativeGenerator(
        ta, tc, da, dc, GenerationConfig(max_new_tokens=long), draft_tokens=K
    )

    # Pin one cache capacity so short/long share one compiled graph set.
    spec_cache = prompt.shape[1] + long + 2 * (K + 1)

    def srun(n):
        t0 = time.perf_counter()
        o = spec(
            loaded.params, draft_params, prompt, max_new_tokens=n,
            cache_len=spec_cache,
        )
        int(o[0, -1])
        return time.perf_counter() - t0

    # Warm EVERY measured specialization (vanilla caches size on
    # prompt+max_new_tokens, so short and long are distinct compiles).
    vrun(short), vrun(long), srun(short), srun(long)
    base_dt = max(
        min(vrun(long) for _ in range(2)) - min(vrun(short) for _ in range(2)), 1e-9
    )
    spec_dt = max(
        min(srun(long) for _ in range(2)) - min(srun(short) for _ in range(2)), 1e-9
    )
    accept = spec.last_accept_rate
    out = {
        "bigmodel_8b_b1_decode_tokens_per_sec": round(n_tokens / base_dt, 1),
        "bigmodel_8b_specdecode_tokens_per_sec": round(n_tokens / spec_dt, 1),
        "bigmodel_8b_specdecode_speedup": round(base_dt / spec_dt, 3),
        "bigmodel_8b_specdecode_accept_rate": round(accept, 3),
    }
    # Mechanism ceiling: tokens/iteration scales 1 -> K+1 with acceptance,
    # iteration time is acceptance-independent (same draft scan + verify).
    # With random synthetic weights accept ~= 0, so the measured rate IS
    # ~the iteration rate; the ceiling says what a trained draft buys.
    iters_per_sec = (n_tokens / spec_dt) / (1 + K * accept)
    out["bigmodel_8b_specdecode_ceiling_tokens_per_sec"] = round(
        (K + 1) * iters_per_sec, 1
    )
    return out


def _bench_overram() -> dict:
    """Disk-offloaded decode: block weights live on DISK as
    memmaps (never resident in host RAM), streamed layer-by-layer per
    generated token — the reference's disk_offload / OPT-30B configuration
    (`big_modeling.py:260`). Decode rate = link-bandwidth / streamed-bytes,
    so the phase streams a layer-sliced view of the 8B repo (same tensors,
    same loader path, ATX_BENCH_OVERRAM_LAYERS of the 32 layers) to keep
    the phase inside the driver budget, and reports the measured stream
    bandwidth so the number scales to other hosts."""
    import dataclasses
    import os

    import accelerate_tpu as atx
    from accelerate_tpu.models import llama
    from accelerate_tpu.state import AcceleratorState

    cache = os.environ.get("ATX_BENCH_CACHE", "/tmp/atx_bench_cache")
    repo = os.path.join(cache, "llama3_8b_synth")
    if not os.path.exists(os.path.join(repo, ".complete")):
        return {"overram_error": "synth repo missing (bigmodel phase runs first)"}
    n_layers = int(os.environ.get("ATX_BENCH_OVERRAM_LAYERS", "3"))
    # A view repo: the 8B safetensors linked in place, config clamped to the
    # first n_layers (the loader reads only the tensors the shapes need).
    view = os.path.join(cache, f"overram_view_l{n_layers}")
    os.makedirs(view, exist_ok=True)
    cfg = dict(_LLAMA3_8B_HF_CONFIG)
    cfg["num_hidden_layers"] = n_layers
    with open(os.path.join(view, "config.json"), "w") as f:
        json.dump(cfg, f)
    for name in os.listdir(repo):
        if name.endswith(".safetensors") or name.endswith(".index.json"):
            dst = os.path.join(view, name)
            if not os.path.exists(dst):
                os.symlink(os.path.join(repo, name), dst)

    AcceleratorState._reset_state()
    t0 = time.perf_counter()
    loaded = atx.load_pretrained(
        view,
        mesh=atx.build_mesh(atx.MeshConfig()),
        dtype=jnp.bfloat16,
        # Budget just above the resident set (embed+lm_head bf16 = 2.1 GiB)
        # so every block is forced onto disk.
        hbm_budget=int(2.4 * 2**30),
        no_offload_patterns=("embed", "lm_head", "final_norm"),
        offload_dir=os.path.join(view, "offload"),
    )
    load_s = time.perf_counter() - t0
    n_memmap = sum(
        isinstance(l, np.memmap) for l in jax.tree.leaves(loaded.params)
    )
    if n_memmap == 0:
        return {"overram_error": "plan offloaded nothing to disk"}
    streamed_bytes = sum(
        l.nbytes for l in jax.tree.leaves(loaded.params) if isinstance(l, np.memmap)
    )

    gen_config = dataclasses.replace(
        loaded.config, remat=False, attention_impl="dot", max_seq_len=64
    )
    prompt = jax.random.randint(
        jax.random.PRNGKey(6), (1, 16), 0, gen_config.vocab_size, jnp.int32
    )
    n_new = int(os.environ.get("ATX_BENCH_OVERRAM_TOKENS", "2"))
    t0 = time.perf_counter()
    out = llama.generate_offloaded(
        loaded.params, prompt, gen_config, max_new_tokens=n_new
    )
    int(out[0, -1])
    dt = time.perf_counter() - t0
    # generate_offloaded runs 1 prefill + (n_new - 1) decode forwards.
    per_pass = dt / n_new
    return {
        "bigmodel_overram_disk_leaves": n_memmap,
        "bigmodel_overram_layers": n_layers,
        "bigmodel_overram_streamed_gib_per_token": round(streamed_bytes / 2**30, 2),
        "bigmodel_overram_stream_mib_s": round(streamed_bytes / per_pass / 2**20, 1),
        "bigmodel_overram_load_s": round(load_s, 1),
        "bigmodel_overram_decode_tokens_per_sec": round(n_new / dt, 4),
    }


def _bench_bert(fetch_latency: float) -> dict:
    """BERT-base training throughput — the `nlp_example` config BASELINE.md
    tracks (samples/sec/chip, bf16, seq 128). Returned as extra fields on the
    bench's single JSON line."""
    import optax

    import accelerate_tpu as atx
    from accelerate_tpu.models import bert
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state()
    config = bert.BertConfig.bert_base()
    batch_size, seq, steps, warmup = 128, 128, 10, 3

    acc = atx.Accelerator(mixed_precision="bf16", seed=0, max_grad_norm=1.0)
    state = acc.create_train_state(lambda r: bert.init(r, config), optax.adamw(3e-5))
    step = acc.make_train_step(lambda p, b, r: bert.loss_fn(p, b, config, r))
    rng = jax.random.PRNGKey(2)
    batch = {
        "input_ids": jax.random.randint(rng, (batch_size, seq), 3, config.vocab_size, jnp.int32),
        "attention_mask": jnp.ones((batch_size, seq), jnp.int32),
        "token_type_ids": jnp.zeros((batch_size, seq), jnp.int32),
        "labels": jax.random.randint(rng, (batch_size,), 0, config.num_labels, jnp.int32),
    }
    batch = jax.device_put(batch)
    state, metrics, dt, _ = _timed_steps(step, state, batch, steps, warmup, fetch_latency)
    stats = {
        "bert_samples_per_sec": round(batch_size * steps / dt, 1),
        "bert_step_time_ms": round(1000 * dt / steps, 2),
        "bert_params": config.param_count(),
    }
    # Free BERT buffers so the long-context bench that follows has full HBM.
    state, batch, metrics = acc.free_memory(state, batch, metrics)
    return stats


# ------------------------------------------------ regression compare gate
# `python bench.py --compare OLD.json NEW.json [--threshold 0.05]
#  [--series name,name,...]` diffs two bench result lines and exits non-zero
# on a regression beyond the threshold — the trajectory gate future perf PRs
# run in CI (`make smoke-trace`).

# Metric direction by suffix. Checked in order: a name matching a
# higher-better suffix is higher-better even when a lower-better suffix
# also matches (e.g. *_mib_s ends with both "_mib_s" and "_s").
_HIGHER_BETTER = (
    "_mfu", "_hfu", "_tokens_per_sec", "_samples_per_sec", "_per_sec", "_tflops",
    "_mib_s", "_gib_s", "_speedup", "_hit_rate", "_flops", "_mfu_bound",
    "_max_slots",
)
_LOWER_BETTER = (
    "_ms", "_s", "_secs", "_compiles", "_gib_per_token", "_comms_mib",
    "_waste_frac", "_peak_hbm_mib",
)


def _direction(name: str) -> int:
    """+1 higher-better, -1 lower-better, 0 not a perf series."""
    for suf in _HIGHER_BETTER:
        if name.endswith(suf):
            return 1
    for suf in _LOWER_BETTER:
        if name.endswith(suf):
            return -1
    return 0


def compare_results(
    old_path: str,
    new_path: str,
    *,
    threshold: float = 0.05,
    series: list[str] | None = None,
) -> tuple[list[str], int]:
    """Diff two bench JSON result files. Returns (regression messages,
    number of series compared). A series regresses when it moves against
    its direction by more than ``threshold`` (relative). ``series``
    restricts the comparison to named keys (and makes a named key MISSING
    from the new result a regression too — a silently dropped series must
    not pass the gate)."""
    with open(old_path) as f:
        old = json.load(f)
    with open(new_path) as f:
        new = json.load(f)
    regressions: list[str] = []
    compared = 0
    names = series if series is not None else sorted(set(old) & set(new))
    for name in names:
        if series is not None and (name not in old or name not in new):
            missing = "new" if name not in new else "old"
            regressions.append(f"{name}: named series missing from {missing} result")
            continue
        ov, nv = old.get(name), new.get(name)
        if (
            isinstance(ov, bool) or isinstance(nv, bool)
            or not isinstance(ov, (int, float))
            or not isinstance(nv, (int, float))
        ):
            continue
        sign = _direction(name)
        if sign == 0 and series is None:
            continue  # unnamed non-perf keys (counts, params) are ignored
        compared += 1
        if not ov:
            continue  # no baseline magnitude to compare against
        rel = (nv - ov) / abs(ov)
        if sign >= 0 and rel < -threshold:
            regressions.append(
                f"{name}: {ov} -> {nv} ({rel:+.1%}, higher is better, "
                f"threshold {threshold:.0%})"
            )
        elif sign < 0 and rel > threshold:
            regressions.append(
                f"{name}: {ov} -> {nv} ({rel:+.1%}, lower is better, "
                f"threshold {threshold:.0%})"
            )
    return regressions, compared


def _compare_main(argv: list[str]) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="bench.py --compare",
        description="Regression-gate two bench result JSON files",
    )
    p.add_argument("old")
    p.add_argument("new")
    p.add_argument(
        "--threshold", type=float, default=0.05,
        help="relative regression tolerance (default 0.05 = 5%%)",
    )
    p.add_argument(
        "--series", default=None,
        help="comma-separated series names to gate on (default: every "
        "shared key with a recognized perf suffix); a named series "
        "missing from either side is itself a regression",
    )
    args = p.parse_args(argv)
    series = (
        [s.strip() for s in args.series.split(",") if s.strip()]
        if args.series else None
    )
    regressions, compared = compare_results(
        args.old, args.new, threshold=args.threshold, series=series
    )
    for msg in regressions:
        print(f"REGRESSION {msg}")
    print(
        json.dumps(
            {
                "compared": compared,
                "regressions": len(regressions),
                "threshold": args.threshold,
                "ok": not regressions,
            }
        )
    )
    return 1 if regressions else 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--compare":
        sys.exit(_compare_main(sys.argv[2:]))
    sys.exit(main())
