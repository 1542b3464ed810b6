"""int8 MXU compute path (`ops/int8.py`): int8×int8→int32
contractions on quantized weights with dynamic per-token activation scaling.

The weight quantization error is shared with the dequantize-first path (same
stored int8 values + scales), so the tests bound only the NEW error source —
activation rounding — against the dequantize-first oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops.fp8 import matmul_einsum
from accelerate_tpu.ops.int8 import (
    _w_scale_to_out,
    int8_compute,
    int8_compute_enabled,
    int8_einsum,
    int8_einsum_quantized,
)
from accelerate_tpu.utils.quantization import (
    dequantize_array,
    quantize_array,
)

# Every projection equation the model zoo routes through matmul_einsum.
MODEL_EQS = [
    ("bsd,dhk->bshk", (2, 8, 32), (32, 4, 8)),     # qkv projection
    ("bshk,hkd->bsd", (2, 8, 4, 8), (4, 8, 32)),   # attention out
    ("bsd,df->bsf", (2, 8, 32), (32, 64)),         # mlp in / gate / up
    ("bsf,fd->bsd", (2, 8, 64), (64, 32)),         # mlp out
    ("ecd,edf->ecf", (4, 6, 32), (4, 32, 16)),     # moe expert ffn
]


class TestInt8Einsum:
    @pytest.mark.parametrize("eq,xs,ws", MODEL_EQS)
    def test_matches_dequant_oracle_per_equation(self, eq, xs, ws):
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, xs, jnp.float32)
        w = jax.random.normal(kw, ws, jnp.float32)
        node = quantize_array(w, stack_dims=1 if eq.startswith("ecd") else 0)
        w_deq = dequantize_array(node, jnp.float32)
        want = jnp.einsum(eq, x, w_deq)
        got = int8_einsum_quantized(eq, x, node).astype(jnp.float32)
        # Only activation rounding separates the two: per-tensor int8 is
        # ~0.4% rms relative error on gaussian data.
        denom = jnp.maximum(jnp.sqrt(jnp.mean(want**2)), 1e-6)
        rel = float(jnp.sqrt(jnp.mean((got - want) ** 2)) / denom)
        assert rel < 0.02, f"{eq}: rel rms {rel:.4f}"

    def test_w_scale_alignment_is_exact(self):
        # With activations already exactly representable in int8 (integers
        # <= 127 under scale 1), the path must be EXACT — any misalignment
        # of the per-channel scale to the output shows up as a hard error.
        from accelerate_tpu.ops.int8 import _x_contracted_axes

        for eq, xs, ws in MODEL_EQS:
            kx, kw = jax.random.split(jax.random.PRNGKey(1))
            # Integer activations where EVERY quantization row's amax is
            # exactly 127: quantize_act is the identity (scale 1), so the
            # whole path must be bit-exact up to the shared weight
            # quantization.
            x = jnp.round(jax.random.uniform(kx, xs) * 254 - 127)
            contracted = _x_contracted_axes(eq)
            pin = tuple(
                0 if i in contracted else slice(None) for i in range(len(xs))
            )
            x = x.at[pin].set(127.0)
            w = jax.random.normal(kw, ws, jnp.float32)
            node = quantize_array(w, stack_dims=1 if eq.startswith("ecd") else 0)
            want = jnp.einsum(eq, x, dequantize_array(node, jnp.float32))
            got = int8_einsum_quantized(eq, x, node).astype(jnp.float32)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-3
            )

    def test_int32_accumulation_no_overflow(self):
        # 4096-deep contraction of worst-case ±127 values stays exact in
        # int32 (127*127*4096 ≈ 6.6e7 << 2^31) — the accumulator dtype is
        # load-bearing, int8 or bf16 accumulation would be garbage.
        D = 4096
        x = jnp.full((1, D), 127.0)
        w = jnp.full((D, 8), 1.0)
        node = quantize_array(w)
        got = int8_einsum_quantized("bd,df->bf", x, node)
        want = jnp.einsum("bd,df->bf", x, dequantize_array(node, jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3)

    def test_int4_unpacks_to_same_mxu_path(self):
        kx, kw = jax.random.split(jax.random.PRNGKey(2))
        x = jax.random.normal(kx, (2, 8, 32), jnp.float32)
        w = jax.random.normal(kw, (32, 64), jnp.float32)
        node = quantize_array(w, bits=4)
        assert "__quant4__" in node
        want = jnp.einsum("bsd,df->bsf", x, dequantize_array(node, jnp.float32))
        got = int8_einsum_quantized("bsd,df->bsf", x, node).astype(jnp.float32)
        denom = jnp.maximum(jnp.sqrt(jnp.mean(want**2)), 1e-6)
        rel = float(jnp.sqrt(jnp.mean((got - want) ** 2)) / denom)
        assert rel < 0.02


class TestModeRouting:
    def test_matmul_einsum_routes_by_context(self):
        kx, kw = jax.random.split(jax.random.PRNGKey(3))
        x = jax.random.normal(kx, (2, 8, 32), jnp.bfloat16)
        w = jax.random.normal(kw, (32, 64), jnp.float32)
        node = quantize_array(w)
        # Outside the context: dequantize-first (bit-identical to manual).
        assert not int8_compute_enabled()
        out_deq = matmul_einsum("bsd,df->bsf", x, node)
        manual = jnp.einsum("bsd,df->bsf", x, dequantize_array(node, x.dtype))
        np.testing.assert_array_equal(np.asarray(out_deq), np.asarray(manual))
        # Inside: int8 path (differs by activation rounding, close).
        with int8_compute():
            assert int8_compute_enabled()
            out_i8 = matmul_einsum("bsd,df->bsf", x, node)
        f32 = np.asarray(out_i8, np.float32)
        ref = np.asarray(manual, np.float32)
        rel = np.sqrt(np.mean((f32 - ref) ** 2)) / max(np.sqrt(np.mean(ref**2)), 1e-6)
        assert rel < 0.03

    def test_plain_weights_unaffected_by_context(self):
        kx, kw = jax.random.split(jax.random.PRNGKey(4))
        x = jax.random.normal(kx, (2, 8, 32), jnp.bfloat16)
        w = jax.random.normal(kw, (32, 64), jnp.bfloat16)
        with int8_compute():
            got = matmul_einsum("bsd,df->bsf", x, w)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(jnp.einsum("bsd,df->bsf", x, w))
        )


class TestJitCacheAliasing:
    def test_with_int8_compute_defeats_shared_trace_cache(self):
        """jax shares the trace cache across jax.jit wrappers of the SAME
        function object, so `jax.jit(f)` traced outside the context and
        called inside it reuses the dequant jaxpr — `with_int8_compute`
        must yield a genuinely different (int8) computation."""
        from accelerate_tpu.models import llama
        from accelerate_tpu.ops.int8 import with_int8_compute
        from accelerate_tpu.utils.quantization import quantize_pytree

        cfg = llama.LlamaConfig.tiny(vocab_size=64)
        qparams = quantize_pytree(
            llama.init(jax.random.PRNGKey(0), cfg), min_size=512
        )
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64, jnp.int32)

        def fwd(p, t):
            return llama.forward(p, t, cfg)

        base = jax.jit(fwd)(qparams, toks)
        # The pitfall: a second jit of the SAME function object, even
        # called inside the context, aliases the first trace.
        with int8_compute():
            aliased = jax.jit(fwd)(qparams, toks)
        np.testing.assert_array_equal(np.asarray(aliased), np.asarray(base))
        # The supported spelling gets its own trace and differs.
        fixed = jax.jit(with_int8_compute(fwd))(qparams, toks)
        assert float(jnp.abs(fixed.astype(jnp.float32) - base.astype(jnp.float32)).max()) > 0


class TestEndToEndLlama:
    def test_quantized_forward_logit_drift_bounded(self):
        """Full quantized-llama forward under int8_compute: logits drift
        from the dequantize-first path only by activation rounding; argmax
        agreement stays high (the decode-relevant bound)."""
        from accelerate_tpu.models import llama
        from accelerate_tpu.utils.quantization import quantize_pytree

        cfg = llama.LlamaConfig.tiny(vocab_size=128)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        qparams = quantize_pytree(params, min_size=512)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128, jnp.int32)

        from accelerate_tpu.ops.int8 import with_int8_compute

        def fwd(p, t):
            return llama.forward(p, t, cfg)

        base = jax.jit(fwd)(qparams, toks).astype(jnp.float32)
        fast = jax.jit(with_int8_compute(fwd))(qparams, toks).astype(jnp.float32)
        rel = float(
            jnp.sqrt(jnp.mean((fast - base) ** 2))
            / jnp.maximum(jnp.sqrt(jnp.mean(base**2)), 1e-6)
        )
        # rel == 0 would mean the int8 trace silently aliased the bf16 one
        # (the shared-jit-cache pitfall that produced a fake 8B comparison
        # in bench development) — the drift must be PRESENT and bounded.
        assert 0.0 < rel < 0.05, f"logit drift {rel:.4f}"
        agree = float(
            jnp.mean((jnp.argmax(fast, -1) == jnp.argmax(base, -1)).astype(jnp.float32))
        )
        assert agree > 0.9, f"argmax agreement {agree:.2f}"

    def test_cached_verify_forward_works_under_int8(self):
        """The speculative-verify shape: forward_with_cache over K+1 tokens
        with quantized weights under int8_compute."""
        from accelerate_tpu.models import llama
        from accelerate_tpu.utils.quantization import quantize_pytree

        cfg = llama.LlamaConfig.tiny(vocab_size=64)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        qparams = quantize_pytree(params, min_size=512)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 64, jnp.int32)

        from accelerate_tpu.ops.int8 import with_int8_compute

        def fwd(p, t, c):
            return llama.forward_with_cache(p, t, c, cfg)

        cache = llama.init_cache(cfg, 2, 32)
        base_logits, _ = jax.jit(fwd)(qparams, toks, cache)
        cache2 = llama.init_cache(cfg, 2, 32)
        fast_logits, cache2 = jax.jit(with_int8_compute(fwd))(qparams, toks, cache2)
        base, fast = base_logits.astype(jnp.float32), fast_logits.astype(jnp.float32)
        rel = float(
            jnp.sqrt(jnp.mean((fast - base) ** 2))
            / jnp.maximum(jnp.sqrt(jnp.mean(base**2)), 1e-6)
        )
        assert 0.0 < rel < 0.05
        assert int(cache2["length"]) == 5


def test_w_scale_to_out_shapes():
    # (D,K,h) scale with contracted D kept as 1 -> aligned to bshk output.
    ws = jnp.arange(1.0, 1.0 + 4 * 8).reshape(1, 4, 8)
    out = _w_scale_to_out("bsd,dhk->bshk", ws)
    assert out.shape == (1, 1, 4, 8)
    np.testing.assert_array_equal(np.asarray(out)[0, 0], np.asarray(ws)[0])
    # moe: e is batch-like in both operands and kept in the output.
    ws = jnp.ones((4, 1, 16))
    assert _w_scale_to_out("ecd,edf->ecf", ws).shape == (4, 1, 16)


class TestComposability:
    def test_speculative_decoding_exact_under_int8_compute(self):
        """Greedy speculative output must be bit-identical to vanilla greedy
        OF THE SAME FORWARD — including when that forward is the int8-MXU
        path on a quantized model (both sides traced under the mode)."""
        from accelerate_tpu.generation import GenerationConfig, Generator
        from accelerate_tpu.models import llama
        from accelerate_tpu.ops.int8 import int8_compute
        from accelerate_tpu.speculative import SpeculativeGenerator
        from accelerate_tpu.utils.quantization import quantize_pytree

        tcfg = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=128)
        dcfg = llama.LlamaConfig.tiny(
            vocab_size=61, max_seq_len=128, n_layers=1, d_model=32,
            num_heads=2, num_kv_heads=2, d_ff=64,
        )
        tp = quantize_pytree(llama.init(jax.random.PRNGKey(1), tcfg), min_size=512)
        dp = quantize_pytree(llama.init(jax.random.PRNGKey(2), dcfg), min_size=512)

        def pair(cfg):
            return (
                lambda p, t, c: llama.forward_with_cache(p, t, c, cfg),
                lambda b, m: llama.init_cache(cfg, b, m),
            )

        ta, tc = pair(tcfg)
        da, dc = pair(dcfg)
        config = GenerationConfig(max_new_tokens=11)
        prompt = jnp.asarray(np.arange(10, dtype=np.int32).reshape(2, 5) % 61)
        # The generators build fresh jitted closures internally, so tracing
        # them inside the mode context is sufficient here.
        with int8_compute():
            want = Generator(ta, tc, config)(tp, prompt)
            got = SpeculativeGenerator(ta, tc, da, dc, config, draft_tokens=3)(
                tp, dp, prompt
            )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_int8_kv_cache_with_int8_weights(self):
        """int8 KV storage and int8 weight compute compose: the carry-layout
        cached forward with BOTH runs and stays close to the bf16 oracle."""
        from accelerate_tpu.models import llama
        from accelerate_tpu.ops.int8 import with_int8_compute
        from accelerate_tpu.utils.quantization import quantize_pytree

        cfg = llama.LlamaConfig.tiny(vocab_size=64)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        qparams = quantize_pytree(params, min_size=512)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64, jnp.int32)

        def fwd(p, t, c):
            return llama.forward_with_cache(p, t, c, cfg)

        oracle, _ = jax.jit(fwd)(params, toks, llama.init_cache(cfg, 2, 16))
        fast, cache = jax.jit(with_int8_compute(fwd))(
            qparams, toks, llama.init_cache(cfg, 2, 16, dtype=jnp.int8)
        )
        assert cache["k"].dtype == jnp.int8
        a = oracle.astype(jnp.float32)
        b = fast.astype(jnp.float32)
        rel = float(
            jnp.sqrt(jnp.mean((b - a) ** 2))
            / jnp.maximum(jnp.sqrt(jnp.mean(a**2)), 1e-6)
        )
        assert 0.0 < rel < 0.1, rel


def test_int8_kernel_takes_per_head_weights():
    """The model's own weight layout through the Pallas kernel: attention
    projections are (d, heads, head_dim) and quantize to ONE scale per
    head_dim channel, shared by the heads — the kernel must broadcast it to
    the (heads * head_dim) output columns (it used to reshape, and raised)."""
    from accelerate_tpu.native.pallas.dispatch import force_kernels
    from accelerate_tpu.ops import int8 as int8_ops
    from accelerate_tpu.utils.quantization import quantize_array

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 64), jnp.bfloat16)
    node = quantize_array(jax.random.normal(jax.random.PRNGKey(1), (64, 4, 16)), stack_dims=0)
    assert node["scale"].shape == (1, 1, 16)
    with force_kernels("off"):
        ref = int8_ops.int8_einsum_quantized("bsd,dhk->bshk", x, node)
    with force_kernels("interpret"):
        out = int8_ops.int8_einsum_quantized("bsd,dhk->bshk", x, node)
    assert out.shape == (2, 8, 4, 16)
    np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(ref, np.float32))


# ------------------------------------------------ layer stacks read in place
# A decoder layer's seven quantized contractions: (equation, activation
# shape, one layer's weight shape, the path `int8_einsum` takes for a stack).
LAYER_CONTRACTIONS = {
    "wq": ("bsd,dhk->bshk", (2, 8, 64), (64, 4, 32), "sliced"),
    "wk": ("bsd,dhk->bshk", (2, 8, 64), (64, 2, 32), "sliced"),
    "wv": ("bsd,dhk->bshk", (2, 8, 64), (64, 2, 32), "sliced"),
    "wo": ("bshk,hkd->bsd", (2, 8, 4, 32), (4, 32, 64), "in_place"),
    "w_gate": ("bsd,df->bsf", (2, 8, 64), (64, 128), "in_place"),
    "w_up": ("bsd,df->bsf", (2, 8, 64), (64, 128), "in_place"),
    "w_down": ("bsf,fd->bsd", (2, 8, 128), (128, 64), "in_place"),
}


@pytest.mark.parametrize("name", list(LAYER_CONTRACTIONS))
def test_int8_einsum_of_a_stack_at_a_traced_layer_equals_the_sliced_call(name):
    """`int8_einsum_quantized` on a node that carries the whole stack and the
    scan's layer counter, against the node the scan would have sliced out:
    bitwise, whichever path the stack takes (the kernel reads it in place
    where its 2D view is the same bytes, else the layer is sliced here)."""
    from accelerate_tpu.native.pallas.dispatch import force_kernels
    from accelerate_tpu.ops.int8 import _LAYER_KEY, record_weight_paths

    eq, x_shape, w_shape, path = LAYER_CONTRACTIONS[name]
    layers = 3
    x = jax.random.normal(jax.random.PRNGKey(0), x_shape, jnp.bfloat16)
    node = quantize_array(jax.random.normal(jax.random.PRNGKey(1), (layers,) + w_shape))

    @jax.jit
    def scanned(stack, scales):
        def body(i, scale):
            layer = {"__quant__": stack, "scale": scale, _LAYER_KEY: i}
            return i + 1, int8_einsum_quantized(eq, x, layer)

        return jax.lax.scan(body, jnp.zeros((), jnp.int32), scales)[1]

    with force_kernels("interpret"), record_weight_paths() as paths:
        got = scanned(node["__quant__"], node["scale"])
        assert paths == [path]
        for i in range(layers):
            one = {"__quant__": node["__quant__"][i], "scale": node["scale"][i]}
            want = int8_einsum_quantized(eq, x, one)
            np.testing.assert_array_equal(
                np.asarray(got[i], np.float32), np.asarray(want, np.float32)
            )
        assert paths == [path] + ["sliced"] * layers  # a lone matrix was handed over sliced


class TestLlamaReadsItsStacksInPlace:
    """`llama.forward_with_cache` under `int8_compute`: the int8 value stacks
    leave the scan's xs and reach the kernel whole, with the layer counter."""

    @staticmethod
    def _model(bits=8):
        from accelerate_tpu.models import llama
        from accelerate_tpu.utils.quantization import quantize_pytree

        cfg = llama.LlamaConfig.tiny(vocab_size=64, head_dim=32, n_layers=3)
        params = quantize_pytree(llama.init(jax.random.PRNGKey(0), cfg), min_size=512, bits=bits)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 64, jnp.int32)
        return llama, cfg, params, toks

    @staticmethod
    def _prefill_and_decode(llama, cfg, params, toks):
        """Logits of a 5-token prefill and a decode step, and the paths each
        program's seven contractions recorded."""
        from accelerate_tpu.ops.int8 import record_weight_paths, with_int8_compute

        # A new function object: the trace cache must not hand back a program
        # traced under another kernel mode.
        step = jax.jit(with_int8_compute(lambda p, t, c: llama.forward_with_cache(p, t, c, cfg)))
        with record_weight_paths() as paths:
            first, cache = step(params, toks[:, :5], llama.init_cache(cfg, 2, 16))
            second, _ = step(params, toks[:, 5:], cache)
        return np.asarray(first, np.float32), np.asarray(second, np.float32), paths

    def test_logits_equal_the_sliced_program_bitwise(self, monkeypatch):
        from accelerate_tpu.native.pallas.dispatch import force_kernels

        llama, *model = self._model()
        with force_kernels("interpret"):
            first, second, paths = self._prefill_and_decode(llama, *model)
            assert paths == (["sliced"] * 3 + ["in_place"] * 4) * 2
            # The same kernel, handed what the scan slices out of its xs.
            monkeypatch.setattr(llama, "hoist_layer_stacks", lambda blocks: (blocks, {}))
            first_sliced, second_sliced, paths = self._prefill_and_decode(llama, *model)
            assert paths == ["sliced"] * 14
        np.testing.assert_array_equal(first, first_sliced)
        np.testing.assert_array_equal(second, second_sliced)
        monkeypatch.undo()
        # The XLA fallback divides by the activation scale in IEEE; the
        # kernel's divide is 1 ulp off, on top of bf16 rounding.
        with force_kernels("off", "int8_matmul"):
            first_off, second_off, paths = self._prefill_and_decode(llama, *model)
        assert paths == ["sliced"] * 14
        np.testing.assert_allclose(first, first_off, rtol=1e-2, atol=1e-2)
        np.testing.assert_allclose(second, second_off, rtol=1e-2, atol=1e-2)

    def test_packed_int4_keeps_the_slice(self):
        """Unpacking is elementwise before the contraction: of a whole stack
        it would be every step's. The scan slices the packed layer."""
        from accelerate_tpu.native.pallas.dispatch import force_kernels
        from accelerate_tpu.ops.int8 import hoist_layer_stacks
        from accelerate_tpu.utils.quantization import has_quantized

        llama, cfg, params, toks = self._model(bits=4)
        assert "__quant4__" in params["blocks"]["mlp"]["w_gate"]
        with force_kernels("interpret"):
            with int8_compute():
                blocks, stacks = hoist_layer_stacks(params["blocks"])
            assert stacks == {} and has_quantized(blocks)
            assert blocks["mlp"]["w_gate"]["__quant4__"] is params["blocks"]["mlp"]["w_gate"]["__quant4__"]
            *_, paths = self._prefill_and_decode(llama, cfg, params, toks)
        assert paths == ["sliced"] * 14

    def test_a_mesh_of_several_devices_keeps_the_slice(self):
        """A Pallas call is not partitioned by this runtime: handed a stack
        sharded over `tensor` it would gather it. Under a mesh of one device
        (or none) the stacks are hoisted."""
        from jax.sharding import Mesh

        from accelerate_tpu.native.pallas.dispatch import force_kernels
        from accelerate_tpu.ops.int8 import hoist_layer_stacks

        llama, cfg, params, toks = self._model()
        hoisted = lambda: hoist_layer_stacks(params["blocks"])[1]
        with force_kernels("interpret"), int8_compute():
            assert len(hoisted()) == 7
            with jax.sharding.set_mesh(Mesh(np.array(jax.devices()[:1]), ("tensor",))):
                assert len(hoisted()) == 7
            with jax.sharding.set_mesh(Mesh(np.array(jax.devices()[:2]), ("tensor",))):
                assert hoisted() == {}
                *_, paths = self._prefill_and_decode(llama, cfg, params, toks)
        assert paths == ["sliced"] * 14

    def test_outside_int8_compute_or_with_the_kernel_off_nothing_is_hoisted(self):
        from accelerate_tpu.native.pallas.dispatch import force_kernels
        from accelerate_tpu.ops.int8 import hoist_layer_stacks

        llama, cfg, params, toks = self._model()
        with force_kernels("interpret"):
            assert hoist_layer_stacks(params["blocks"])[1] == {}  # dequantize-first path
        with force_kernels("off", "int8_matmul"), int8_compute():
            assert hoist_layer_stacks(params["blocks"])[1] == {}  # XLA fuses its own slice

    def test_streamed_blocks_are_lone_matrices(self):
        """`forward_with_cache_offloaded` stages one layer's block at a time
        from the host: a 2D weight with no layer axis, the kernel as it was."""
        from accelerate_tpu.native.pallas.dispatch import force_kernels
        from accelerate_tpu.ops.int8 import record_weight_paths

        llama, cfg, params, toks = self._model()
        host = dict(params, blocks=jax.tree.map(np.asarray, params["blocks"]))
        with force_kernels("interpret"), int8_compute(), record_weight_paths() as paths:
            logits, _ = llama.forward_with_cache_offloaded(
                host, toks, llama.init_cache(cfg, 2, 16), cfg
            )
        assert paths == ["sliced"] * 7  # one jitted layer step, traced once
        assert logits.shape == (2, 6, 64) and bool(jnp.isfinite(logits).all())
