"""Pallas kernel tier (`native/pallas/`): interpret-mode parity against the
exact fallback lowerings, dispatch knob resolution, and ATX-lint cleanliness
of the kernel-enabled decode and train steps.

Parity expectations are documented per kernel: the fp8 contraction kernel is
structurally identical to the fallback (quantization stays outside) so it
matches to f32 tolerance; the int8 kernel's integer accumulation is exact
but its activation-scale divide lowers with TPU reciprocal semantics (1 ulp
off IEEE) — ~1e-7 relative, not bitwise; fused AdamW's divides/sqrt likewise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.native.pallas import (
    force_kernels,
    kernel_mode,
    kernel_status,
    pallas_available,
)
from accelerate_tpu.native.pallas import decode_attention, fused_adamw, prefill_attention, quant_matmul

pytestmark = pytest.mark.skipif(
    not pallas_available(), reason="jax.experimental.pallas not importable"
)


# ================================================================ dispatch
class TestDispatch:
    def test_default_auto_falls_back_off_tpu(self):
        assert jax.default_backend() != "tpu"
        assert kernel_mode("decode_attn") is None

    def test_global_and_per_kernel_knobs(self):
        with force_kernels("interpret"):
            assert kernel_mode("decode_attn") == "interpret"
        with force_kernels("off"):
            assert kernel_mode("decode_attn") is None
        # A kernel's own override beats the one for all kernels, whichever
        # was entered first.
        with force_kernels("off"), force_kernels("interpret", "decode_attn"):
            assert kernel_mode("decode_attn") == "interpret"
            assert kernel_mode("fused_adamw") is None
        with force_kernels("interpret", "decode_attn"), force_kernels("off"):
            assert kernel_mode("decode_attn") == "interpret"
            assert kernel_mode("fused_adamw") is None
        # "on" means compiled-iff-TPU: fallback on CPU.
        with force_kernels("on"):
            assert kernel_mode("decode_attn") is None

    def test_unknown_knob_value_raises(self):
        with pytest.raises(ValueError, match="unknown kernel mode 'fastplease'"):
            with force_kernels("fastplease"):
                pass
        assert kernel_mode("decode_attn") is None  # nothing was left forced

    def test_force_kernels_nests_and_restores(self):
        with force_kernels("off"):
            assert kernel_mode("decode_attn") is None
            with force_kernels("interpret", "decode_attn"):
                assert kernel_mode("decode_attn") == "interpret"
                assert kernel_mode("fused_adamw") is None  # outer "off"
            assert kernel_mode("decode_attn") is None
        assert kernel_mode("decode_attn") is None  # the code's own choice again

    def test_kernel_status_lists_all_kernels(self):
        names = {row["kernel"] for row in kernel_status()}
        assert {"decode_attn", "prefill_attn", "int8_matmul", "fp8_matmul", "fused_adamw"} <= names
        with force_kernels("interpret"):
            modes = {row["kernel"]: row["mode"] for row in kernel_status()}
        assert modes["decode_attn"] == "interpret"


# ===================================================== flash-prefill attention
def _chunk_reference(q, k, v, start):
    """What the three families' chunk lowering computes: key row ``j`` is
    seen from the query at ``start + r`` iff ``j <= start + r``."""
    from accelerate_tpu.models.layers import cache_positions, dot_product_attention

    B, S = q.shape[:2]
    positions = cache_positions(jnp.asarray(start, jnp.int32), S, B)
    mask = jnp.arange(k.shape[1])[None, None, :] <= positions[:, :, None]
    return dot_product_attention(q, k, v, mask=mask)


def _chunk_operands(dtype, B, S, T, K, group, h=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, K * group, h), dtype)
    k = jax.random.normal(ks[1], (B, T, K, h), dtype)
    v = jax.random.normal(ks[2], (B, T, K, h), dtype)
    return q, k, v


class TestFlashPrefill:
    T, S = 2048, 64  # four blocks of 512, four query tiles of 16

    @pytest.mark.parametrize("group", [4, 7, 1])
    @pytest.mark.parametrize(
        "start",
        [0, 512, 1000, 2048 - 64, (0, 1000, 1536)],
        ids=["start-0", "block-multiple", "unaligned-1000", "slot-end", "per-row-cursors"],
    )
    def test_parity_with_the_masked_reference_on_a_layer_of_the_stack(self, start, group):
        """A chunk written at the cursor against layer 1 of a stack of three
        whose other layers and whose rows past the chunk hold noise: a wrong
        layer, or a row past the cursor, cannot pass. The pad tail of a
        bucket is computed like any row (its own keys are in the cache)."""
        B = 3 if isinstance(start, tuple) else 1
        q, k, v = _chunk_operands(jnp.float32, B, self.S, self.T, K=2, group=group)
        starts = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
        seen = jnp.arange(self.T)[None, :] < (starts[:, None] + self.S)
        loud = lambda x: jnp.where(seen[:, :, None, None], x, 50.0)  # noqa: E731
        out = jax.jit(
            lambda i, at: prefill_attention.flash_prefill(
                q, _stacked(loud(k), 1, 3), _stacked(loud(v), 1, 3), at, i, tiles=(16, 512), interpret=True
            )
        )(jnp.int32(1), jnp.asarray(start, jnp.int32))
        np.testing.assert_allclose(out, _chunk_reference(q, k, v, start), rtol=2e-5, atol=2e-5)

    def test_bf16_rows_and_several_query_tiles(self):
        """bf16 rows (products in bf16, softmax in float32) and a chunk of
        four query tiles whose last blocks differ."""
        q, k, v = _chunk_operands(jnp.bfloat16, 1, 256, 1024, K=2, group=2, h=32)
        out = prefill_attention.flash_prefill(
            q, _stacked(k), _stacked(v), 300, tiles=(64, 128), interpret=True
        )
        ref = _chunk_reference(q, k, v, 300)
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref.astype(jnp.float32), rtol=3e-2, atol=3e-2
        )

    def test_the_grid_ends_at_the_cursor(self):
        """A query tile's blocks end at the one that holds its last position:
        the steps follow the cursor, not the slot's length."""
        bq, bk, T = 16, 512, 2048
        for start, blocks in ((0, [1, 1, 1, 1]), (500, [2, 2, 2, 2]), (1000, [2, 3, 3, 3]), (T - 64, [4] * 4)):
            ends = start + (jnp.arange(4, dtype=jnp.int32) + 1) * bq
            n, row, _ = decode_attention.live_steps(ends, T, bk)
            assert np.bincount(np.asarray(row)[: int(n)], minlength=4).tolist() == blocks, start

    @pytest.mark.parametrize(
        "case",
        ["int8-kv", "window", "five-rows", "length-no-block-divides", "one-row"],
    )
    def test_unsupported_shapes_decline_and_the_caller_gets_the_reference(self, case):
        """`supported()` says no, `maybe_flash_prefill` hands back None, and
        `layers.cached_attention` then returns the sliced lowering's values
        with the kernels forced to interpret mode."""
        from accelerate_tpu.models.layers import cached_attention, quantize_kv, record_attention_paths

        S, T = {"five-rows": (5, 64), "length-no-block-divides": (16, 1001), "one-row": (1, 64)}.get(case, (16, 64))
        q, k, v = _chunk_operands(jnp.float32, 2, S, T, K=2, group=2)
        kv = {"k": _stacked(k, 1, 2), "v": _stacked(v, 1, 2)}
        kw = {}
        if case == "int8-kv":
            (kq, ksc), (vq, vsc) = quantize_kv(k), quantize_kv(v)
            kv = {
                "k": _stacked(kq, 1, 2), "v": _stacked(vq, 1, 2),
                "k_scale": _stacked(ksc, 1, 2), "v_scale": _stacked(vsc, 1, 2),
            }
            kw["quantized"] = True
        if case == "window":
            kw["window"] = 8
        assert not prefill_attention.supported(q, kv["k"], **kw)
        positions = 7 + jnp.arange(S)[None, :]
        mask = jnp.arange(T)[None, None, :] <= positions[:, :, None]
        if case == "window":
            mask = mask & (jnp.arange(T)[None, None, :] > positions[:, :, None] - 8)
        mask = jnp.broadcast_to(mask, (2, S, T))
        with force_kernels("interpret"), record_attention_paths() as paths:
            assert prefill_attention.maybe_flash_prefill(q, kv["k"], kv["v"], 7, 1, **kw) is None
            got = cached_attention(q, kv, jnp.int32(1), mask=mask, start=jnp.int32(7), window=kw.get("window"))
        with force_kernels("off"):
            want = cached_attention(q, kv, jnp.int32(1), mask=mask, start=jnp.int32(7), window=kw.get("window"))
        assert paths == ["sliced"]
        np.testing.assert_array_equal(got, want)

    def test_cached_attention_takes_the_kernel_for_a_chunk_and_says_so(self):
        """The one entry the three families share: a chunk with a cursor and
        no window reads the stack in place under the kernel, with or without
        the caller's mask, and slices under "off"; both agree."""
        from accelerate_tpu.models.layers import (
            cached_attention, note_attention_path, record_attention_paths, traced_once_for,
        )

        q, k, v = _chunk_operands(jnp.float32, 1, 32, 256, K=2, group=2)
        kv = {"k": _stacked(k, 2, 3), "v": _stacked(v, 2, 3)}
        start = jnp.int32(100)
        with force_kernels("interpret"), record_attention_paths() as paths:
            got = cached_attention(q, kv, jnp.int32(2), start=start, q_block=8)
        with force_kernels("off"), record_attention_paths() as off:
            want = cached_attention(q, kv, jnp.int32(2), start=start, q_block=8)
        assert paths == ["in_place"] and off == ["sliced"]
        # A scan's caller says how many layers its body's one trace stands
        # for; nothing is read off the stacks, so a ring as long and as deep
        # as the full-length stack beside it is still counted on its own.
        ring = {name: jnp.zeros_like(buf) for name, buf in kv.items()}
        with force_kernels("interpret"), record_attention_paths() as paths:
            with traced_once_for(3):
                jax.lax.scan(
                    lambda c, _: (c + cached_attention(q, kv, jnp.int32(2), start=start).sum(), None),
                    jnp.zeros(()), None, length=3,
                )
                assert ring["k"].shape == kv["k"].shape
                note_attention_path("sliced")
            cached_attention(q, kv, jnp.int32(0), start=start)  # outside a scan: one a call
        assert (paths.count("in_place"), paths.count("sliced")) == (4, 3)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got, _chunk_reference(q, k, v, 100), rtol=2e-5, atol=2e-5)


# ====================================================== flash-decode attention
def _ref_decode(q, k, v, lengths):
    """`models.layers.dot_product_attention` semantics for the T=1 decode
    read: GQA reshape, fp32 logits/softmax at 1/sqrt(h), -1e30 length mask,
    probs cast to v.dtype before the value contraction."""
    B, _, H, h = q.shape
    T, K = k.shape[1], k.shape[2]
    group = H // K
    qf = q.astype(jnp.float32).reshape(B, 1, K, group, h)
    logits = jnp.einsum("bskgh,btkh->bkgst", qf, k.astype(jnp.float32))
    logits = logits / np.sqrt(h)
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1, 1), (B, 1))
    keep = jnp.arange(T)[None, :] < lens  # (B, T)
    logits = jnp.where(keep[:, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, 1, H, h).astype(q.dtype)


def _decode_operands(dtype, B=2, T=64, K=2, group=2, h=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, K * group, h), dtype)
    k = jax.random.normal(ks[1], (B, T, K, h), dtype)
    v = jax.random.normal(ks[2], (B, T, K, h), dtype)
    return q, k, v


def _stacked(x, layer=0, n_layers=1):
    """One layer's (B, T, K, h) keys or values (or (B, T, K) scales) as the
    kernel's operand: layer ``layer`` of an (L, B, T, K*h) stack whose other
    layers hold noise, so a wrong layer index cannot pass."""
    flat = x.reshape(x.shape[:2] + (-1,))
    noise = 3.0 * jax.random.normal(
        jax.random.PRNGKey(7), (n_layers,) + flat.shape, jnp.float32
    )
    return noise.astype(x.dtype).at[layer].set(flat)


class TestFlashDecode:
    def test_f32_parity_scalar_length(self):
        q, k, v = _decode_operands(jnp.float32)
        out = decode_attention.flash_decode(q, _stacked(k), _stacked(v), 48, interpret=True)
        ref = _ref_decode(q, k, v, 48)
        np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)

    def test_ragged_lengths_gqa(self):
        q, k, v = _decode_operands(jnp.float32, B=4, T=64, K=2, group=4)
        lengths = jnp.asarray([3, 17, 64, 40], jnp.int32)
        out = decode_attention.flash_decode(
            q, _stacked(k), _stacked(v), lengths, interpret=True
        )
        ref = _ref_decode(q, k, v, lengths)
        np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)

    @pytest.mark.parametrize("K, group", [(12, 2), (16, 1), (3, 3)])
    def test_kv_heads_in_sets_of_one_product(self, K, group):
        """Up to eight kv heads share a product (their queries block-diagonal
        in one operand); more are walked in sets, a count eight does not
        divide in sets of its largest divisor."""
        q, k, v = _decode_operands(jnp.float32, B=3, T=64, K=K, group=group)
        lengths = jnp.asarray([5, 64, 33], jnp.int32)
        out = decode_attention.flash_decode(q, _stacked(k), _stacked(v), lengths, interpret=True)
        np.testing.assert_allclose(out, _ref_decode(q, k, v, lengths), rtol=2e-6, atol=2e-6)

    def test_bf16_parity(self):
        q, k, v = _decode_operands(jnp.bfloat16)
        out = decode_attention.flash_decode(q, _stacked(k), _stacked(v), 40, interpret=True)
        ref = _ref_decode(q, k, v, 40)
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), rtol=2e-2, atol=2e-2
        )

    def test_int8_kv_dequant_in_kernel(self):
        from accelerate_tpu.models.layers import dequant_kv, quantize_kv

        q, k, v = _decode_operands(jnp.bfloat16, B=2, T=32)
        kq, ksc = quantize_kv(k)
        vq, vsc = quantize_kv(v)
        out = decode_attention.flash_decode(
            q,
            _stacked(kq),
            _stacked(vq),
            20,
            k_scale=_stacked(ksc),
            v_scale=_stacked(vsc),
            interpret=True,
        )
        ref = _ref_decode(
            q, dequant_kv(kq, ksc, q.dtype), dequant_kv(vq, vsc, q.dtype), 20
        )
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), rtol=3e-2, atol=3e-2
        )

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    @pytest.mark.parametrize("layer", [1, 2])
    def test_reads_layer_of_the_stack_with_per_row_cursors(self, kv_dtype, layer):
        """The kernel indexes the whole (L, B, T, K*h) stack by a traced
        layer index and masks by one cursor a row; the oracle is
        `dot_product_attention` over that layer alone."""
        from accelerate_tpu.models.layers import (
            dequant_kv, dot_product_attention, quantize_kv,
        )

        q, k, v = _decode_operands(jnp.bfloat16, B=4, T=64, K=2, group=4)
        lengths = jnp.asarray([1, 23, 64, 40], jnp.int32)
        scales = {}
        if kv_dtype == "int8":
            (k_in, ksc), (v_in, vsc) = quantize_kv(k), quantize_kv(v)
            scales = {"k_scale": _stacked(ksc, layer, 3), "v_scale": _stacked(vsc, layer, 3)}
            k, v = dequant_kv(k_in, ksc, q.dtype), dequant_kv(v_in, vsc, q.dtype)
        else:
            k_in, v_in = k, v
        out = jax.jit(
            lambda i: decode_attention.flash_decode(
                q, _stacked(k_in, layer, 3), _stacked(v_in, layer, 3), lengths, i,
                interpret=True, **scales,
            )
        )(jnp.int32(layer))
        mask = (jnp.arange(64)[None, :] < lengths[:, None])[:, None, :]
        ref = dot_product_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), rtol=3e-2, atol=3e-2
        )

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_cursors_around_a_block_edge_and_noise_past_them(self, kv_dtype):
        """SmallThinker's head layout (4 kv heads of 128 in 512 lanes, 7
        query heads each) at layer 2 of a stack of noise, one batch with
        cursors at 0, 1, either side of a block edge and the whole slot.
        Past every cursor the cache holds loud noise the oracle never sees:
        a block the index map keeps pointing at, or a dead row of a live
        block, must not reach the output."""
        from accelerate_tpu.models.layers import (
            dequant_kv, dot_product_attention, quantize_kv,
        )

        T, K, h, layer = 2048, 4, 128, 2
        blk = decode_attention.pick_block(T, K * h * (1 if kv_dtype == "int8" else 2))
        assert T // blk >= 2
        lengths = np.asarray([0, 1, blk - 1, blk, blk + 1, T], np.int32)
        q, k, v = _decode_operands(jnp.bfloat16, B=len(lengths), T=T, K=K, group=7, h=h)
        dead = (np.arange(T)[None, :] >= lengths[:, None])[:, :, None]  # (B, T, 1)

        def loud(x):
            past = dead.reshape(dead.shape + (1,) * (x.ndim - 3))
            return jnp.where(past, 100.0 * (1 + jnp.abs(x.astype(jnp.float32))), x).astype(x.dtype)

        scales = {}
        if kv_dtype == "int8":
            (k_q, ksc), (v_q, vsc) = quantize_kv(k), quantize_kv(v)
            k, v = dequant_kv(k_q, ksc, q.dtype), dequant_kv(v_q, vsc, q.dtype)
            k_in = jnp.where(dead[..., None], 127, k_q).astype(jnp.int8)
            v_in = jnp.where(dead[..., None], -127, v_q).astype(jnp.int8)
            scales = {"k_scale": _stacked(loud(ksc), layer, 3), "v_scale": _stacked(loud(vsc), layer, 3)}
        else:
            k_in, v_in = loud(k), loud(v)
        out = jax.jit(
            lambda i: decode_attention.flash_decode(
                q, _stacked(k_in, layer, 3), _stacked(v_in, layer, 3), jnp.asarray(lengths), i,
                interpret=True, **scales,
            )
        )(jnp.int32(layer))
        out = np.asarray(out.astype(jnp.float32))
        mask = (jnp.arange(T)[None, :] < lengths[:, None])[:, None, :]
        ref = np.asarray(dot_product_attention(q, k, v, mask=mask).astype(jnp.float32))
        np.testing.assert_allclose(out[1:], ref[1:], rtol=3e-2, atol=3e-2)
        assert not out[0].any()  # nothing attended: no block is computed, the row reads 0

    @pytest.mark.parametrize("blk", [128, 512])
    def test_rows_fetched_is_what_the_grid_fetches(self, blk, monkeypatch):
        """The engine's ``kv_rows_fetched_*`` arithmetic against the grid
        itself: one step a block, so a call copies the distinct (row, block)
        pairs of `live_steps`, which the K / V index maps read."""
        T, row_bytes = 2048, 1024
        monkeypatch.setenv("ATX_BLOCK_DECODE_ATTENTION", str(blk))
        assert decode_attention.pick_block(T, row_bytes) == blk
        lengths = np.asarray([0, 1, blk - 1, blk, blk + 1, 3 * blk, T - 1, T, T + 1], np.int32)
        n, row, block = decode_attention.live_steps(jnp.asarray(lengths), T, blk)
        assert row.shape == block.shape == (len(lengths) * (T // blk),)
        steps = list(zip(np.asarray(row)[: int(n)].tolist(), np.asarray(block)[: int(n)].tolist()))
        assert steps == sorted(set(steps))  # rows in order, a row's blocks in order, none twice
        by_row = [sum(1 for r, _ in steps if r == b) for b in range(len(lengths))]
        assert by_row == [1, 1, 1, 1, 2, 3, T // blk, T // blk, T // blk]
        assert decode_attention.rows_fetched(lengths, T, row_bytes) == len(steps) * blk
        # Every slot full: the whole layer, as the sliced lowering reads it.
        assert decode_attention.rows_fetched(np.full(7, T), T, row_bytes) == 7 * T

    def test_unsupported_shapes_fall_back(self):
        q, k, v = _decode_operands(jnp.float32, T=12)  # 12 has no block divisor
        k, v = _stacked(k), _stacked(v)
        assert not decode_attention.supported(q, k)
        with force_kernels("interpret"):
            assert decode_attention.maybe_flash_decode(q, k, v, 8) is None
        # T_new > 1 (prefill) is never this kernel's shape.
        q2 = jnp.zeros((2, 3, 4, 16), jnp.float32)
        assert not decode_attention.supported(q2, jnp.zeros((1, 2, 64, 2 * 16)))
        # A last axis that is not whole heads of q's width is not a cache.
        q3 = jnp.zeros((2, 1, 4, 16), jnp.float32)
        assert decode_attention.supported(q3, jnp.zeros((1, 2, 64, 2 * 16)))
        assert not decode_attention.supported(q3, jnp.zeros((1, 2, 64, 2 * 16 + 8)))

    def test_forward_with_cache_off_is_byte_identical_to_default(self):
        # On this backend the code's own choice is the fallback, so forcing
        # "off" must change NOTHING.
        from accelerate_tpu.models import llama

        config = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 16), 0, config.vocab_size, jnp.int32
        )

        def run():
            cache = llama.init_cache(config, 2, 64)
            logits, cache = jax.jit(
                lambda p, t, c: llama.forward_with_cache(p, t, c, config)
            )(params, tokens, cache)
            tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
            logits, _ = jax.jit(
                lambda p, t, c: llama.forward_with_cache(p, t, c, config)
            )(params, tok, cache)
            return np.asarray(logits)

        base = run()
        with force_kernels("off"):
            off = run()
        assert np.array_equal(base, off)

    def test_forward_with_cache_interpret_matches_off(self):
        from accelerate_tpu.models import llama

        config = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 16), 0, config.vocab_size, jnp.int32
        )

        def run(cache_dtype):
            cache = llama.init_cache(config, 2, 64, dtype=cache_dtype)
            logits, cache = jax.jit(
                lambda p, t, c: llama.forward_with_cache(p, t, c, config)
            )(params, tokens, cache)
            tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
            logits, _ = jax.jit(
                lambda p, t, c: llama.forward_with_cache(p, t, c, config)
            )(params, tok, cache)
            return np.asarray(logits, np.float32)

        for cache_dtype in (jnp.float32, jnp.int8):
            with force_kernels("off"):
                ref = run(cache_dtype)
            with force_kernels("interpret"):
                out = run(cache_dtype)
            np.testing.assert_allclose(out, ref, rtol=5e-5, atol=5e-5)


    @pytest.mark.parametrize("kernels", ["off", "interpret"])
    @pytest.mark.parametrize("cache_len", [64, 4096])  # the lengths the old layout switch separated
    def test_per_row_cursor_decode_matches_forward(self, cache_len, kernels):
        """The engine's decode contract on the one cache layout: after a
        prefill, rows at DIFFERENT cursors each append one token a step (a
        row scatter into the carried stack) and must see the cache-free
        forward's logits at their own positions - through the sliced
        lowering and through the in-place flash-decode kernel alike."""
        from accelerate_tpu.models import llama

        config = llama.LlamaConfig.tiny(vocab_size=97, max_seq_len=8192)
        params = llama.init(jax.random.PRNGKey(0), config)
        tok = jnp.asarray(np.arange(24, dtype=np.int32).reshape(2, 12) % 97)
        want = np.asarray(llama.forward(params, tok, config))
        step = jax.jit(lambda p, t, c: llama.forward_with_cache(p, t, c, config))
        cursors = np.asarray([7, 4])  # row 1 rewinds: it re-appends tokens 4.. at 4..
        with force_kernels(kernels):
            cache = llama.init_cache(config, 2, cache_len, dtype=jnp.float32)
            _, cache = step(params, tok[:, :7], cache)
            cache = dict(cache, length=jnp.asarray(cursors, jnp.int32))
            for t in range(5):
                new = tok[np.arange(2), cursors + t][:, None]
                got, cache = step(params, new, cache)
                np.testing.assert_allclose(
                    np.asarray(got[:, 0]), want[np.arange(2), cursors + t],
                    atol=2e-5, rtol=2e-5,
                )
        np.testing.assert_array_equal(np.asarray(cache["length"]), cursors + 5)


# ========================================================== quantized matmul
class TestQuantMatmul:
    def test_parse_rejects_non_matmul_equations(self):
        parse = quant_matmul._parse_matmul_eq
        assert parse("bij,bjk->bik") is None  # shared batch label
        assert parse("ij,jk->ki") is None  # out != a_rest + b_rest
        assert parse("ij,kl->ijkl") is None  # no contraction
        assert parse("ij,jk->ik") == ("trail", "lead", 1, 1)
        assert parse("ki,kj->ij") == ("lead", "lead", 1, 1)

    def test_int8_kernel_near_bitwise_parity(self):
        from accelerate_tpu.ops import int8 as int8_ops

        eq = "bsd,df->bsf"
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(3), (64, 48), jnp.float32)
        wq, wsc = int8_ops.quantize_act(w, (0,))
        out = quant_matmul.int8_matmul_fused(eq, x, wq, wsc, interpret=True)
        assert out is not None and out.shape == (2, 16, 48)
        with force_kernels("off"):
            ref = int8_ops.int8_einsum(eq, x, wq, wsc)
        # Integer accumulation is exact; only the activation-scale divide
        # (TPU reciprocal semantics in-kernel) can differ, by 1 ulp.
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_int8_einsum_dispatches_under_interpret(self):
        from accelerate_tpu.ops import int8 as int8_ops

        eq = "sd,df->sf"
        x = jax.random.normal(jax.random.PRNGKey(4), (8, 32), jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(5), (32, 16), jnp.float32)
        wq, wsc = int8_ops.quantize_act(w, (0,))
        with force_kernels("off"):
            ref = int8_ops.int8_einsum(eq, x, wq, wsc)
        with force_kernels("interpret"):
            out = jax.jit(lambda x: int8_ops.int8_einsum(eq, x, wq, wsc))(x)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=1e-2, atol=1e-2,  # bf16 output rounding on top of the 1 ulp
        )

    # The seven quantized contractions of a decoder layer, as a small layer
    # stack: (equation, activation shape, one layer's weight shape, does the
    # kernel take the stack in place). Per-head weights (d, h, k) share one
    # scale a head_dim channel; as (d, h * k) they are a relayout, so the
    # kernel declines their stack; (h, k, d) is (h * k, d) as it lies.
    STACKED = {
        "wq": ("bsd,dhk->bshk", (2, 8, 64), (64, 4, 32), False),
        "wk": ("bsd,dhk->bshk", (2, 8, 64), (64, 2, 32), False),
        "wv": ("bsd,dhk->bshk", (2, 8, 64), (64, 2, 32), False),
        "wo": ("bshk,hkd->bsd", (2, 8, 4, 32), (4, 32, 64), True),
        "w_gate": ("bsd,df->bsf", (2, 8, 64), (64, 128), True),
        "w_up": ("bsd,df->bsf", (2, 8, 64), (64, 128), True),
        "w_down": ("bsf,fd->bsd", (2, 8, 128), (128, 64), True),
    }

    @pytest.mark.parametrize("name", list(STACKED))
    def test_int8_kernel_reads_a_layer_stack_in_place(self, name):
        """The kernel handed the whole stack and a traced layer index inside
        `lax.scan` against the same kernel handed each layer sliced out:
        bitwise, per-head scales included."""
        from accelerate_tpu.utils.quantization import quantize_array

        eq, x_shape, w_shape, in_place = self.STACKED[name]
        layers = 3
        x = jax.random.normal(jax.random.PRNGKey(0), x_shape, jnp.bfloat16)
        node = quantize_array(jax.random.normal(jax.random.PRNGKey(1), (layers,) + w_shape))
        stack, scales = node["__quant__"], node["scale"]
        assert scales.shape[0] == layers and scales.shape[-1] == w_shape[-1]

        def whole_stack(i, scale):
            return quant_matmul.int8_matmul_fused(eq, x, stack, scale, i, interpret=True)

        if not in_place:
            assert whole_stack(jnp.int32(1), scales[1]) is None
            return

        @jax.jit
        def scanned(stack, scales):
            body = lambda i, scale: (i + 1, whole_stack(i, scale))
            return jax.lax.scan(body, jnp.zeros((), jnp.int32), scales)[1]

        got = scanned(stack, scales)
        for i in range(layers):
            want = quant_matmul.int8_matmul_fused(eq, x, stack[i], scales[i], interpret=True)
            np.testing.assert_array_equal(
                np.asarray(got[i], np.float32), np.asarray(want, np.float32)
            )
        assert not np.array_equal(np.asarray(got[0], np.float32), np.asarray(got[1], np.float32))

    def test_one_row_tile_takes_weight_tiles_up_to_the_budget(self):
        """A call whose rows are one tile reads every weight byte once, from
        HBM: its weight tile is its DMA and is sized from the shapes up to
        the budget. Two row tiles (a 1024-row chunk) keep `pick_block`'s."""
        w = jax.ShapeDtypeStruct((4096, 14336), jnp.int8)
        plan = lambda rows: quant_matmul._plan(
            "mc,cn->mn", jax.ShapeDtypeStruct((rows, 4096), jnp.bfloat16), w, jnp.bfloat16
        )
        assert plan(32)[5:8] == (32, 3584, 1024)
        assert plan(256)[5:8] == (256, 2048, 1024)
        assert plan(1024)[5:8] == (512, 512, 1024)
        down = quant_matmul._plan(
            "mc,cn->mn", jax.ShapeDtypeStruct((32, 14336), jnp.bfloat16),
            jax.ShapeDtypeStruct((14336, 4096), jnp.int8), jnp.bfloat16,
        )
        assert down[5:8] == (32, 4096, 1024)

    def test_scaled_matmul_matches_reference_all_orientations(self):
        f8 = jnp.float8_e4m3fn
        for eq, ashape, bshape in (
            ("ij,jk->ik", (32, 64), (64, 16)),
            ("ki,kj->ij", (64, 32), (64, 16)),
            ("ik,jk->ij", (32, 64), (16, 64)),
        ):
            qa = jax.random.normal(jax.random.PRNGKey(6), ashape).astype(f8)
            qb = jax.random.normal(jax.random.PRNGKey(7), bshape).astype(f8)
            scale = jnp.float32(0.37)
            out = quant_matmul.scaled_matmul(
                eq, qa, qb, scale, jnp.bfloat16, interpret=True
            )
            ref = (
                jnp.einsum(eq, qa, qb, preferred_element_type=jnp.float32) * scale
            ).astype(jnp.bfloat16)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref, np.float32),
                rtol=1e-5, atol=1e-5,
            )

    def test_fp8_einsum_fwd_and_bwd_match_fallback(self):
        from accelerate_tpu.ops import fp8 as fp8_ops

        x = jax.random.normal(jax.random.PRNGKey(8), (16, 64), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(9), (64, 32), jnp.float32)

        def loss(x, w):
            with fp8_ops.fp8_matmuls(True):
                return jnp.sum(fp8_ops.matmul_einsum("ij,jk->ik", x, w) ** 2)

        with force_kernels("off"):
            ref, (rgx, rgw) = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        with force_kernels("interpret"):
            out, (gx, gw) = jax.jit(
                jax.value_and_grad(loss, argnums=(0, 1))
            )(x, w)
        np.testing.assert_allclose(out, ref, rtol=1e-5)
        np.testing.assert_allclose(gx, rgx, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(gw, rgw, rtol=1e-5, atol=1e-5)


# =============================================================== fused AdamW
class TestFusedAdamW:
    def _leaf(self, n, dtype=jnp.float32, seed=10):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        g = (jax.random.normal(ks[0], (n,)) * 1e-2).astype(dtype)
        mu = jax.random.normal(ks[1], (n,)) * 1e-3
        nu = jnp.abs(jax.random.normal(ks[2], (n,))) * 1e-6
        p = jax.random.normal(ks[3], (n,))
        return g, mu, nu, p

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("with_scale", [False, True])
    def test_parity_vs_adamw_slice(self, dtype, with_scale):
        from accelerate_tpu.parallel import host_offload

        g, mu, nu, p = self._leaf(2048, dtype)
        args = (g, mu, nu, p, jnp.asarray(7.0), 1e-3, 0.9, 0.999, 1e-8, 1e-4)
        scale = jnp.asarray(0.5) if with_scale else None
        out = fused_adamw.fused_adamw_update(*args, scale, interpret=True)
        assert out is not None
        with force_kernels("off"):
            ref = host_offload._adamw_slice(*args, grad_scale=scale)
        for a, b in zip(out, ref):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-6, atol=1e-7,
            )

    def test_tiny_leaf_falls_back(self):
        g, mu, nu, p = self._leaf(24)
        out = fused_adamw.fused_adamw_update(
            g, mu, nu, p, jnp.asarray(1.0), 1e-3, 0.9, 0.999, 1e-8, 0.0
        )
        assert out is None

    def test_adamw_slice_dispatches_under_interpret(self):
        from accelerate_tpu.parallel import host_offload

        g, mu, nu, p = self._leaf(4096)
        args = (g, mu, nu, p, jnp.asarray(3.0), 1e-3, 0.9, 0.999, 1e-8, 1e-4)
        with force_kernels("off"):
            ref = host_offload._adamw_slice(*args)
        with force_kernels("interpret"):
            # Hyperparams stay Python floats under jit (the optimizer's real
            # calling convention); count/lr could be traced.
            out = jax.jit(
                lambda g, mu, nu, p, c: host_offload._adamw_slice(
                    g, mu, nu, p, c, 1e-3, 0.9, 0.999, 1e-8, 1e-4
                )
            )(g, mu, nu, p, jnp.asarray(3.0))
        for a, b in zip(out, ref):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        # Traced hyperparams can't be baked into the kernel: dispatch must
        # fall back (None), not crash.
        with force_kernels("interpret"):
            traced = jax.jit(lambda *a: host_offload._adamw_slice(*a))(*args)
        for a, b in zip(traced, ref):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# ================================================================= ATX lint
class TestKernelLint:
    def test_decode_step_has_no_new_donation_or_sync_findings(self):
        from accelerate_tpu import analysis
        from accelerate_tpu.generation import GenerationConfig
        from accelerate_tpu.models import llama
        from accelerate_tpu.serving import Engine

        config = llama.LlamaConfig.tiny(vocab_size=128, max_seq_len=128)
        params = llama.init(jax.random.PRNGKey(0), config)
        with force_kernels("interpret"):
            engine = Engine(
                lambda p, t, c: llama.forward_with_cache(p, t, c, config),
                lambda b, m: llama.init_cache(config, b, m),
                params,
                GenerationConfig(eos_token_id=0),
                slots=4,
                buckets=(16,),
                max_len=96,
            )
            report = analysis.lint_step(
                engine._decode_fn,
                *engine.abstract_decode_args(),
                donate_argnums=(3,),
                target="kernels.decode",
            )
        bad = [
            f
            for f in report.findings
            if f.rule_id.startswith("ATX2") or f.rule_id.startswith("ATX3")
        ]
        assert bad == [], [f.format() for f in bad]

    def test_train_step_has_no_new_donation_or_sync_findings(self):
        import numpy as onp

        from accelerate_tpu import analysis
        from accelerate_tpu.accelerator import Accelerator
        from accelerate_tpu.models import gpt
        from accelerate_tpu.parallel import host_offload
        from accelerate_tpu.state import AcceleratorState

        AcceleratorState._reset_state()
        acc = Accelerator(seed=0, mixed_precision="bf16", max_grad_norm=1.0)
        config = gpt.GPTConfig(
            vocab_size=128, d_model=64, n_layers=2, num_heads=4, d_ff=128,
            max_seq_len=32,
        )
        batch = {"input_ids": onp.zeros((8, 32), onp.int32)}
        with force_kernels("interpret"):
            report = analysis.lint_training(
                acc,
                lambda r: gpt.init(r, config),
                host_offload.host_offloaded_adamw(3e-3),
                lambda params, b, rng: gpt.loss_fn(params, b, config, rng),
                batch,
                target="kernels.train",
            )
        bad = [
            f
            for f in report.findings
            if f.rule_id.startswith("ATX2") or f.rule_id.startswith("ATX3")
        ]
        assert bad == [], [f.format() for f in bad]
