"""Attention kernel tests: flash (Pallas, interpret mode on CPU) and ring
(shard_map over the sequence axis) against the XLA oracle
(`models/layers.py:dot_product_attention`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.heavy  # compile-heavy / subprocess lane

from accelerate_tpu import MeshConfig
from accelerate_tpu.models.layers import dot_product_attention
from accelerate_tpu.ops.flash_attention import flash_attention
from accelerate_tpu.ops.ring_attention import ring_attention
from accelerate_tpu.parallel.mesh import build_mesh, use_mesh


def _qkv(rng, B=2, S=128, H=4, K=2, h=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, S, H, h), dtype)
    k = jax.random.normal(kk, (B, S, K, h), dtype)
    v = jax.random.normal(kv, (B, S, K, h), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_oracle(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        expected = dot_product_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_size=64, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_mha_no_gqa(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), H=4, K=4)
        expected = dot_product_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_size=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_gradients_match_oracle(self):
        q, k, v = _qkv(jax.random.PRNGKey(2), B=1, S=64, H=4, K=2, h=16)
        w = jax.random.normal(jax.random.PRNGKey(3), q.shape)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, block_size=32, interpret=True) * w)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) * w)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
            )

    def test_mask_falls_back_to_oracle(self):
        q, k, v = _qkv(jax.random.PRNGKey(4), S=32)
        mask = jnp.ones((2, 32), jnp.int32).at[:, 20:].set(0)
        out = flash_attention(q, k, v, causal=True, segment_mask=mask, interpret=True)
        expected = dot_product_attention(q, k, v, mask=mask, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-6)

    def test_odd_length_falls_back(self):
        q, k, v = _qkv(jax.random.PRNGKey(5), S=100)
        out = flash_attention(q, k, v, causal=True, block_size=64, interpret=True)
        expected = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-6)

    def test_bf16_inputs(self):
        q, k, v = _qkv(jax.random.PRNGKey(6), dtype=jnp.bfloat16)
        expected = dot_product_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_size=64, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expected, np.float32), atol=2e-2, rtol=2e-2
        )


class TestBlockedKernels:
    """The long-context path: KV blocked through the grid with scratch
    carries. Forced by zeroing the resident budget; numerics must match the
    oracle exactly as the resident path does."""

    def _force_blocked(self, monkeypatch):
        from accelerate_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "_RESIDENT_KV_BUDGET", 0)

    def test_forward_matches_oracle(self, monkeypatch):
        self._force_blocked(monkeypatch)
        q, k, v = _qkv(jax.random.PRNGKey(3), B=2, S=256, H=4, K=2, h=32)
        for causal in (True, False):
            expected = dot_product_attention(q, k, v, causal=causal)
            out = flash_attention(q, k, v, causal=causal, block_size=64)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5
            )

    def test_grads_match_oracle(self, monkeypatch):
        self._force_blocked(monkeypatch)
        q, k, v = _qkv(jax.random.PRNGKey(4), B=1, S=128, H=4, K=2, h=32)
        w = jax.random.normal(jax.random.PRNGKey(5), q.shape)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) * w)

        g_flash = jax.grad(
            loss(lambda q, k, v, causal: flash_attention(q, k, v, causal=causal, block_size=64)),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_ref = jax.grad(
            loss(lambda q, k, v, causal: dot_product_attention(q, k, v, causal=causal)),
            argnums=(0, 1, 2),
        )(q, k, v)
        for gf, ge, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(ge), atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
            )

    def test_padded_seq_len(self, monkeypatch):
        self._force_blocked(monkeypatch)
        # S not a block multiple: the padding path under the blocked kernels.
        q, k, v = _qkv(jax.random.PRNGKey(6), B=1, S=100, H=2, K=2, h=16)
        expected = dot_product_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_size=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)


class TestRingAttention:
    @pytest.mark.parametrize("seq_shards", [2, 4, 8])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_oracle(self, seq_shards, causal):
        mesh = build_mesh(MeshConfig(data=-1, sequence=seq_shards))
        q, k, v = _qkv(jax.random.PRNGKey(7), B=2, S=64, H=4, K=2, h=16)
        expected = dot_product_attention(q, k, v, causal=causal)
        out = ring_attention(q, k, v, causal=causal, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_inside_jit(self):
        mesh = build_mesh(MeshConfig(data=1, sequence=8))
        q, k, v = _qkv(jax.random.PRNGKey(8), B=1, S=64, H=4, K=4, h=16)
        expected = dot_product_attention(q, k, v, causal=True)
        out = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=True, mesh=mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_fused_forward_matches_oracle(self):
        # Fused path: Pallas flash kernel per ring chunk (128-aligned chunks).
        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        q, k, v = _qkv(jax.random.PRNGKey(20), B=2, S=512, H=4, K=2, h=32)
        for causal in (True, False):
            expected = dot_product_attention(q, k, v, causal=causal)
            out = ring_attention(q, k, v, causal=causal, mesh=mesh, impl="fused")
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(expected), atol=3e-5, rtol=3e-5
            )

    def test_fused_grads_match_oracle(self):
        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        q, k, v = _qkv(jax.random.PRNGKey(21), B=1, S=512, H=4, K=2, h=32)
        w = jax.random.normal(jax.random.PRNGKey(22), q.shape)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, causal=True, mesh=mesh, impl="fused") * w)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) * w)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gr, ge, name in zip(g_ring, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gr), np.asarray(ge), atol=2e-3, rtol=2e-3, err_msg=f"d{name}"
            )

    def test_auto_picks_fused_when_aligned(self):
        # auto == fused for aligned no-mask inputs; equals einsum numerically.
        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        q, k, v = _qkv(jax.random.PRNGKey(23), B=1, S=512, H=2, K=2, h=16)
        auto = ring_attention(q, k, v, causal=True, mesh=mesh)
        einsum = ring_attention(q, k, v, causal=True, mesh=mesh, impl="einsum")
        np.testing.assert_allclose(np.asarray(auto), np.asarray(einsum), atol=3e-5, rtol=3e-5)

    def test_fused_rejects_mask_and_ragged(self):
        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        q, k, v = _qkv(jax.random.PRNGKey(24), B=1, S=512, H=2, K=2, h=16)
        with pytest.raises(NotImplementedError, match="kv_mask"):
            ring_attention(q, k, v, mesh=mesh, impl="fused", kv_mask=jnp.ones((1, 512)))
        q2, k2, v2 = _qkv(jax.random.PRNGKey(25), B=1, S=64, H=2, K=2, h=16)
        with pytest.raises(ValueError, match="multiple of 128"):
            ring_attention(q2, k2, v2, mesh=mesh, impl="fused")

    def test_padding_mask_matches_oracle(self):
        # (B, S) key-padding mask rotates around the ring with its kv chunk.
        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        q, k, v = _qkv(jax.random.PRNGKey(11), B=2, S=64, H=4, K=2, h=16)
        lengths = jnp.array([40, 64])
        mask = (jnp.arange(64)[None, :] < lengths[:, None]).astype(jnp.int32)
        for causal in (True, False):
            expected = dot_product_attention(q, k, v, mask=mask, causal=causal)
            out = ring_attention(q, k, v, causal=causal, kv_mask=mask, mesh=mesh)
            # compare only real (unpadded) query rows; padded rows are
            # masked out of any loss by construction
            for b, L in enumerate([40, 64]):
                np.testing.assert_allclose(
                    np.asarray(out[b, :L]), np.asarray(expected[b, :L]),
                    atol=2e-5, rtol=2e-5,
                )

    def test_llama_ring_with_padding_mask(self):
        from accelerate_tpu.models import llama

        cfg_ring = llama.LlamaConfig.tiny(attention_impl="ring")
        cfg_dot = llama.LlamaConfig.tiny(attention_impl="dot")
        params = llama.init(jax.random.PRNGKey(0), cfg_ring)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg_ring.vocab_size)
        mask = (jnp.arange(64)[None, :] < jnp.array([48, 64])[:, None]).astype(jnp.int32)
        out_ring = llama.forward(params, tokens, cfg_ring, mask=mask)
        out_dot = llama.forward(params, tokens, cfg_dot, mask=mask)
        np.testing.assert_allclose(
            np.asarray(out_ring[0, :48]), np.asarray(out_dot[0, :48]), atol=2e-4, rtol=2e-4
        )

    def test_differentiable(self):
        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        q, k, v = _qkv(jax.random.PRNGKey(9), B=1, S=32, H=2, K=2, h=16)
        w = jax.random.normal(jax.random.PRNGKey(10), q.shape)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, causal=True, mesh=mesh) * w)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) * w)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gr, ge, name in zip(g_ring, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gr), np.asarray(ge), atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
            )


class TestUlyssesAttention:
    """All-to-all sequence parallelism (ops/ulysses.py): exact full-sequence
    attention over head slices between two all-to-alls."""

    @pytest.mark.parametrize("seq_shards", [2, 4])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_oracle(self, seq_shards, causal):
        from accelerate_tpu.ops.ulysses import ulysses_attention

        mesh = build_mesh(MeshConfig(data=-1, sequence=seq_shards))
        q, k, v = _qkv(jax.random.PRNGKey(30), B=2, S=64, H=4, K=4, h=16)
        expected = dot_product_attention(q, k, v, causal=causal)
        out = ulysses_attention(q, k, v, causal=causal, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_gqa_and_jit(self):
        from accelerate_tpu.ops.ulysses import ulysses_attention

        mesh = build_mesh(MeshConfig(data=4, sequence=2))
        q, k, v = _qkv(jax.random.PRNGKey(31), B=4, S=64, H=4, K=2, h=16)
        expected = dot_product_attention(q, k, v, causal=True)
        out = jax.jit(lambda q, k, v: ulysses_attention(q, k, v, causal=True, mesh=mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_grads_match_oracle(self):
        from accelerate_tpu.ops.ulysses import ulysses_attention

        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        q, k, v = _qkv(jax.random.PRNGKey(32), B=2, S=128, H=4, K=4, h=16)
        w = jax.random.normal(jax.random.PRNGKey(33), q.shape)

        def loss_u(q, k, v):
            return jnp.sum(ulysses_attention(q, k, v, causal=True, mesh=mesh) * w)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) * w)

        g_u = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_u, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)

    def test_padding_mask(self):
        from accelerate_tpu.ops.ulysses import ulysses_attention

        mesh = build_mesh(MeshConfig(data=-1, sequence=4))
        q, k, v = _qkv(jax.random.PRNGKey(34), B=2, S=64, H=4, K=4, h=16)
        mask = jnp.ones((2, 64), jnp.int32).at[:, 48:].set(0)
        expected = dot_product_attention(q, k, v, mask=mask, causal=False)
        out = ulysses_attention(q, k, v, causal=False, kv_mask=mask, mesh=mesh)
        np.testing.assert_allclose(
            np.asarray(out[:, :48]), np.asarray(expected[:, :48]), atol=2e-5, rtol=2e-5
        )

    def test_indivisible_heads_rejected(self):
        from accelerate_tpu.ops.ulysses import ulysses_attention

        mesh = build_mesh(MeshConfig(data=-1, sequence=8))
        q, k, v = _qkv(jax.random.PRNGKey(35), B=1, S=64, H=4, K=2, h=16)
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, k, v, mesh=mesh)


def test_llama_ulysses_matches_dot():
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.models import llama

    AcceleratorState._reset_state()
    mesh = build_mesh(MeshConfig(data=2, sequence=4))
    config = llama.LlamaConfig.tiny()
    config_u = llama.LlamaConfig.tiny(attention_impl="ulysses")
    params = llama.init(jax.random.PRNGKey(0), config)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, config.vocab_size, jnp.int32)
    expected = llama.forward(params, tokens, config)
    out = llama.forward(params, tokens, config_u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=3e-4, rtol=3e-4)


def test_flash_partitions_under_jit():
    """The pallas kernel must partition over batch/heads under plain jit
    (a shard_map over the ambient mesh) instead of being replicated as an
    opaque custom-call — the pod-scale failure tests/test_pod_aot.py documents.
    Numerics must match the oracle and the output must keep the batch
    sharding."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from accelerate_tpu.models.layers import dot_product_attention
    from accelerate_tpu.ops.flash_attention import flash_attention

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "tensor"))
    B, S, H, K, h = 4, 64, 4, 2, 32
    k0 = jax.random.PRNGKey(0)
    q = jax.random.normal(k0, (B, S, H, h), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.float32)
    bsh = NamedSharding(mesh, PartitionSpec("data", None, "tensor", None))
    kvsh = NamedSharding(mesh, PartitionSpec("data", None, "tensor", None))
    qd = jax.device_put(q, bsh)
    kd = jax.device_put(k, kvsh)
    vd = jax.device_put(v, kvsh)

    with use_mesh(mesh):
        out = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True))(qd, kd, vd)
    expected = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-3, rtol=2e-2)
    # Batch stayed sharded (no silent all-gather of the activations).
    assert "data" in str(out.sharding.spec), out.sharding

    # Gradients flow through the partitioned backward too.
    def loss(a, b, c):
        return jnp.sum(flash_attention(a, b, c, causal=True) ** 2)

    with use_mesh(mesh):
        g = jax.jit(jax.grad(loss))(qd, kd, vd)
    g_ref = jax.grad(lambda a, b, c: jnp.sum(dot_product_attention(a, b, c, causal=True) ** 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-3, rtol=5e-2)


class TestSlidingWindowKernel:
    """In-kernel sliding-window attention (band tile skipping): numerics
    must match the oracle with the band mask, on both kernel paths."""

    def _ref(self, q, k, v, window):
        from accelerate_tpu.models.layers import dot_product_attention

        S = q.shape[1]
        band = (jnp.arange(S)[:, None] - jnp.arange(S)[None, :]) < window
        mask = jnp.broadcast_to(band, (q.shape[0], S, S))
        return dot_product_attention(q, k, v, mask=mask, causal=True)

    @pytest.mark.parametrize("S,window", [(128, 32), (256, 64), (256, 200)])
    def test_matches_banded_oracle(self, S, window):
        from accelerate_tpu.ops.flash_attention import flash_attention

        B, H, K, h = 2, 4, 2, 32
        k0 = jax.random.PRNGKey(3)
        q = jax.random.normal(k0, (B, S, H, h), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=window)
        ref = self._ref(q, k, v, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-2)
        # And the window actually changes the result vs full causal.
        if window < S:
            full = flash_attention(q, k, v, causal=True)
            assert np.abs(np.asarray(out) - np.asarray(full)).max() > 1e-3

    @pytest.mark.parametrize("block", [64, 128, 256])
    def test_blocked_path_matches_banded_oracle(self, monkeypatch, block):
        """Small blocks force window_grid=True (the banded KV grid): the
        left-edge tiles with clamped fetches must be fully masked — the
        review repro that double-counted block-0 keys."""
        from accelerate_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "_use_resident", lambda *a: False)
        B, S, H, K, h, window = 1, 256, 2, 2, 32, 96
        k0 = jax.random.PRNGKey(4)
        q = jax.random.normal(k0, (B, S, H, h), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.float32)
        out = fa.flash_attention(
            q, k, v, causal=True, window=window, block_size=block
        )
        ref = self._ref(q, k, v, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-2)

    def test_decode_fallback_bands_by_absolute_position(self):
        """S != T (KV-cache decode) fallback: the window anchors at the
        LAST T positions, not at row index 0 — otherwise single-token
        decode silently attends the whole cache."""
        from accelerate_tpu.models.layers import dot_product_attention
        from accelerate_tpu.ops.flash_attention import flash_attention

        B, T, H, K, h, window = 1, 128, 2, 2, 32, 32
        k0 = jax.random.PRNGKey(6)
        q = jax.random.normal(k0, (B, 1, H, h), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, T, K, h), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, T, K, h), jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=window)
        band = ((T - 1) - jnp.arange(T)[None, :] < window)[None]
        ref = dot_product_attention(
            q, k, v, mask=jnp.broadcast_to(band, (B, 1, T)), causal=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-2)
        full = dot_product_attention(q, k, v, causal=True)
        assert np.abs(np.asarray(out) - np.asarray(full)).max() > 1e-3

    def test_noncausal_resident_window(self):
        from accelerate_tpu.models.layers import dot_product_attention
        from accelerate_tpu.ops.flash_attention import flash_attention

        B, S, H, K, h, window = 1, 128, 2, 2, 32, 32
        k0 = jax.random.PRNGKey(7)
        q = jax.random.normal(k0, (B, S, H, h), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.float32)
        out = flash_attention(q, k, v, causal=False, window=window)
        band = (jnp.arange(S)[:, None] - jnp.arange(S)[None, :]) < window
        ref = dot_product_attention(
            q, k, v, mask=jnp.broadcast_to(band, (B, S, S)), causal=False
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-2)

    def test_llama_flash_window_with_positions_matches_dot(self):
        """Non-default positions band by POSITION: flash and dot must agree
        (flash folds to the mask path rather than the row-index kernel)."""
        import dataclasses as dc

        from accelerate_tpu.models import llama

        config = llama.LlamaConfig.tiny(
            max_seq_len=256, sliding_window=24, attention_impl="flash"
        )
        params = llama.init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, config.vocab_size)
        positions = 100 + jnp.broadcast_to(jnp.arange(64), (2, 64))
        got = llama.forward(params, tokens, config, positions=positions)
        want = llama.forward(
            params, tokens, dc.replace(config, attention_impl="dot"),
            positions=positions,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-3, rtol=2e-2
        )

    def test_windowed_backward_is_finite(self):
        from accelerate_tpu.ops.flash_attention import flash_attention

        B, S, H, h = 1, 64, 2, 32
        q = jax.random.normal(jax.random.PRNGKey(5), (B, S, H, h))
        g = jax.grad(
            lambda a: jnp.sum(flash_attention(a, a, a, causal=True, window=16) ** 2)
        )(q)
        assert np.isfinite(np.asarray(g)).all()

    def test_llama_flash_window_matches_dot(self):
        """The model-level wiring: flash in-kernel band == dot + mask."""
        import dataclasses as dc

        from accelerate_tpu.models import llama

        config = llama.LlamaConfig.tiny(
            max_seq_len=128, sliding_window=24, attention_impl="flash"
        )
        params = llama.init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, config.vocab_size)
        got = llama.forward(params, tokens, config)
        want = llama.forward(
            params, tokens, dc.replace(config, attention_impl="dot")
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-3, rtol=2e-2
        )


class TestSlidingWindowBackward:
    """Windowed flash BACKWARD: gradients must match the banded oracle on
    both kernel paths (resident and banded-grid blocked)."""

    def _grads(self, fn, q, k, v):
        return jax.grad(lambda a, b, c: jnp.sum(fn(a, b, c) ** 2), argnums=(0, 1, 2))(q, k, v)

    def _check(self, q, k, v, window, flash_fn):
        from accelerate_tpu.models.layers import dot_product_attention

        S = q.shape[1]
        band = (jnp.arange(S)[:, None] - jnp.arange(S)[None, :]) < window
        mask = jnp.broadcast_to(band, (q.shape[0], S, S))
        got = self._grads(flash_fn, q, k, v)
        want = self._grads(
            lambda a, b, c: dot_product_attention(a, b, c, mask=mask, causal=True),
            q, k, v,
        )
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-3, rtol=5e-2)

    def test_resident_grads_match_banded_oracle(self):
        from accelerate_tpu.ops.flash_attention import flash_attention

        B, S, H, K, h, window = 1, 128, 2, 2, 32, 48
        k0 = jax.random.PRNGKey(8)
        q = jax.random.normal(k0, (B, S, H, h), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.float32)
        self._check(q, k, v, window,
                    lambda a, b, c: flash_attention(a, b, c, causal=True, window=window))

    @pytest.mark.parametrize("block", [64, 128])
    def test_blocked_banded_grads_match_oracle(self, monkeypatch, block):
        from accelerate_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "_use_resident", lambda *a: False)
        B, S, H, K, h, window = 1, 256, 2, 2, 32, 96
        k0 = jax.random.PRNGKey(9)
        q = jax.random.normal(k0, (B, S, H, h), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.float32)
        self._check(
            q, k, v, window,
            lambda a, b, c: fa.flash_attention(
                a, b, c, causal=True, window=window, block_size=block
            ),
        )

    def test_llama_windowed_training_grads_match_dot(self):
        import dataclasses as dc

        from accelerate_tpu.models import llama

        config = llama.LlamaConfig.tiny(
            max_seq_len=128, sliding_window=24, attention_impl="flash"
        )
        params = llama.init(jax.random.PRNGKey(0), config)
        batch = {"input_ids": jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, config.vocab_size)}
        g_flash = jax.grad(lambda p: llama.loss_fn(p, batch, config))(params)
        g_dot = jax.grad(
            lambda p: llama.loss_fn(p, batch, dc.replace(config, attention_impl="dot"))
        )(params)
        for a, b in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_dot)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-2
            )


class TestUlyssesSlidingWindow:
    def test_matches_banded_oracle(self):
        from accelerate_tpu.ops.ulysses import ulysses_attention

        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        B, S, H, K, h, window = 2, 128, 4, 4, 16, 32
        k0 = jax.random.PRNGKey(30)
        q = jax.random.normal(k0, (B, S, H, h), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.float32)
        out = ulysses_attention(q, k, v, causal=True, mesh=mesh, window=window)
        band = (jnp.arange(S)[:, None] - jnp.arange(S)[None, :]) < window
        ref = dot_product_attention(
            q, k, v, mask=jnp.broadcast_to(band, (B, S, S)), causal=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-2)

    def test_llama_ulysses_window_matches_dot(self):
        import dataclasses as dc

        from accelerate_tpu.models import llama
        from accelerate_tpu.state import AcceleratorState

        AcceleratorState._reset_state()
        import accelerate_tpu as atx

        atx.Accelerator(seed=0, mesh_config=MeshConfig(data=2, sequence=4))
        config = llama.LlamaConfig.tiny(
            max_seq_len=128, sliding_window=24, attention_impl="ulysses",
            num_heads=4, num_kv_heads=4,
        )
        params = llama.init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, config.vocab_size)
        got = llama.forward(params, tokens, config)
        want = llama.forward(
            params, tokens, dc.replace(config, attention_impl="dot")
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-3, rtol=2e-2
        )
        AcceleratorState._reset_state()


class TestRingSlidingWindow:
    @pytest.mark.parametrize("seq_shards", [2, 4])
    def test_matches_banded_oracle(self, seq_shards):
        mesh = build_mesh(MeshConfig(data=-1, sequence=seq_shards))
        B, S, H, K, h, window = 2, 64, 4, 2, 16, 24
        k0 = jax.random.PRNGKey(31)
        q = jax.random.normal(k0, (B, S, H, h), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.float32)
        out = ring_attention(q, k, v, causal=True, mesh=mesh, window=window)
        band = (jnp.arange(S)[:, None] - jnp.arange(S)[None, :]) < window
        ref = dot_product_attention(
            q, k, v, mask=jnp.broadcast_to(band, (B, S, S)), causal=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_window_with_padding_mask(self):
        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        # window 24 (not 16): with keys >= 48 padded, every row keeps at
        # least one visible key — rows whose band and padding intersect to
        # the empty set have UNDEFINED attention in any implementation.
        B, S, H, K, h, window = 2, 64, 4, 2, 16, 24
        k0 = jax.random.PRNGKey(32)
        q = jax.random.normal(k0, (B, S, H, h), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.float32)
        pad = jnp.ones((B, S), jnp.int32).at[:, 48:].set(0)
        out = ring_attention(
            q, k, v, causal=True, mesh=mesh, window=window, kv_mask=pad
        )
        band = (jnp.arange(S)[:, None] - jnp.arange(S)[None, :]) < window
        full_mask = jnp.broadcast_to(band, (B, S, S)) & pad[:, None, :].astype(bool)
        ref = dot_product_attention(q, k, v, mask=full_mask, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_fused_with_window_refuses(self):
        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        q, k, v = _qkv(jax.random.PRNGKey(33), B=1, S=512, H=4, K=2, h=32)
        with pytest.raises(NotImplementedError, match="einsum"):
            ring_attention(q, k, v, causal=True, mesh=mesh, window=64, impl="fused")

    def test_grads_flow(self):
        mesh = build_mesh(MeshConfig(data=2, sequence=4))
        B, S, H, K, h, window = 1, 64, 4, 2, 16, 24
        k0 = jax.random.PRNGKey(34)
        q = jax.random.normal(k0, (B, S, H, h), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, K, h), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, K, h), jnp.float32)
        band = (jnp.arange(S)[:, None] - jnp.arange(S)[None, :]) < window
        mask = jnp.broadcast_to(band, (B, S, S))
        g_ring = jax.grad(
            lambda a: jnp.sum(ring_attention(a, k, v, causal=True, mesh=mesh, window=window) ** 2)
        )(q)
        g_ref = jax.grad(
            lambda a: jnp.sum(dot_product_attention(a, k, v, mask=mask, causal=True) ** 2)
        )(q)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref), atol=5e-4, rtol=5e-4)

    def test_llama_ring_window_matches_dot(self):
        import dataclasses as dc

        from accelerate_tpu.models import llama
        from accelerate_tpu.state import AcceleratorState

        AcceleratorState._reset_state()
        import accelerate_tpu as atx

        atx.Accelerator(seed=0, mesh_config=MeshConfig(data=2, sequence=4))
        config = llama.LlamaConfig.tiny(
            max_seq_len=128, sliding_window=24, attention_impl="ring"
        )
        params = llama.init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, config.vocab_size)
        got = llama.forward(params, tokens, config)
        want = llama.forward(
            params, tokens, dc.replace(config, attention_impl="dot")
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-3, rtol=2e-2
        )
        AcceleratorState._reset_state()
