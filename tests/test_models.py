"""Model-family tests.

Oracle pattern from the reference self-test (`test_utils/scripts/
test_script.py:454` `training_check`): the same model trained under different
sharding layouts must produce (numerically) identical results. Here that
collapses to: forward under DP / FSDP / TP / hybrid shardings on the 8-device
CPU mesh must match the replicated forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

pytestmark = [pytest.mark.heavy, pytest.mark.slow]  # model-zoo forward parity compiles; excluded from the tier-1 smoke lane

from accelerate_tpu import Accelerator, MeshConfig
from accelerate_tpu.models import bert, gpt, llama, t5, vit
from accelerate_tpu.parallel.sharding import ShardingStrategy, infer_param_specs, shard_pytree
from accelerate_tpu.parallel.tp import get_tp_plan
from accelerate_tpu.utils.dataclasses import ShardingStrategyType


def _llama_batch(rng, config, batch=8, seq=16):
    tokens = jax.random.randint(rng, (batch, seq), 0, config.vocab_size, jnp.int32)
    return {"input_ids": tokens}


class TestLlama:
    def test_forward_shape(self):
        config = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.PRNGKey(0), config)
        tokens = jnp.zeros((2, 8), jnp.int32)
        logits = llama.forward(params, tokens, config)
        assert logits.shape == (2, 8, config.vocab_size)

    def test_param_count_matches(self):
        config = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.PRNGKey(0), config)
        actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        assert actual == config.param_count()

    def test_causality(self):
        """Changing a future token must not change past logits."""
        config = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.PRNGKey(0), config)
        t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, config.vocab_size, jnp.int32)
        t2 = t1.at[0, -1].set((t1[0, -1] + 1) % config.vocab_size)
        l1 = llama.forward(params, t1, config)
        l2 = llama.forward(params, t2, config)
        np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)

    def test_loss_decreases_with_accelerator(self):
        config = llama.LlamaConfig.tiny()
        acc = Accelerator(mesh_config=MeshConfig(), seed=0)
        state = acc.create_train_state(
            lambda rng: llama.init(rng, config), optax.adam(1e-3)
        )
        step = acc.make_train_step(lambda p, b, r: llama.loss_fn(p, b, config, r))
        batch = _llama_batch(jax.random.PRNGKey(42), config)
        batch = {k: jax.device_put(v) for k, v in batch.items()}
        losses = []
        for _ in range(10):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]

    @pytest.mark.parametrize(
        "mesh_config,strategy",
        [
            (MeshConfig(), None),  # 8-way DP
            (MeshConfig(data=2, fsdp=4), "FSDP"),
            (MeshConfig(data=1, fsdp=2, tensor=4), "HYBRID"),
            (MeshConfig(data=2, tensor=4), "TENSOR_PARALLEL"),
        ],
    )
    def test_sharded_forward_matches_replicated(self, mesh_config, strategy):
        config = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, config.vocab_size, jnp.int32)
        expected = np.asarray(llama.forward(params, tokens, config), np.float32)

        acc = Accelerator(
            mesh_config=mesh_config,
            strategy=strategy,
            sharding_rules=get_tp_plan("llama") if strategy in ("HYBRID", "TENSOR_PARALLEL") else (),
        )
        spec = ShardingStrategy.resolve(
            strategy, rules=get_tp_plan("llama") if strategy in ("HYBRID", "TENSOR_PARALLEL") else ()
        )
        param_specs = infer_param_specs(jax.eval_shape(lambda: params), acc.mesh, spec)
        sharded = shard_pytree(params, param_specs, acc.mesh)
        out = jax.jit(lambda p, t: llama.forward(p, t, config))(sharded, tokens)
        np.testing.assert_allclose(np.asarray(out, np.float32), expected, atol=2e-4, rtol=2e-4)

    def test_tp_plan_actually_shards(self):
        config = llama.LlamaConfig.tiny()
        acc = Accelerator(
            mesh_config=MeshConfig(data=2, tensor=4),
            strategy="TENSOR_PARALLEL",
            sharding_rules=get_tp_plan("llama"),
        )
        state = acc.create_train_state(lambda rng: llama.init(rng, config), optax.sgd(1e-3))
        wq = state.params["blocks"]["attn"]["wq"]
        # 4-way tensor sharding over the head dim (dim 2 of (L, D, H, h)).
        assert len(wq.sharding.device_set) == 8
        shard_shape = wq.sharding.shard_shape(wq.shape)
        assert shard_shape[2] == wq.shape[2] // 4

    def test_remat_matches(self):
        config = llama.LlamaConfig.tiny()
        config_r = llama.LlamaConfig.tiny(remat=True)
        params = llama.init(jax.random.PRNGKey(0), config)
        batch = _llama_batch(jax.random.PRNGKey(3), config, batch=2, seq=8)
        g1 = jax.grad(lambda p: llama.loss_fn(p, batch, config))(params)
        g2 = jax.grad(lambda p: llama.loss_fn(p, batch, config_r))(params)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5), g1, g2)


class TestBert:
    def test_classify_shape(self):
        config = bert.BertConfig.tiny()
        params = bert.init(jax.random.PRNGKey(0), config)
        batch = {
            "input_ids": jnp.zeros((4, 16), jnp.int32),
            "attention_mask": jnp.ones((4, 16), jnp.int32),
        }
        logits = bert.classify(params, batch, config)
        assert logits.shape == (4, config.num_labels)

    def test_param_count_matches(self):
        config = bert.BertConfig.tiny()
        params = bert.init(jax.random.PRNGKey(0), config)
        actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        assert actual == config.param_count()

    def test_dropout_train_vs_eval(self):
        config = bert.BertConfig.tiny(dropout_rate=0.5)
        params = bert.init(jax.random.PRNGKey(0), config)
        batch = {"input_ids": jnp.zeros((2, 8), jnp.int32)}
        eval1 = bert.classify(params, batch, config)
        eval2 = bert.classify(params, batch, config)
        np.testing.assert_allclose(eval1, eval2)  # eval deterministic
        t1 = bert.classify(params, batch, config, rng=jax.random.PRNGKey(1))
        t2 = bert.classify(params, batch, config, rng=jax.random.PRNGKey(2))
        assert not np.allclose(t1, t2)  # dropout active under rng

    def test_padding_mask_ignored(self):
        """Padding tokens must not affect the [CLS] representation."""
        config = bert.BertConfig.tiny()
        params = bert.init(jax.random.PRNGKey(0), config)
        ids = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, config.vocab_size, jnp.int32)
        mask = jnp.ones((1, 16), jnp.int32).at[0, 8:].set(0)
        l1 = bert.classify(params, {"input_ids": ids, "attention_mask": mask}, config)
        ids2 = ids.at[0, 12].set((ids[0, 12] + 5) % config.vocab_size)
        l2 = bert.classify(params, {"input_ids": ids2, "attention_mask": mask}, config)
        np.testing.assert_allclose(l1, l2, atol=1e-5)

    def test_training_decreases_loss(self):
        config = bert.BertConfig.tiny()
        acc = Accelerator(mesh_config=MeshConfig(), seed=0, mixed_precision="no")
        state = acc.create_train_state(lambda rng: bert.init(rng, config), optax.adam(1e-3))
        step = acc.make_train_step(lambda p, b, r: bert.loss_fn(p, b, config, r))
        rng = jax.random.PRNGKey(7)
        batch = {
            "input_ids": jax.random.randint(rng, (8, 16), 0, config.vocab_size, jnp.int32),
            "attention_mask": jnp.ones((8, 16), jnp.int32),
            "labels": jax.random.randint(jax.random.PRNGKey(8), (8,), 0, config.num_labels, jnp.int32),
        }
        losses = []
        for _ in range(10):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]

    def test_tp_forward_matches(self):
        config = bert.BertConfig.tiny()
        params = bert.init(jax.random.PRNGKey(0), config)
        batch = {
            "input_ids": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, config.vocab_size, jnp.int32),
        }
        expected = np.asarray(bert.classify(params, batch, config), np.float32)
        acc = Accelerator(
            mesh_config=MeshConfig(data=4, tensor=2),
            strategy="TENSOR_PARALLEL",
            sharding_rules=get_tp_plan("bert"),
        )
        spec = ShardingStrategy.resolve("TENSOR_PARALLEL", rules=get_tp_plan("bert"))
        param_specs = infer_param_specs(jax.eval_shape(lambda: params), acc.mesh, spec)
        sharded = shard_pytree(params, param_specs, acc.mesh)
        out = jax.jit(lambda p, b: bert.classify(p, b, config))(sharded, batch)
        np.testing.assert_allclose(np.asarray(out, np.float32), expected, atol=2e-4, rtol=2e-4)


class TestGPT:
    def test_forward_shape_and_param_count(self):
        config = gpt.GPTConfig.tiny()
        params = gpt.init(jax.random.PRNGKey(0), config)
        actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        assert actual == config.param_count()
        logits = gpt.forward(params, jnp.zeros((2, 8), jnp.int32), config)
        assert logits.shape == (2, 8, config.vocab_size)

    def test_causality(self):
        config = gpt.GPTConfig.tiny()
        params = gpt.init(jax.random.PRNGKey(0), config)
        t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, config.vocab_size, jnp.int32)
        t2 = t1.at[0, -1].set((t1[0, -1] + 1) % config.vocab_size)
        l1 = gpt.forward(params, t1, config)
        l2 = gpt.forward(params, t2, config)
        np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)

    def test_untied_head(self):
        config = gpt.GPTConfig.tiny(tie_embeddings=False)
        params = gpt.init(jax.random.PRNGKey(0), config)
        assert "lm_head" in params
        logits = gpt.forward(params, jnp.zeros((1, 4), jnp.int32), config)
        assert logits.shape == (1, 4, config.vocab_size)

    def test_training_decreases_loss(self):
        config = gpt.GPTConfig.tiny()
        acc = Accelerator(mesh_config=MeshConfig(), seed=0)
        state = acc.create_train_state(lambda rng: gpt.init(rng, config), optax.adam(1e-3))
        step = acc.make_train_step(lambda p, b, r: gpt.loss_fn(p, b, config, r))
        batch = {
            "input_ids": jax.random.randint(
                jax.random.PRNGKey(42), (8, 16), 0, config.vocab_size, jnp.int32
            )
        }
        losses = []
        for _ in range(10):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]

    def test_tp_forward_matches_replicated(self):
        config = gpt.GPTConfig.tiny()
        params = gpt.init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, config.vocab_size, jnp.int32)
        expected = np.asarray(gpt.forward(params, tokens, config), np.float32)
        acc = Accelerator(
            mesh_config=MeshConfig(data=2, tensor=4),
            strategy="TENSOR_PARALLEL",
            sharding_rules=get_tp_plan("gpt"),
        )
        spec = ShardingStrategy.resolve("TENSOR_PARALLEL", rules=get_tp_plan("gpt"))
        param_specs = infer_param_specs(jax.eval_shape(lambda: params), acc.mesh, spec)
        sharded = shard_pytree(params, param_specs, acc.mesh)
        out = jax.jit(lambda p, t: gpt.forward(p, t, config))(sharded, tokens)
        np.testing.assert_allclose(np.asarray(out, np.float32), expected, atol=2e-4, rtol=2e-4)

    def test_tp_plan_actually_shards(self):
        config = gpt.GPTConfig.tiny()
        acc = Accelerator(
            mesh_config=MeshConfig(data=2, tensor=4),
            strategy="TENSOR_PARALLEL",
            sharding_rules=get_tp_plan("gpt"),
        )
        state = acc.create_train_state(lambda rng: gpt.init(rng, config), optax.sgd(1e-3))
        wq = state.params["blocks"]["attn"]["wq"]
        shard_shape = wq.sharding.shard_shape(wq.shape)
        assert shard_shape[2] == wq.shape[2] // 4

    def test_generate_greedy_matches_forward(self):
        """One greedy step from the cache path must agree with the full
        forward's argmax (cache correctness oracle)."""
        from accelerate_tpu.generation import GenerationConfig

        config = gpt.GPTConfig.tiny()
        params = gpt.init(jax.random.PRNGKey(0), config)
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, config.vocab_size, jnp.int32)
        out = gpt.generate(
            params, prompt, config,
            generation_config=GenerationConfig(max_new_tokens=4, temperature=0.0),
        )
        assert out.shape == (2, 16)
        logits = gpt.forward(params, prompt, config)
        np.testing.assert_array_equal(
            np.asarray(out[:, 12]), np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        )

    def test_remat_matches(self):
        config = gpt.GPTConfig.tiny()
        config_r = gpt.GPTConfig.tiny(remat=True)
        params = gpt.init(jax.random.PRNGKey(0), config)
        batch = {
            "input_ids": jax.random.randint(
                jax.random.PRNGKey(3), (2, 8), 0, config.vocab_size, jnp.int32
            )
        }
        g1 = jax.grad(lambda p: gpt.loss_fn(p, batch, config))(params)
        g2 = jax.grad(lambda p: gpt.loss_fn(p, batch, config_r))(params)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5), g1, g2)


class TestT5:
    def test_shapes_and_param_count(self):
        config = t5.T5Config.tiny()
        params = t5.init(jax.random.PRNGKey(0), config)
        actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        assert actual == config.param_count()
        logits = t5.forward(
            params, jnp.zeros((2, 10), jnp.int32), jnp.zeros((2, 6), jnp.int32), config
        )
        assert logits.shape == (2, 6, config.vocab_size)

    def test_decoder_causality(self):
        """Changing a future decoder token must not change past logits."""
        config = t5.T5Config.tiny()
        params = t5.init(jax.random.PRNGKey(0), config)
        src = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, config.vocab_size, jnp.int32)
        d1 = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, config.vocab_size, jnp.int32)
        d2 = d1.at[0, -1].set((d1[0, -1] + 1) % config.vocab_size)
        l1 = t5.forward(params, src, d1, config)
        l2 = t5.forward(params, src, d2, config)
        np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)

    def test_encoder_is_bidirectional(self):
        """Encoder states must depend on later source tokens (no causal mask)."""
        config = t5.T5Config.tiny()
        params = t5.init(jax.random.PRNGKey(0), config)
        s1 = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, config.vocab_size, jnp.int32)
        s2 = s1.at[0, -1].set((s1[0, -1] + 1) % config.vocab_size)
        e1 = t5.encode(params, s1, config)
        e2 = t5.encode(params, s2, config)
        assert not np.allclose(np.asarray(e1[0, 0]), np.asarray(e2[0, 0]), atol=1e-7)

    def test_rel_bucket_properties(self):
        # bidirectional: sign distinguishes direction; monotone in distance
        rp = jnp.arange(-20, 21)[None, :]
        b = t5.relative_position_bucket(rp, bidirectional=True, num_buckets=32, max_distance=128)
        assert b.min() >= 0 and b.max() < 32
        assert int(b[0, 20]) == 0  # zero offset -> bucket 0
        b_causal = t5.relative_position_bucket(rp, bidirectional=False, num_buckets=32, max_distance=128)
        assert b_causal.min() >= 0 and b_causal.max() < 32

    def test_src_padding_masked_out(self):
        config = t5.T5Config.tiny()
        params = t5.init(jax.random.PRNGKey(0), config)
        src = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, config.vocab_size, jnp.int32)
        mask = jnp.ones((1, 8), jnp.int32).at[0, 5:].set(0)
        dec = jnp.zeros((1, 4), jnp.int32)
        l1 = t5.forward(params, src, dec, config, attention_mask=mask)
        src2 = src.at[0, 6].set((src[0, 6] + 3) % config.vocab_size)
        l2 = t5.forward(params, src2, dec, config, attention_mask=mask)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)

    def test_training_decreases_loss(self):
        config = t5.T5Config.tiny()
        acc = Accelerator(mesh_config=MeshConfig(), seed=0)
        state = acc.create_train_state(lambda rng: t5.init(rng, config), optax.adam(1e-3))
        step = acc.make_train_step(lambda p, b, r: t5.loss_fn(p, b, config, r))
        batch = {
            "input_ids": jax.random.randint(jax.random.PRNGKey(4), (8, 12), 0, config.vocab_size, jnp.int32),
            "decoder_input_ids": jax.random.randint(jax.random.PRNGKey(5), (8, 8), 0, config.vocab_size, jnp.int32),
        }
        losses = []
        for _ in range(10):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]

    def test_tp_forward_matches_replicated(self):
        config = t5.T5Config.tiny()
        params = t5.init(jax.random.PRNGKey(0), config)
        src = jax.random.randint(jax.random.PRNGKey(1), (8, 12), 0, config.vocab_size, jnp.int32)
        dec = jax.random.randint(jax.random.PRNGKey(2), (8, 8), 0, config.vocab_size, jnp.int32)
        expected = np.asarray(t5.forward(params, src, dec, config), np.float32)
        acc = Accelerator(
            mesh_config=MeshConfig(data=2, tensor=4),
            strategy="TENSOR_PARALLEL",
            sharding_rules=get_tp_plan("t5"),
        )
        spec = ShardingStrategy.resolve("TENSOR_PARALLEL", rules=get_tp_plan("t5"))
        param_specs = infer_param_specs(jax.eval_shape(lambda: params), acc.mesh, spec)
        sharded = shard_pytree(params, param_specs, acc.mesh)
        out = jax.jit(lambda p, s, d: t5.forward(p, s, d, config))(sharded, src, dec)
        np.testing.assert_allclose(np.asarray(out, np.float32), expected, atol=2e-4, rtol=2e-4)

    def test_generate_greedy(self):
        config = t5.T5Config.tiny()
        params = t5.init(jax.random.PRNGKey(0), config)
        src = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, config.vocab_size, jnp.int32)
        out = t5.generate(params, src, config, max_new_tokens=5)
        assert out.shape == (2, 5)
        # greedy first token must equal the argmax of a single decode step
        enc = t5.encode(params, src, config)
        logits = t5.decode(params, jnp.zeros((2, 1), jnp.int32), enc, config)
        np.testing.assert_array_equal(
            np.asarray(out[:, 0]), np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        )


class TestViT:
    def test_shapes_and_param_count(self):
        config = vit.ViTConfig.tiny()
        params = vit.init(jax.random.PRNGKey(0), config)
        actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        assert actual == config.param_count()
        images = jnp.zeros((2, 32, 32, 3))
        logits = vit.forward(params, images, config)
        assert logits.shape == (2, config.num_classes)

    def test_patchify_roundtrip(self):
        """Patch extraction preserves pixels (reshape, not resample)."""
        config = vit.ViTConfig.tiny()
        images = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 32, 3))
        patches = vit.patchify(images, config)
        assert patches.shape == (1, config.n_patches, config.patch_dim)
        # first patch = top-left 8x8 block
        np.testing.assert_allclose(
            np.asarray(patches[0, 0]), np.asarray(images[0, :8, :8, :]).reshape(-1)
        )

    def test_permutation_changes_prediction(self):
        """Spatial information must matter (pos embeddings active)."""
        config = vit.ViTConfig.tiny()
        params = vit.init(jax.random.PRNGKey(0), config)
        images = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3))
        flipped = images[:, ::-1]
        l1 = vit.forward(params, images, config)
        l2 = vit.forward(params, flipped, config)
        assert not np.allclose(np.asarray(l1), np.asarray(l2), atol=1e-7)

    def test_training_decreases_loss(self):
        config = vit.ViTConfig.tiny()
        acc = Accelerator(mesh_config=MeshConfig(), seed=0)
        state = acc.create_train_state(lambda rng: vit.init(rng, config), optax.adam(1e-3))
        step = acc.make_train_step(lambda p, b, r: vit.loss_fn(p, b, config, r))
        batch = {
            "pixel_values": jax.random.normal(jax.random.PRNGKey(2), (8, 32, 32, 3)),
            "labels": jax.random.randint(jax.random.PRNGKey(3), (8,), 0, config.num_classes, jnp.int32),
        }
        losses = []
        for _ in range(10):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]

    def test_tp_forward_matches_replicated(self):
        config = vit.ViTConfig.tiny()
        params = vit.init(jax.random.PRNGKey(0), config)
        images = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
        expected = np.asarray(vit.forward(params, images, config), np.float32)
        acc = Accelerator(
            mesh_config=MeshConfig(data=2, tensor=4),
            strategy="TENSOR_PARALLEL",
            sharding_rules=get_tp_plan("vit"),
        )
        spec = ShardingStrategy.resolve("TENSOR_PARALLEL", rules=get_tp_plan("vit"))
        param_specs = infer_param_specs(jax.eval_shape(lambda: params), acc.mesh, spec)
        sharded = shard_pytree(params, param_specs, acc.mesh)
        out = jax.jit(lambda p, i: vit.forward(p, i, config))(sharded, images)
        np.testing.assert_allclose(np.asarray(out, np.float32), expected, atol=2e-4, rtol=2e-4)


def test_seq_len_overflow_raises():
    """Position/RoPE tables clamp under jit; the forwards must refuse instead
    of silently degrading."""
    gcfg = gpt.GPTConfig.tiny(max_seq_len=16)
    gparams = gpt.init(jax.random.PRNGKey(0), gcfg)
    with pytest.raises(ValueError, match="max_seq_len"):
        gpt.forward(gparams, jnp.zeros((1, 32), jnp.int32), gcfg)
    lcfg = llama.LlamaConfig.tiny(max_seq_len=16)
    lparams = llama.init(jax.random.PRNGKey(0), lcfg)
    with pytest.raises(ValueError, match="max_seq_len"):
        llama.forward(lparams, jnp.zeros((1, 32), jnp.int32), lcfg)


class TestChunkedLoss:
    def test_matches_unchunked_value_and_grads(self):
        config = llama.LlamaConfig.tiny()
        config_c = llama.LlamaConfig.tiny(loss_chunk_size=8)
        params = llama.init(jax.random.PRNGKey(0), config)
        batch = {
            "input_ids": jax.random.randint(
                jax.random.PRNGKey(1), (2, 32), 0, config.vocab_size, jnp.int32
            )
        }
        l1, g1 = jax.value_and_grad(lambda p: llama.loss_fn(p, batch, config))(params)
        l2, g2 = jax.value_and_grad(lambda p: llama.loss_fn(p, batch, config_c))(params)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5), g1, g2
        )

    def test_with_attention_mask(self):
        config = llama.LlamaConfig.tiny()
        config_c = llama.LlamaConfig.tiny(loss_chunk_size=16)
        params = llama.init(jax.random.PRNGKey(0), config)
        mask = jnp.ones((2, 32), jnp.int32).at[:, 20:].set(0)
        batch = {
            "input_ids": jax.random.randint(
                jax.random.PRNGKey(2), (2, 32), 0, config.vocab_size, jnp.int32
            ),
            "attention_mask": mask,
        }
        l1 = llama.loss_fn(params, batch, config)
        l2 = llama.loss_fn(params, batch, config_c)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)

    def test_indivisible_chunk_rejected(self):
        config = llama.LlamaConfig.tiny(loss_chunk_size=7)
        params = llama.init(jax.random.PRNGKey(0), config)
        batch = {"input_ids": jnp.zeros((1, 32), jnp.int32)}
        with pytest.raises(ValueError, match="chunk_size"):
            llama.loss_fn(params, batch, config)


def test_gpt_chunked_loss_matches():
    config = gpt.GPTConfig.tiny()
    config_c = gpt.GPTConfig.tiny(loss_chunk_size=8)
    params = gpt.init(jax.random.PRNGKey(0), config)
    batch = {
        "input_ids": jax.random.randint(
            jax.random.PRNGKey(1), (2, 32), 0, config.vocab_size, jnp.int32
        )
    }
    l1, g1 = jax.value_and_grad(lambda p: gpt.loss_fn(p, batch, config))(params)
    l2, g2 = jax.value_and_grad(lambda p: gpt.loss_fn(p, batch, config_c))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5), g1, g2
    )


def test_gpt_chunked_loss_with_mask_matches():
    config = gpt.GPTConfig.tiny()
    config_c = gpt.GPTConfig.tiny(loss_chunk_size=16)
    params = gpt.init(jax.random.PRNGKey(0), config)
    mask = jnp.ones((2, 32), jnp.int32).at[:, 24:].set(0)
    batch = {
        "input_ids": jax.random.randint(
            jax.random.PRNGKey(1), (2, 32), 0, config.vocab_size, jnp.int32
        ),
        "attention_mask": mask,
    }
    l1 = gpt.loss_fn(params, batch, config)
    l2 = gpt.loss_fn(params, batch, config_c)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


class TestInt8KvCache:
    """int8 KV cache (llama `init_cache(dtype=jnp.int8)`) on the one cache
    layout `forward_with_cache` has (the stacked cache in the scan carry),
    at a short and a long cache: numerically identical per dtype, and int8
    within the per-token-scale quantization envelope."""

    CFG = llama.LlamaConfig.tiny(vocab_size=97, max_seq_len=8192)

    @pytest.fixture(scope="class")
    def params(self):
        return llama.init(jax.random.PRNGKey(0), self.CFG)

    @pytest.mark.parametrize("cache_len", [64, 4096])  # one path, two lengths
    def test_fp32_cache_matches_forward_exactly(self, params, cache_len):
        tok = jnp.asarray(np.arange(20, dtype=np.int32).reshape(2, 10) % 97)
        want = np.asarray(llama.forward(params, tok, self.CFG))
        cache = llama.init_cache(self.CFG, 2, cache_len, dtype=jnp.float32)
        got, _ = llama.forward_with_cache(params, tok, cache, self.CFG)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("cache_len", [64, 4096])
    def test_int8_cache_within_quantization_envelope(self, params, cache_len):
        tok = jnp.asarray(np.arange(20, dtype=np.int32).reshape(2, 10) % 97)
        want = np.asarray(llama.forward(params, tok, self.CFG))
        cache = llama.init_cache(self.CFG, 2, cache_len, dtype=jnp.int8)
        assert cache["k"].dtype == jnp.int8 and "k_scale" in cache
        got, _ = llama.forward_with_cache(params, tok, cache, self.CFG)
        drift = float(np.max(np.abs(np.asarray(got) - want)))
        assert drift < 0.1, drift  # per-token-scale int8 envelope
        assert drift > 0.0  # quantization actually happened

    @pytest.mark.parametrize("cache_len", [64, 4096])
    def test_int8_incremental_matches_oneshot(self, params, cache_len):
        """Prefill-then-decode must quantize each token ONCE at its final
        position: the int8 cache contents (values AND scales) are
        bit-identical to one-shot prefill; logits agree to fp reduction
        order (chunked attention sums in a different order)."""
        tok = jnp.asarray(np.arange(20, dtype=np.int32).reshape(2, 10) % 97)
        cache = llama.init_cache(self.CFG, 2, cache_len, dtype=jnp.int8)
        one, c_one = llama.forward_with_cache(params, tok, cache, self.CFG)
        cache = llama.init_cache(self.CFG, 2, cache_len, dtype=jnp.int8)
        l1, cache = llama.forward_with_cache(params, tok[:, :6], cache, self.CFG)
        l2, cache = llama.forward_with_cache(params, tok[:, 6:], cache, self.CFG)
        for key in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(np.asarray(cache[key]), np.asarray(c_one[key]))
        inc = np.concatenate([np.asarray(l1), np.asarray(l2)], axis=1)
        np.testing.assert_allclose(inc, np.asarray(one), atol=1e-5, rtol=1e-5)

    def test_generate_wires_kv_cache_dtype(self, params):
        from accelerate_tpu.generation import GenerationConfig

        tok = jnp.asarray(np.arange(10, dtype=np.int32).reshape(2, 5) % 97)
        out = llama.generate(
            params, tok, self.CFG,
            generation_config=GenerationConfig(max_new_tokens=6, kv_cache_dtype="int8"),
        )
        assert out.shape == (2, 11)

    def test_gpt_family_refuses_int8(self):
        cfg = gpt.GPTConfig.tiny()
        with pytest.raises(NotImplementedError, match="llama"):
            gpt.init_cache(cfg, 1, 16, dtype=jnp.int8)

    def test_unknown_kv_cache_dtype_rejected(self):
        from accelerate_tpu.generation import GenerationConfig, cache_dtype

        with pytest.raises(ValueError, match="kv_cache_dtype"):
            cache_dtype(GenerationConfig(kv_cache_dtype="fp8"))


@pytest.mark.parametrize("cache_len", [32, 4096])  # one path, two lengths
def test_gpt_cache_layouts_match_forward(cache_len):
    """The gpt family's cached forward (same cache layout as llama's) must be
    numerically identical to the uncached forward on every block variant."""
    cfg = gpt.GPTConfig.tiny(
        max_seq_len=8192, positional="rotary", rotary_dim=8,
        rotary_interleaved=True, parallel_residual=True,
        shared_parallel_norm=True, attn_bias=False,
        tie_embeddings=False, head_bias=True,
    )
    params = gpt.init(jax.random.PRNGKey(7), cfg)
    tok = jnp.arange(24, dtype=jnp.int32).reshape(2, 12) % 256
    want = np.asarray(gpt.forward(params, tok, cfg))
    cache = gpt.init_cache(cfg, 2, cache_len, dtype=jnp.float32)
    got, cache = gpt.forward_with_cache(params, tok, cache, cfg)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-5)


def test_offloaded_decode_refuses_int8_cache():
    """The streamed decode path has no dequant plumbing; it must refuse an
    int8 cache rather than read scale-free garbage."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(jax.random.PRNGKey(0), cfg)
    cache = llama.init_cache(cfg, 1, 16, dtype=jnp.int8)
    tok = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="offloaded"):
        llama.forward_with_cache_offloaded(params, tok, cache, cfg)
