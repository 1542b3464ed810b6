"""HF-checkpoint ingestion (`models/hf.py`): zero-key-map loading of real
Hugging Face repo layouts, numerically verified against `transformers`'
own forward pass (the strongest possible parity check — reference
`load_checkpoint_in_model`, `utils/modeling.py:1787`)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.heavy  # compile-heavy / subprocess lane

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from accelerate_tpu.big_modeling import infer_sharding_plan
from accelerate_tpu.models import bert, gpt, hf, llama, vit
from accelerate_tpu.parallel import MeshConfig, build_mesh


def _save_hf(model, tmp_path, name):
    d = tmp_path / name
    model.save_pretrained(str(d), safe_serialization=True)
    return str(d)


@pytest.fixture(scope="module")
def tiny_hf_llama(tmp_path_factory):
    cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg).eval()
    d = _save_hf(model, tmp_path_factory.mktemp("hf"), "llama")
    return model, d


class TestLlamaParity:
    def test_config_translation(self, tiny_hf_llama):
        _, repo = tiny_hf_llama
        family, config = hf.from_hf_config(repo)
        assert family == "llama"
        assert (config.d_model, config.n_layers, config.num_heads,
                config.num_kv_heads, config.d_ff) == (64, 2, 4, 2, 128)
        assert config.rope_theta == 10000.0

    def test_forward_matches_transformers(self, tiny_hf_llama):
        model, repo = tiny_hf_llama
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        tokens = np.arange(24, dtype=np.int32).reshape(2, 12) % 256
        ours = np.asarray(
            llama.forward(loaded.params, jnp.asarray(tokens), loaded.config)
        )
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-3)

    def test_offloaded_leaves_loadable(self, tiny_hf_llama):
        _, repo = tiny_hf_llama
        mesh = build_mesh(MeshConfig(data=1, fsdp=8))
        family, config = hf.from_hf_config(repo)
        shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), config))
        total = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize for l in jax.tree.leaves(shapes)
        )
        plan = infer_sharding_plan(shapes, mesh, hbm_budget=total // 16)
        assert plan.offload
        params = hf.load_hf_checkpoint(
            shapes, repo, plan, family=family, config=config
        )
        from accelerate_tpu.parallel.sharding import _path_str

        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        for p, leaf in flat:
            if _path_str(p) in plan.offload:
                assert isinstance(leaf, np.ndarray)

    def test_dtype_cast(self, tiny_hf_llama):
        _, repo = tiny_hf_llama
        mesh = build_mesh(MeshConfig())
        loaded = hf.load_pretrained(repo, mesh=mesh, dtype=jnp.bfloat16)
        assert all(
            l.dtype == jnp.bfloat16 for l in jax.tree.leaves(loaded.params)
        )

    def test_missing_tensor_error_is_actionable(self, tiny_hf_llama, tmp_path):
        _, repo = tiny_hf_llama
        # A repo whose config promises more layers than its weights have.
        cfg = json.load(open(f"{repo}/config.json"))
        cfg["num_hidden_layers"] = 4
        broken = tmp_path / "broken"
        broken.mkdir()
        json.dump(cfg, open(broken / "config.json", "w"))
        import shutil

        for f in ("model.safetensors",):
            shutil.copy(f"{repo}/{f}", broken / f)
        mesh = build_mesh(MeshConfig())
        with pytest.raises(KeyError, match="model.layers.2"):
            hf.load_pretrained(str(broken), mesh=mesh)


class TestGPT2Parity:
    def test_forward_matches_transformers(self, tmp_path):
        cfg = transformers.GPT2Config(
            vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        )
        torch.manual_seed(1)
        model = transformers.GPT2LMHeadModel(cfg).eval()
        repo = _save_hf(model, tmp_path, "gpt2")
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        assert loaded.family == "gpt"
        tokens = np.arange(20, dtype=np.int32).reshape(2, 10) % 128
        ours = np.asarray(
            gpt.forward(loaded.params, jnp.asarray(tokens), loaded.config)
        )
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)


class TestBertParity:
    def test_forward_matches_transformers(self, tmp_path):
        cfg = transformers.BertConfig(
            vocab_size=128, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=64, num_labels=3,
        )
        torch.manual_seed(2)
        model = transformers.BertForSequenceClassification(cfg).eval()
        repo = _save_hf(model, tmp_path, "bert")
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        tokens = np.arange(20, dtype=np.int32).reshape(2, 10) % 128
        ours = np.asarray(
            bert.classify(
                loaded.params, {"input_ids": jnp.asarray(tokens)}, loaded.config
            )
        )
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)


class TestViTParity:
    def test_forward_matches_transformers(self, tmp_path):
        cfg = transformers.ViTConfig(
            image_size=32, patch_size=8, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64, num_labels=5,
        )
        torch.manual_seed(3)
        model = transformers.ViTForImageClassification(cfg).eval()
        repo = _save_hf(model, tmp_path, "vit")
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        rng = np.random.RandomState(0)
        images = rng.rand(2, 32, 32, 3).astype(np.float32)
        ours = np.asarray(
            vit.forward(loaded.params, jnp.asarray(images), loaded.config)
        )
        with torch.no_grad():
            # HF ViT eats NCHW; this framework eats NHWC.
            theirs = model(
                torch.from_numpy(images.transpose(0, 3, 1, 2))
            ).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)


class TestDefaultSharding:
    def test_default_rules_shard_over_mesh(self, tiny_hf_llama):
        # Regression: with no explicit rules, load_pretrained must apply the
        # family TP plan — NOT replicate every leaf on every device.
        _, repo = tiny_hf_llama
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        wq = loaded.params["blocks"]["attn"]["wq"]
        n_devices = 8
        # Sharded: each device holds a strict fraction of the leaf.
        shard_elems = wq.addressable_shards[0].data.size
        assert shard_elems * n_devices == wq.size


class TestQuantizedLoad:
    @pytest.mark.parametrize("bits", [8, 4])
    def test_quantize_on_load_forward_close(self, tiny_hf_llama, bits):
        from accelerate_tpu.utils.quantization import is_quantized

        model, repo = tiny_hf_llama
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(
            repo, mesh=mesh, min_weight_size=1, quantize_bits=bits,
            dtype=jnp.float32,
        )
        blocks = loaded.params["blocks"]
        # Big matmul weights packed; embeddings/norms full precision.
        assert is_quantized(blocks["attn"]["wq"])
        assert is_quantized(blocks["mlp"]["w_gate"])
        assert not is_quantized(loaded.params["embed"])
        assert not is_quantized(blocks["attn_norm"])
        tokens = np.arange(24, dtype=np.int32).reshape(2, 12) % 256
        ours = np.asarray(
            llama.forward(loaded.params, jnp.asarray(tokens), loaded.config)
        )
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        # Quantization error bounded: logits still track the fp32 model.
        err = np.abs(ours - theirs).max()
        assert err < (0.06 if bits == 8 else 0.6), err


class TestT5Parity:
    def test_forward_matches_transformers(self, tmp_path):
        cfg = transformers.T5Config(
            vocab_size=128, d_model=32, d_kv=8, d_ff=64, num_layers=2,
            num_decoder_layers=2, num_heads=4,
            feed_forward_proj="gated-gelu", tie_word_embeddings=False,
            relative_attention_num_buckets=8, relative_attention_max_distance=16,
        )
        torch.manual_seed(4)
        model = transformers.T5ForConditionalGeneration(cfg).eval()
        repo = _save_hf(model, tmp_path, "t5")
        from accelerate_tpu.models import t5

        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        assert loaded.family == "t5"
        enc_in = np.arange(16, dtype=np.int32).reshape(2, 8) % 128
        dec_in = (np.arange(12, dtype=np.int32).reshape(2, 6) * 3) % 128
        ours = np.asarray(
            t5.forward(loaded.params, jnp.asarray(enc_in), jnp.asarray(dec_in), loaded.config)
        )
        with torch.no_grad():
            theirs = model(
                input_ids=torch.from_numpy(enc_in).long(),
                decoder_input_ids=torch.from_numpy(dec_in).long(),
            ).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=1e-3, rtol=5e-3)

    def test_ungated_t5_rejected(self, tmp_path):
        json.dump(
            {"model_type": "t5", "vocab_size": 64, "d_model": 16, "d_kv": 4,
             "d_ff": 32, "num_layers": 1, "num_heads": 4,
             "feed_forward_proj": "relu"},
            open(tmp_path / "config.json", "w"),
        )
        with pytest.raises(ValueError, match="gated"):
            hf.from_hf_config(str(tmp_path))


class TestExportRoundTrip:
    def test_transformers_loads_our_export(self, tiny_hf_llama, tmp_path):
        """The return leg of the migration loop: load an HF repo, export it
        back with save_pretrained, and let transformers load THE EXPORT —
        logits must match the original torch model end to end."""
        model, repo = tiny_hf_llama
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        out_dir = str(tmp_path / "exported")
        hf.save_pretrained(out_dir, loaded.family, loaded.config, loaded.params)

        reloaded = transformers.LlamaForCausalLM.from_pretrained(out_dir).eval()
        tokens = np.arange(24, dtype=np.int32).reshape(2, 12) % 256
        with torch.no_grad():
            orig = model(torch.from_numpy(tokens).long()).logits.numpy()
            ours = reloaded(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, orig, atol=2e-5, rtol=1e-4)

    def test_quantized_params_rejected(self, tiny_hf_llama, tmp_path):
        _, repo = tiny_hf_llama
        mesh = build_mesh(MeshConfig())
        loaded = hf.load_pretrained(repo, mesh=mesh, quantize_bits=8)
        with pytest.raises(ValueError, match="full-precision"):
            hf.save_pretrained(
                str(tmp_path / "q"), loaded.family, loaded.config, loaded.params
            )


class TestMixtralParity:
    def test_forward_matches_transformers(self, tmp_path):
        cfg = transformers.MixtralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            num_local_experts=4, num_experts_per_tok=2,
            max_position_embeddings=64, rope_theta=10000.0,
        )
        torch.manual_seed(5)
        model = transformers.MixtralForCausalLM(cfg).eval()
        repo = _save_hf(model, tmp_path, "mixtral")
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        assert loaded.family == "llama" and loaded.config.n_experts == 4
        tokens = np.arange(24, dtype=np.int32).reshape(2, 12) % 128
        ours = np.asarray(
            llama.forward(loaded.params, jnp.asarray(tokens), loaded.config)
        )
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)


class TestMixtralExport:
    def test_export_round_trip(self, tmp_path):
        """Close the migration loop for the sparse family —
        per-expert inverse transforms re-fuse block_sparse_moe and
        transformers reproduces the original logits."""
        cfg = transformers.MixtralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            num_local_experts=4, num_experts_per_tok=2,
            max_position_embeddings=64, rope_theta=10000.0,
        )
        torch.manual_seed(14)
        model = transformers.MixtralForCausalLM(cfg).eval()
        repo = _save_hf(model, tmp_path, "mixtralsrc")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        out_dir = str(tmp_path / "mixtralexp")
        hf.save_pretrained(out_dir, loaded.family, loaded.config, loaded.params)
        exported = json.load(open(f"{out_dir}/config.json"))
        assert exported["model_type"] == "mixtral"
        assert exported["num_local_experts"] == 4
        reloaded = transformers.MixtralForCausalLM.from_pretrained(out_dir).eval()
        tokens = np.arange(24, dtype=np.int32).reshape(2, 12) % 128
        with torch.no_grad():
            orig = model(torch.from_numpy(tokens).long()).logits.numpy()
            ours = reloaded(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, orig, atol=2e-5, rtol=1e-4)


class TestQwen2Parity:
    def test_forward_matches_transformers(self, tmp_path):
        cfg = transformers.Qwen2Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0,
            tie_word_embeddings=False,
        )
        torch.manual_seed(6)
        model = transformers.Qwen2ForCausalLM(cfg).eval()
        repo = _save_hf(model, tmp_path, "qwen2")
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        assert loaded.family == "llama" and loaded.config.attn_bias
        tokens = np.arange(24, dtype=np.int32).reshape(2, 12) % 128
        ours = np.asarray(
            llama.forward(loaded.params, jnp.asarray(tokens), loaded.config)
        )
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)

    def test_export_round_trip(self, tmp_path):
        cfg = transformers.Qwen2Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, tie_word_embeddings=False,
        )
        torch.manual_seed(7)
        model = transformers.Qwen2ForCausalLM(cfg).eval()
        repo = _save_hf(model, tmp_path, "qwen2src")
        mesh = build_mesh(MeshConfig())
        loaded = hf.load_pretrained(repo, mesh=mesh)
        out_dir = str(tmp_path / "qwen2exp")
        hf.save_pretrained(out_dir, loaded.family, loaded.config, loaded.params)
        reloaded = transformers.Qwen2ForCausalLM.from_pretrained(out_dir).eval()
        tokens = np.arange(16, dtype=np.int32).reshape(2, 8) % 128
        with torch.no_grad():
            orig = model(torch.from_numpy(tokens).long()).logits.numpy()
            ours = reloaded(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, orig, atol=2e-5, rtol=1e-4)


class TestLlama31RopeScaling:
    """Llama-3.1/3.2-style checkpoints: the `llama3` banded frequency rescale
    must reproduce transformers' tables and logits (reference loads these
    via its name-based loader, `utils/modeling.py:1787`)."""

    _scaling = {
        "rope_type": "llama3", "factor": 4.0, "low_freq_factor": 1.0,
        "high_freq_factor": 4.0, "original_max_position_embeddings": 32,
    }

    def test_rope_tables_match_transformers(self):
        from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

        from accelerate_tpu.models.layers import RopeScaling, rope_frequencies

        cfg = transformers.LlamaConfig(
            hidden_size=64, num_attention_heads=4, max_position_embeddings=128,
            rope_theta=10000.0, rope_scaling=dict(self._scaling),
        )
        theirs_inv, _ = ROPE_INIT_FUNCTIONS["llama3"](cfg, device="cpu")
        cos, _sin = rope_frequencies(
            16, 128, 10000.0,
            scaling=RopeScaling(
                "llama3", 4.0, 1.0, 4.0, original_max_position_embeddings=32
            ),
        )
        expected = np.cos(np.outer(np.arange(128), theirs_inv.numpy()))
        np.testing.assert_allclose(cos, expected, atol=1e-6)

    def test_forward_matches_transformers(self, tmp_path):
        cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128, rope_theta=10000.0,
            rope_scaling=dict(self._scaling), tie_word_embeddings=False,
        )
        torch.manual_seed(8)
        model = transformers.LlamaForCausalLM(cfg).eval()
        repo = _save_hf(model, tmp_path, "llama31")
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        assert loaded.config.rope_scaling.rope_type == "llama3"
        # S=64 spans positions past original_max_position_embeddings=32, so
        # every frequency band (kept / scaled / smoothed) is exercised.
        tokens = np.arange(128, dtype=np.int32).reshape(2, 64) % 128
        ours = np.asarray(
            llama.forward(loaded.params, jnp.asarray(tokens), loaded.config)
        )
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)

    def test_linear_scaling_matches_transformers(self, tmp_path):
        cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128, rope_theta=10000.0,
            rope_scaling={"type": "linear", "factor": 2.0},  # old-style key
            tie_word_embeddings=False,
        )
        torch.manual_seed(9)
        model = transformers.LlamaForCausalLM(cfg).eval()
        repo = _save_hf(model, tmp_path, "llamalin")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        assert loaded.config.rope_scaling.rope_type == "linear"
        tokens = np.arange(96, dtype=np.int32).reshape(2, 48) % 128
        ours = np.asarray(
            llama.forward(loaded.params, jnp.asarray(tokens), loaded.config)
        )
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)

    def test_export_round_trips_rope_scaling(self, tmp_path):
        cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128, rope_theta=10000.0,
            rope_scaling=dict(self._scaling), tie_word_embeddings=False,
        )
        torch.manual_seed(10)
        model = transformers.LlamaForCausalLM(cfg).eval()
        repo = _save_hf(model, tmp_path, "llama31src")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        out_dir = str(tmp_path / "llama31exp")
        hf.save_pretrained(out_dir, loaded.family, loaded.config, loaded.params)
        exported = json.load(open(f"{out_dir}/config.json"))
        assert exported["rope_scaling"]["rope_type"] == "llama3"
        reloaded = transformers.LlamaForCausalLM.from_pretrained(out_dir).eval()
        tokens = np.arange(96, dtype=np.int32).reshape(2, 48) % 128
        with torch.no_grad():
            orig = model(torch.from_numpy(tokens).long()).logits.numpy()
            ours = reloaded(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, orig, atol=2e-5, rtol=1e-4)

    def test_unimplemented_rope_type_rejected(self, tmp_path):
        base = {"model_type": "llama", "vocab_size": 64, "hidden_size": 16,
                "intermediate_size": 32, "num_hidden_layers": 1,
                "num_attention_heads": 2, "num_key_value_heads": 2,
                "rope_scaling": {"rope_type": "yarn", "factor": 4.0}}
        json.dump(base, open(tmp_path / "config.json", "w"))
        with pytest.raises(ValueError, match="yarn"):
            hf.from_hf_config(str(tmp_path))


class TestMistralSlidingWindow:
    """Published Mistral-7B configs all carry sliding_window; the window mask
    must match transformers' eager-attention banding exactly."""

    def _model(self, tmp_path, window=8):
        cfg = transformers.MistralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0,
            sliding_window=window, attn_implementation="eager",
        )
        torch.manual_seed(11)
        model = transformers.MistralForCausalLM(cfg).eval()
        return model, _save_hf(model, tmp_path, "mistral")

    def test_forward_matches_transformers(self, tmp_path):
        model, repo = self._model(tmp_path)
        mesh = build_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        assert loaded.config.sliding_window == 8
        # S=24 is 3x the window, so most positions have truncated context.
        tokens = np.arange(48, dtype=np.int32).reshape(2, 24) % 128
        ours = np.asarray(
            llama.forward(loaded.params, jnp.asarray(tokens), loaded.config)
        )
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)

    def test_window_actually_masks(self, tmp_path):
        """Guards against the mask silently not being applied (in which case
        the parity test would only be comparing full-attention paths)."""
        import dataclasses as dc

        _, repo = self._model(tmp_path)
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        tokens = jnp.arange(24, dtype=jnp.int32)[None, :] % 128
        windowed = llama.forward(loaded.params, tokens, loaded.config)
        full = llama.forward(
            loaded.params, tokens, dc.replace(loaded.config, sliding_window=None)
        )
        # Positions inside the first window see identical context...
        np.testing.assert_allclose(windowed[:, :8], full[:, :8], atol=1e-5)
        # ...later positions must differ, or the window did nothing.
        assert np.abs(np.asarray(windowed[:, 12:]) - np.asarray(full[:, 12:])).max() > 1e-3

    def test_decode_matches_forward(self, tmp_path):
        """Incremental (prefill+decode) logits must equal the full forward at
        the same positions — the cache path applies the same window."""
        _, repo = self._model(tmp_path)
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        tokens = jnp.arange(20, dtype=jnp.int32)[None, :] % 128
        full = llama.forward(loaded.params, tokens, loaded.config)
        cache = llama.init_cache(loaded.config, 1, 32, dtype=jnp.float32)
        logits, cache = llama.forward_with_cache(
            loaded.params, tokens[:, :16], cache, loaded.config
        )
        np.testing.assert_allclose(logits, full[:, :16], atol=2e-4, rtol=2e-3)
        for i in range(16, 20):
            step, cache = llama.forward_with_cache(
                loaded.params, tokens[:, i : i + 1], cache, loaded.config
            )
            np.testing.assert_allclose(
                step[:, 0], full[:, i], atol=2e-4, rtol=2e-3
            )

    def test_export_round_trip(self, tmp_path):
        model, repo = self._model(tmp_path)
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        out_dir = str(tmp_path / "mistralexp")
        hf.save_pretrained(out_dir, loaded.family, loaded.config, loaded.params)
        exported = json.load(open(f"{out_dir}/config.json"))
        assert exported["model_type"] == "mistral"
        assert exported["sliding_window"] == 8
        reloaded = transformers.MistralForCausalLM.from_pretrained(
            out_dir, attn_implementation="eager"
        ).eval()
        tokens = np.arange(48, dtype=np.int32).reshape(2, 24) % 128
        with torch.no_grad():
            orig = model(torch.from_numpy(tokens).long()).logits.numpy()
            ours = reloaded(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, orig, atol=2e-5, rtol=1e-4)


class TestQwen2SlidingWindow:
    """HF qwen2 windows layers i >= max_window_layers, so uniform SWA is
    max_window_layers=0 and mwl >= n_layers means no window at all."""

    def _cfg(self, **kw):
        base = dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0,
            tie_word_embeddings=False, attn_implementation="eager",
        )
        base.update(kw)
        return transformers.Qwen2Config(**base)

    def test_uniform_window_parity(self, tmp_path):
        cfg = self._cfg(use_sliding_window=True, sliding_window=8, max_window_layers=0)
        torch.manual_seed(12)
        model = transformers.Qwen2ForCausalLM(cfg).eval()
        repo = _save_hf(model, tmp_path, "qwen2swa")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        assert loaded.config.sliding_window == 8
        tokens = np.arange(48, dtype=np.int32).reshape(2, 24) % 128
        ours = np.asarray(
            llama.forward(loaded.params, jnp.asarray(tokens), loaded.config)
        )
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)

    def test_export_writes_uniform_band(self, tmp_path):
        cfg = self._cfg(use_sliding_window=True, sliding_window=8, max_window_layers=0)
        torch.manual_seed(13)
        model = transformers.Qwen2ForCausalLM(cfg).eval()
        repo = _save_hf(model, tmp_path, "qwen2swasrc")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        out_dir = str(tmp_path / "qwen2swaexp")
        hf.save_pretrained(out_dir, loaded.family, loaded.config, loaded.params)
        exported = json.load(open(f"{out_dir}/config.json"))
        # max_window_layers = n_layers would silently disable SWA on reload.
        assert exported["use_sliding_window"] and exported["max_window_layers"] == 0
        reloaded = transformers.Qwen2ForCausalLM.from_pretrained(
            out_dir, attn_implementation="eager"
        ).eval()
        assert all(t == "sliding_attention" for t in reloaded.config.layer_types)
        tokens = np.arange(48, dtype=np.int32).reshape(2, 24) % 128
        with torch.no_grad():
            orig = model(torch.from_numpy(tokens).long()).logits.numpy()
            ours = reloaded(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, orig, atol=2e-5, rtol=1e-4)

    def test_banded_window_past_last_layer_is_full_attention(self, tmp_path):
        # mwl >= n_layers: transformers runs full attention everywhere.
        cfg = {"model_type": "qwen2", "vocab_size": 64, "hidden_size": 16,
               "intermediate_size": 32, "num_hidden_layers": 2,
               "num_attention_heads": 2, "num_key_value_heads": 2,
               "use_sliding_window": True, "sliding_window": 8,
               "max_window_layers": 2}
        json.dump(cfg, open(tmp_path / "config.json", "w"))
        _family, config = hf.from_hf_config(str(tmp_path))
        assert config.sliding_window is None

    def test_mixed_band_rejected(self, tmp_path):
        cfg = {"model_type": "qwen2", "vocab_size": 64, "hidden_size": 16,
               "intermediate_size": 32, "num_hidden_layers": 2,
               "num_attention_heads": 2, "num_key_value_heads": 2,
               "use_sliding_window": True, "sliding_window": 8,
               "max_window_layers": 1}
        json.dump(cfg, open(tmp_path / "config.json", "w"))
        with pytest.raises(ValueError, match="max_window_layers"):
            hf.from_hf_config(str(tmp_path))


def test_nondefault_activations_rejected(tmp_path):
    """A checkpoint whose activation differs from the family's hardwired one
    must refuse loudly — substituting it would silently break parity."""
    llama_cfg = {"model_type": "llama", "vocab_size": 64, "hidden_size": 16,
                 "intermediate_size": 32, "num_hidden_layers": 1,
                 "num_attention_heads": 2, "num_key_value_heads": 2,
                 "hidden_act": "gelu"}
    json.dump(llama_cfg, open(tmp_path / "config.json", "w"))
    with pytest.raises(ValueError, match="hidden_act"):
        hf.from_hf_config(str(tmp_path))
    gpt_cfg = {"model_type": "gpt2", "vocab_size": 64, "n_embd": 16,
               "n_layer": 1, "n_head": 2, "activation_function": "gelu"}
    json.dump(gpt_cfg, open(tmp_path / "config.json", "w"))
    with pytest.raises(ValueError, match="activation_function"):
        hf.from_hf_config(str(tmp_path))
    bert_cfg = {"model_type": "bert", "vocab_size": 64, "hidden_size": 16,
                "intermediate_size": 32, "num_hidden_layers": 1,
                "num_attention_heads": 2, "hidden_act": "relu"}
    json.dump(bert_cfg, open(tmp_path / "config.json", "w"))
    with pytest.raises(ValueError, match="hidden_act"):
        hf.from_hf_config(str(tmp_path))


def test_llama_bias_variants_rejected(tmp_path):
    """Community llama configs with attention_bias/mlp_bias must refuse
    loudly — silently dropping their bias tensors would break parity."""
    base = {"model_type": "llama", "vocab_size": 64, "hidden_size": 16,
            "intermediate_size": 32, "num_hidden_layers": 1,
            "num_attention_heads": 2, "num_key_value_heads": 2}
    json.dump({**base, "attention_bias": True}, open(tmp_path / "config.json", "w"))
    with pytest.raises(ValueError, match="attention_bias"):
        hf.from_hf_config(str(tmp_path))
    json.dump({**base, "mlp_bias": True}, open(tmp_path / "config.json", "w"))
    with pytest.raises(ValueError, match="mlp_bias"):
        hf.from_hf_config(str(tmp_path))


class TestAllFamilyExports:
    """Round-trip every exportable family: transformers must load our export
    and reproduce the original logits."""

    def _round_trip(self, model, repo, tmp_path, family_cls, fwd):
        mesh = build_mesh(MeshConfig())
        loaded = hf.load_pretrained(repo, mesh=mesh)
        out_dir = str(tmp_path / "exp")
        hf.save_pretrained(out_dir, loaded.family, loaded.config, loaded.params)
        reloaded = family_cls.from_pretrained(out_dir).eval()
        with torch.no_grad():
            orig = fwd(model)
            ours = fwd(reloaded)
        np.testing.assert_allclose(ours, orig, atol=5e-5, rtol=2e-4)

    def test_gpt2(self, tmp_path):
        cfg = transformers.GPT2Config(vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4)
        torch.manual_seed(8)
        model = transformers.GPT2LMHeadModel(cfg).eval()
        repo = _save_hf(model, tmp_path, "g")
        tokens = torch.arange(20).reshape(2, 10) % 128
        self._round_trip(model, repo, tmp_path, transformers.GPT2LMHeadModel,
                         lambda m: m(tokens).logits.numpy())

    def test_bert(self, tmp_path):
        cfg = transformers.BertConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                                      num_attention_heads=4, intermediate_size=64,
                                      max_position_embeddings=64, num_labels=3)
        torch.manual_seed(9)
        model = transformers.BertForSequenceClassification(cfg).eval()
        repo = _save_hf(model, tmp_path, "b")
        tokens = torch.arange(20).reshape(2, 10) % 128
        self._round_trip(model, repo, tmp_path, transformers.BertForSequenceClassification,
                         lambda m: m(tokens).logits.numpy())

    def test_vit(self, tmp_path):
        cfg = transformers.ViTConfig(image_size=32, patch_size=8, hidden_size=32,
                                     num_hidden_layers=2, num_attention_heads=4,
                                     intermediate_size=64, num_labels=5)
        torch.manual_seed(10)
        model = transformers.ViTForImageClassification(cfg).eval()
        repo = _save_hf(model, tmp_path, "v")
        images = torch.rand(2, 3, 32, 32)
        self._round_trip(model, repo, tmp_path, transformers.ViTForImageClassification,
                         lambda m: m(images).logits.numpy())

    def test_t5(self, tmp_path):
        cfg = transformers.T5Config(vocab_size=128, d_model=32, d_kv=8, d_ff=64,
                                    num_layers=2, num_decoder_layers=2, num_heads=4,
                                    feed_forward_proj="gated-gelu", tie_word_embeddings=False,
                                    relative_attention_num_buckets=8,
                                    relative_attention_max_distance=16)
        torch.manual_seed(11)
        model = transformers.T5ForConditionalGeneration(cfg).eval()
        repo = _save_hf(model, tmp_path, "t")
        enc = torch.arange(16).reshape(2, 8) % 128
        dec = (torch.arange(12).reshape(2, 6) * 3) % 128
        self._round_trip(model, repo, tmp_path, transformers.T5ForConditionalGeneration,
                         lambda m: m(input_ids=enc, decoder_input_ids=dec).logits.numpy())


def test_gpt2_untied_head_exports(tmp_path):
    """A natively-built untied-head GPT must export its lm_head (and config)
    rather than silently re-tying on reload."""
    from accelerate_tpu.models import gpt as gpt_mod

    config = gpt_mod.GPTConfig.tiny(vocab_size=64, max_seq_len=32, tie_embeddings=False)
    params = gpt_mod.init(jax.random.PRNGKey(0), config)
    out = str(tmp_path / "g")
    hf.save_pretrained(out, "gpt", config, params)
    reloaded = transformers.GPT2LMHeadModel.from_pretrained(out).eval()
    tokens = np.arange(16, dtype=np.int32).reshape(2, 8) % 64
    ours = np.asarray(gpt_mod.forward(params, jnp.asarray(tokens), config))
    with torch.no_grad():
        theirs = reloaded(torch.from_numpy(tokens).long()).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)


def test_gpt2_untied_export_reingests(tmp_path):
    """Our own untied-GPT export must round-trip through load_pretrained
    with the trained head intact (not silently re-tied)."""
    from accelerate_tpu.models import gpt as gpt_mod

    config = gpt_mod.GPTConfig.tiny(vocab_size=64, max_seq_len=32, tie_embeddings=False)
    params = gpt_mod.init(jax.random.PRNGKey(3), config)
    out = str(tmp_path / "g")
    hf.save_pretrained(out, "gpt", config, params)
    loaded = hf.load_pretrained(out, mesh=build_mesh(MeshConfig()))
    assert not loaded.config.tie_embeddings
    tokens = np.arange(16, dtype=np.int32).reshape(2, 8) % 64
    ours = np.asarray(gpt_mod.forward(params, jnp.asarray(tokens), config))
    theirs = np.asarray(
        gpt_mod.forward(loaded.params, jnp.asarray(tokens), loaded.config)
    )
    np.testing.assert_allclose(theirs, ours, atol=1e-5, rtol=1e-5)


class TestHubIdResolution:
    """Hub ids resolve cache-first (fully offline
    against a pre-populated HF_HUB_CACHE); uncached ids in an air-gapped
    environment fail with the pre-download remedy."""

    def _fake_cache(self, tmp_path, org, name):
        """A minimal HF hub cache layout for one repo."""
        repo_dir = tmp_path / "hub" / f"models--{org}--{name}"
        snap = repo_dir / "snapshots" / "0000000000000000000000000000000000000000"
        snap.mkdir(parents=True)
        (repo_dir / "refs").mkdir()
        (repo_dir / "refs" / "main").write_text(
            "0000000000000000000000000000000000000000"
        )
        cfg = transformers.LlamaConfig(
            vocab_size=64, hidden_size=16, intermediate_size=32,
            num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
            max_position_embeddings=32, tie_word_embeddings=False,
        )
        torch.manual_seed(20)
        model = transformers.LlamaForCausalLM(cfg).eval()
        model.save_pretrained(str(snap), safe_serialization=True)
        return str(tmp_path / "hub")

    def test_cached_hub_id_loads_offline(self, tmp_path, monkeypatch):
        cache = self._fake_cache(tmp_path, "acme", "tiny-llama")
        monkeypatch.setenv("HF_HUB_CACHE", cache)
        monkeypatch.setenv("HF_HUB_OFFLINE", "1")  # prove no network needed
        loaded = hf.load_pretrained(
            "acme/tiny-llama", mesh=build_mesh(MeshConfig())
        )
        assert loaded.family == "llama" and loaded.config.d_model == 16

    def test_uncached_hub_id_fails_actionably(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HF_HUB_CACHE", str(tmp_path / "empty"))
        monkeypatch.setenv("HF_HUB_OFFLINE", "1")
        with pytest.raises(ValueError, match="huggingface-cli download"):
            hf.from_hf_config("acme/does-not-exist")

    def test_filesystem_paths_never_hit_the_hub(self, tmp_path):
        with pytest.raises(ValueError, match="does not exist"):
            hf.from_hf_config(str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# The gpt family's variant layouts: GPT-NeoX / GPT-J / OPT — the reference's
# published big-model-inference table (reference
# benchmarks/big_model_inference/README.md:27-37).
class TestGPTNeoXParity:
    def _tiny(self, **over):
        kw = dict(
            vocab_size=128, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=64, rotary_pct=0.5,
            use_parallel_residual=True, tie_word_embeddings=False,
        )
        kw.update(over)
        return transformers.GPTNeoXConfig(**kw)

    def test_config_translation(self, tmp_path):
        torch.manual_seed(30)
        model = transformers.GPTNeoXForCausalLM(self._tiny()).eval()
        repo = _save_hf(model, tmp_path, "neox")
        family, config = hf.from_hf_config(repo)
        assert family == "gpt"
        assert config.hf_layout == "gpt_neox"
        assert config.positional == "rotary"
        assert config.rotary_dim == 4  # head_dim 8 * rotary_pct 0.5
        assert not config.rotary_interleaved
        assert config.parallel_residual and not config.shared_parallel_norm
        assert config.activation == "gelu"

    @pytest.mark.parametrize("parallel", [True, False])
    def test_forward_matches_transformers(self, tmp_path, parallel):
        torch.manual_seed(31)
        model = transformers.GPTNeoXForCausalLM(
            self._tiny(use_parallel_residual=parallel)
        ).eval()
        repo = _save_hf(model, tmp_path, "neox")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()), min_weight_size=1)
        tokens = np.arange(20, dtype=np.int32).reshape(2, 10) % 128
        ours = np.asarray(gpt.forward(loaded.params, jnp.asarray(tokens), loaded.config))
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)

    def test_forward_matches_on_tp_mesh(self, tmp_path):
        """The fused-qkv per-head fetcher must slice correctly when heads
        are sharded over a tensor axis."""
        torch.manual_seed(32)
        model = transformers.GPTNeoXForCausalLM(self._tiny()).eval()
        repo = _save_hf(model, tmp_path, "neox")
        mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=4))
        loaded = hf.load_pretrained(repo, mesh=mesh, min_weight_size=1)
        tokens = np.arange(20, dtype=np.int32).reshape(2, 10) % 128
        ours = np.asarray(gpt.forward(loaded.params, jnp.asarray(tokens), loaded.config))
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)

    def test_export_round_trip(self, tmp_path):
        torch.manual_seed(33)
        model = transformers.GPTNeoXForCausalLM(self._tiny()).eval()
        repo = _save_hf(model, tmp_path, "neox")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        out = str(tmp_path / "exp")
        hf.save_pretrained(out, loaded.family, loaded.config, loaded.params)
        reloaded = transformers.GPTNeoXForCausalLM.from_pretrained(out).eval()
        tokens = torch.arange(20).reshape(2, 10) % 128
        with torch.no_grad():
            np.testing.assert_allclose(
                reloaded(tokens).logits.numpy(), model(tokens).logits.numpy(),
                atol=5e-5, rtol=2e-4,
            )

    def test_rope_scaled_neox_rejected(self, tmp_path):
        cfg = self._tiny()
        d = tmp_path / "rs"
        d.mkdir()
        payload = cfg.to_dict()
        payload["rope_scaling"] = {"rope_type": "linear", "factor": 2.0}
        json.dump(payload, open(d / "config.json", "w"))
        with pytest.raises(ValueError, match="rope_scaling"):
            hf.from_hf_config(str(d / "config.json"))


class TestGPTJParity:
    def _model(self, seed=40):
        cfg = transformers.GPTJConfig(
            vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4,
            rotary_dim=4, tie_word_embeddings=False,
        )
        torch.manual_seed(seed)
        return transformers.GPTJForCausalLM(cfg).eval()

    def test_config_translation(self, tmp_path):
        repo = _save_hf(self._model(), tmp_path, "gptj")
        family, config = hf.from_hf_config(repo)
        assert family == "gpt"
        assert config.hf_layout == "gptj"
        assert config.rotary_interleaved
        assert config.rotary_dim == 4
        assert config.parallel_residual and config.shared_parallel_norm
        assert not config.attn_bias and config.head_bias

    def test_forward_matches_transformers(self, tmp_path):
        model = self._model(41)
        repo = _save_hf(model, tmp_path, "gptj")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()), min_weight_size=1)
        tokens = np.arange(20, dtype=np.int32).reshape(2, 10) % 128
        ours = np.asarray(gpt.forward(loaded.params, jnp.asarray(tokens), loaded.config))
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)

    def test_decode_matches_forward(self, tmp_path):
        """Interleaved partial rotary must agree between the full forward
        and the KV-cache decode path."""
        model = self._model(42)
        repo = _save_hf(model, tmp_path, "gptj")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        tokens = np.arange(16, dtype=np.int32).reshape(2, 8) % 128
        full = np.asarray(gpt.forward(loaded.params, jnp.asarray(tokens), loaded.config))
        cache = gpt.init_cache(loaded.config, 2, 16, dtype=jnp.float32)
        inc, _ = gpt.forward_with_cache(loaded.params, jnp.asarray(tokens), cache, loaded.config)
        np.testing.assert_allclose(np.asarray(inc), full, atol=1e-5, rtol=1e-5)

    def test_export_round_trip(self, tmp_path):
        model = self._model(43)
        repo = _save_hf(model, tmp_path, "gptj")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        out = str(tmp_path / "exp")
        hf.save_pretrained(out, loaded.family, loaded.config, loaded.params)
        reloaded = transformers.GPTJForCausalLM.from_pretrained(out).eval()
        tokens = torch.arange(20).reshape(2, 10) % 128
        with torch.no_grad():
            np.testing.assert_allclose(
                reloaded(tokens).logits.numpy(), model(tokens).logits.numpy(),
                atol=5e-5, rtol=2e-4,
            )


class TestOPTParity:
    def _cfg(self, **over):
        kw = dict(
            vocab_size=128, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, ffn_dim=64, max_position_embeddings=64,
            do_layer_norm_before=True, word_embed_proj_dim=32,
        )
        kw.update(over)
        return transformers.OPTConfig(**kw)

    def test_config_translation(self, tmp_path):
        torch.manual_seed(50)
        model = transformers.OPTForCausalLM(self._cfg()).eval()
        repo = _save_hf(model, tmp_path, "opt")
        family, config = hf.from_hf_config(repo)
        assert family == "gpt"
        assert config.hf_layout == "opt"
        assert config.positional == "learned"
        assert config.activation == "relu"
        assert config.tie_embeddings

    def test_forward_matches_transformers(self, tmp_path):
        torch.manual_seed(51)
        model = transformers.OPTForCausalLM(self._cfg()).eval()
        repo = _save_hf(model, tmp_path, "opt")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()), min_weight_size=1)
        tokens = np.arange(20, dtype=np.int32).reshape(2, 10) % 128
        ours = np.asarray(gpt.forward(loaded.params, jnp.asarray(tokens), loaded.config))
        with torch.no_grad():
            theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=5e-4, rtol=2e-3)

    def test_export_round_trip(self, tmp_path):
        torch.manual_seed(52)
        model = transformers.OPTForCausalLM(self._cfg()).eval()
        repo = _save_hf(model, tmp_path, "opt")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        out = str(tmp_path / "exp")
        hf.save_pretrained(out, loaded.family, loaded.config, loaded.params)
        reloaded = transformers.OPTForCausalLM.from_pretrained(out).eval()
        tokens = torch.arange(20).reshape(2, 10) % 128
        with torch.no_grad():
            np.testing.assert_allclose(
                reloaded(tokens).logits.numpy(), model(tokens).logits.numpy(),
                atol=5e-5, rtol=2e-4,
            )

    def test_postln_350m_layout_rejected(self, tmp_path):
        d = tmp_path / "pl"
        d.mkdir()
        json.dump(self._cfg(do_layer_norm_before=False).to_dict(), open(d / "config.json", "w"))
        with pytest.raises(ValueError, match="post-layernorm"):
            hf.from_hf_config(str(d / "config.json"))

    def test_projected_embeddings_rejected(self, tmp_path):
        d = tmp_path / "pe"
        d.mkdir()
        json.dump(self._cfg(word_embed_proj_dim=16).to_dict(), open(d / "config.json", "w"))
        with pytest.raises(ValueError, match="word_embed_proj_dim"):
            hf.from_hf_config(str(d / "config.json"))

    def test_untied_head_round_trips(self, tmp_path):
        """An untied OPT head must export (not silently drop) and re-ingest."""
        torch.manual_seed(53)
        model = transformers.OPTForCausalLM(self._cfg(tie_word_embeddings=False)).eval()
        repo = _save_hf(model, tmp_path, "optu")
        loaded = hf.load_pretrained(repo, mesh=build_mesh(MeshConfig()))
        assert "lm_head" in loaded.params
        out = str(tmp_path / "exp")
        hf.save_pretrained(out, loaded.family, loaded.config, loaded.params)
        reloaded = transformers.OPTForCausalLM.from_pretrained(out).eval()
        tokens = torch.arange(20).reshape(2, 10) % 128
        with torch.no_grad():
            np.testing.assert_allclose(
                reloaded(tokens).logits.numpy(), model(tokens).logits.numpy(),
                atol=5e-5, rtol=2e-4,
            )
