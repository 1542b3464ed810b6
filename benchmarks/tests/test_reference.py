"""The plain reference against `models/llama.py` at a tiny size on the CPU,
in float32, for both architectures (the q/k/v bias path included): logits,
loss and the gradient norm agree to rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import program
from benchmarks.reference.decoder import Arch, Decoder

TINY = dict(
    vocab_size=300, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=96, rope_theta=1e4, rms_norm_eps=1e-5,
    tie_word_embeddings=False,
)


@pytest.mark.parametrize("qkv_bias", [False, True], ids=["mistral-like", "qwen2-like"])
def test_reference_agrees_with_the_program(qkv_bias):
    from accelerate_tpu.models import llama

    config = dict(TINY, program={"qkv_bias": qkv_bias})
    lcfg = program.llama_config(config, max_seq_len=64)
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32), program.init_bf16_params(jax.random.PRNGKey(3), lcfg)
    )
    # the init's norm scales are all zero (g = 1): move them, so that the
    # g - 1 convention is in the comparison
    params["blocks"]["attn_norm"] = params["blocks"]["attn_norm"] + 0.1
    params["final_norm"] = params["final_norm"] - 0.05
    if qkv_bias:
        assert float(jnp.abs(params["blocks"]["attn"]["bq"]).max()) > 0
    tokens = np.random.RandomState(0).randint(0, 300, (2, 64)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(p, {"input_ids": jnp.asarray(tokens)}, lcfg)
        )(params)
        logits = np.asarray(llama.forward(params, jnp.asarray(tokens), lcfg))
    norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))))

    decoder = Decoder(Arch.from_config(config), q_block=16, vocab_block=128)
    get_layer, top = program.reference_weights(params, lcfg, jax.devices()[0])
    out = decoder.loss_and_grad_norm(get_layer, top, tokens)
    assert out["loss"] == pytest.approx(float(loss), rel=1e-5)
    assert out["grad_norm"] == pytest.approx(norm, rel=1e-4)
    # the norm weights' own gradients, under the names `program.norm_scales` gives them
    theirs = program.norm_scales(grads)
    assert sorted(out["norm_grads"]) == sorted(theirs)
    for name, g in out["norm_grads"].items():
        assert g.shape == theirs[name].shape
        np.testing.assert_allclose(g, theirs[name], rtol=1e-3, atol=1e-7)
    ref = decoder.forward_logits(get_layer, top, tokens, [slice(10, 20), slice(0, 64)])
    np.testing.assert_allclose(ref[0], logits[0, 10:20], atol=2e-5)
    np.testing.assert_allclose(ref[1], logits[1], atol=2e-5)
    # blocks of queries are exact: one block gives the same
    whole = Decoder(Arch.from_config(config), q_block=None, vocab_block=512)
    again = whole.loss_and_grad_norm(get_layer, top, tokens)
    assert again["grad_norm"] == pytest.approx(out["grad_norm"], rel=1e-5)


def test_right_padding_does_not_reach_earlier_positions():
    config = dict(TINY, program={"qkv_bias": False})
    lcfg = program.llama_config(config, max_seq_len=64)
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32), program.init_bf16_params(jax.random.PRNGKey(1), lcfg)
    )
    decoder = Decoder(Arch.from_config(config), q_block=16)
    get_layer, top = program.reference_weights(params, lcfg, jax.devices()[0])
    tokens = np.random.RandomState(1).randint(0, 300, (1, 48)).astype(np.int32)
    padded = np.concatenate([tokens, np.zeros((1, 16), np.int32)], axis=1)
    a = decoder.forward_logits(get_layer, top, tokens, [slice(20, 48)])[0]
    b = decoder.forward_logits(get_layer, top, padded, [slice(20, 48)])[0]
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_an_int8_node_is_read_as_values_times_scales():
    node = {"__quant__": jnp.asarray([[1, -2], [3, 4]], jnp.int8), "scale": jnp.asarray([[0.5, 2.0]])}
    np.testing.assert_allclose(np.asarray(program._plain(node)), [[0.5, -4.0], [1.5, 8.0]])
