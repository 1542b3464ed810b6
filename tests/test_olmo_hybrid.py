"""`models/olmo_hybrid.py` at a tiny size on the CPU, in float32: the family
against the plain reference (`benchmarks/reference/olmo_hybrid.py`) on logits,
cache-free and through the cache; the config mapping; the kernels' path in
interpret mode."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import GenerationConfig
from accelerate_tpu.models import hf, olmo_hybrid
from accelerate_tpu.models.layers import is_state_leaf
from accelerate_tpu.native.pallas.dispatch import force_kernels
from benchmarks.reference.olmo_hybrid import Arch, Decoder
from benchmarks.systems import engine_olmo_hybrid as system

PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["linear_attention", "linear_attention", "linear_attention", "full_attention"] * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
TINY = dict(
    PUBLISHED, vocab_size=300, hidden_size=64, intermediate_size=96, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=4, linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=16, max_position_embeddings=256,
    program={"model_type": "olmo_hybrid"},
)


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(system.model_config(TINY, 256), attention_q_block=8)
    params = system.init_params(3, cfg, jax.devices()[0])  # every mechanism given weight
    return cfg, jax.tree.map(lambda a: a.astype(jnp.float32), params)


def _reference(cfg, params, tokens, **what_if):
    arch = dataclasses.replace(Arch.from_config(TINY), **what_if)
    get_layer, top = system.reference_weights(params, cfg)
    decoder = Decoder(arch, q_block=16, vocab_block=128)
    return np.stack(decoder.forward_logits(get_layer, top, tokens, [slice(0, tokens.shape[1])] * len(tokens)))


def _reference_states(cfg, params, tokens, after, **what_if):
    """The rule's states after each row's first ``after`` tokens: (rows, linear layers, H, d_k, d_v)."""
    arch = dataclasses.replace(Arch.from_config(TINY), **what_if)
    get_layer, top = system.reference_weights(params, cfg)
    decoder = Decoder(arch, q_block=16, vocab_block=128)
    _, states = decoder.forward(get_layer, top, tokens, [slice(0, 1)] * len(tokens), [(after,)] * len(tokens))
    return np.stack(states)[:, :, 0]


def test_the_published_config_maps():
    family, cfg = hf.from_hf_config(PUBLISHED)
    assert family == "olmo_hybrid" and cfg.period == 4 and cfg.n_linear_layers == 24
    assert cfg.head_dim == 128 and cfg.conv_channels == 11520
    assert cfg.param_count() == 7_430_870_688
    cut = hf.from_hf_config(dict(PUBLISHED, num_hidden_layers=16))[1]  # the layout's first entries
    assert cut.kinds == tuple(PUBLISHED["layer_types"][:16]) and cut.param_count() == 4_100_788_944


@pytest.mark.parametrize(
    "change,key",
    [
        ({"linear_allow_neg_eigval": False}, "linear_allow_neg_eigval"),
        ({"linear_num_value_heads": 60}, "linear_num_value_heads"),
        ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_theta"),
        ({"attention_bias": True}, "attention_bias"),
        ({"layer_types": ["sliding_attention"] * 32}, "layer_types"),
    ],
)
def test_what_the_family_does_not_implement_is_refused_by_its_key(change, key):
    with pytest.raises(ValueError, match=key):
        hf.from_hf_config(dict(PUBLISHED, **change))


def test_unknown_model_type_names_the_family_among_the_supported():
    with pytest.raises(ValueError, match="olmo_hybrid"):
        hf.from_hf_config({"model_type": "nonesuch"})


def test_param_count_is_the_tree(tiny):
    cfg, params = tiny
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == cfg.param_count()


def test_forward_agrees_with_the_reference(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(0).integers(0, 300, (2, 150)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(olmo_hybrid.forward(params, jnp.asarray(tokens), cfg))
    np.testing.assert_allclose(logits, _reference(cfg, params, tokens), atol=2e-3)


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("split", [(70,), (64, 64), (33, 64, 20)], ids=["one-chunk", "on-boundary", "off-boundary"])
def test_prefill_then_decode_agrees_with_the_reference(tiny, split, mode):
    """Chunks through the cache, then token by token, against the
    reference's one full forward: the state and the convolution's tail are
    handed from call to call; with the kernels in interpret mode too."""
    cfg, params = tiny
    tokens = np.random.default_rng(1).integers(0, 300, (2, 160)).astype(np.int32)
    cache = olmo_hybrid.init_cache(cfg, 2, 192, jnp.float32)
    assert {n for n in cache if is_state_leaf(n)} == {"state_gdn", "state_conv"}
    out, at = [], 0
    # Jitted here, inside the mode: one program a shape, traced with this mode's lowering.
    step = jax.jit(lambda p, t, c: olmo_hybrid.forward_with_cache(p, t, c, cfg))
    with force_kernels(mode), jax.default_matmul_precision("highest"):
        for n in split:
            logits, cache = step(params, jnp.asarray(tokens[:, at : at + n]), cache)
            out.append(logits)
            at += n
        for t in range(at, 160):
            logits, cache = step(params, jnp.asarray(tokens[:, t : t + 1]), cache)
            out.append(logits)
    np.testing.assert_allclose(np.concatenate(out, axis=1), _reference(cfg, params, tokens), atol=3e-3)
    # and the states it ends on are the reference's (layers, rows, ...) -> (rows, layers, ...)
    want = _reference_states(cfg, params, tokens, 160)
    np.testing.assert_allclose(np.swapaxes(np.asarray(cache["state_gdn"]), 0, 1), want, atol=1e-4)


def test_the_reference_hands_back_the_state_at_a_position_and_its_precision_shows(tiny):
    """The states after n tokens do not depend on what follows, and a state
    rounded to bf16 after every token lies a rounding away from the float32
    one: the distance the cell's comparison judges the state by."""
    cfg, params = tiny
    tokens = np.random.default_rng(6).integers(0, 300, (1, 96)).astype(np.int32)
    at_70 = _reference_states(cfg, params, tokens, 70)
    np.testing.assert_allclose(_reference_states(cfg, params, tokens[:, :70], 70), at_70, atol=1e-4)
    assert np.abs(_reference_states(cfg, params, tokens, 96) - at_70).max() > 1e-2
    rounded = _reference_states(cfg, params, tokens, 70, state_dtype="bfloat16")
    d = system.state_distances(
        [np.stack([a, a], axis=1) for a in rounded], [np.stack([a, a], axis=1) for a in at_70]
    )
    assert all(1e-3 < x < 3e-2 for x in d["state_rel_after_prefill"]), d


def test_a_bucket_pad_tail_and_a_non_decoding_row_change_nothing(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(2).integers(0, 300, (2, 80)).astype(np.int32)
    want = _reference(cfg, params, tokens)
    cache = olmo_hybrid.init_cache(cfg, 2, 128, jnp.float32)
    padded = np.concatenate([tokens[:, :50], np.zeros((2, 14), np.int32)], axis=1)
    with jax.default_matmul_precision("highest"):
        first, cache = olmo_hybrid.forward_with_cache(params, jnp.asarray(padded), dict(cache, valid=jnp.int32(50)), cfg)
        cache["length"] = jnp.int32(50)
        # a decode step in which only row 0 decodes: row 1's states stay
        before = {n: np.asarray(cache[n]) for n in cache if is_state_leaf(n)}
        step, after = olmo_hybrid.forward_with_cache(
            params, jnp.asarray(tokens[:, 50:51]), dict(cache, decoding=jnp.array([True, False])), cfg
        )
        for n in before:
            np.testing.assert_array_equal(np.asarray(after[n])[:, 1], before[n][:, 1])
            assert np.abs(np.asarray(after[n])[:, 0] - before[n][:, 0]).max() > 0
        rest, _ = olmo_hybrid.forward_with_cache(params, jnp.asarray(tokens[:, 50:]), cache, cfg)
    np.testing.assert_allclose(first[:, :50], want[:, :50], atol=3e-3)
    np.testing.assert_allclose(step[0, 0], want[0, 50], atol=3e-3)
    np.testing.assert_allclose(rest, want[:, 50:], atol=3e-3)


WHAT_IFS = [
    {"linear_allow_neg_eigval": False}, {"decay": False}, {"conv": False}, {"qk_l2norm": False},
    {"qk_norm": False}, {"rope_full_layers": True}, {"block_norm": "pre"},
]


@pytest.mark.parametrize("what_if", WHAT_IFS, ids=lambda w: next(iter(w)))
def test_every_flag_of_the_reference_moves_the_logits(tiny, what_if):
    """Each assumed convention and each part of the mechanism is a flag of
    the reference's `Arch`, and flipping it is no rounding error."""
    cfg, params = tiny
    tokens = np.random.default_rng(4).integers(0, 300, (1, 96)).astype(np.int32)
    moved = np.abs(_reference(cfg, params, tokens, **what_if) - _reference(cfg, params, tokens))
    assert np.nan_to_num(moved, nan=np.inf).max() > 0.05  # un-normalised keys let the state diverge


def test_the_configuration_file_lists_what_it_assumes():
    with open("benchmarks/configs/olmo-hybrid-7b-16l.json") as f:
        config = json.load(f)
    for key, value in PUBLISHED.items():
        assert config[key] == (16 if key == "num_hidden_layers" else value), key
    assert config["reduced"] == ["num_hidden_layers"] and config["published"]["num_hidden_layers"] == 32
    assert {"block norm", "qk norm", "rotary", "state precision", "l2 norm eps"} <= set(config["assumed"])
    fields = {f.name for f in dataclasses.fields(Arch)}
    assert {"block_norm", "qk_norm", "rope_full_layers", "state_dtype", "l2_eps"} <= fields
    assert system.model_config(config, 2048).param_count() == config["program"]["parameters"]


def test_generate_matches_token_by_token_argmax(tiny):
    cfg, params = tiny
    prompt = np.random.default_rng(5).integers(0, 300, (1, 37)).astype(np.int32)
    out = np.asarray(olmo_hybrid.generate(params, jnp.asarray(prompt), cfg, generation_config=GenerationConfig(max_new_tokens=6)))
    seq = prompt
    for _ in range(6):
        nxt = np.asarray(olmo_hybrid.forward(params, jnp.asarray(seq), cfg))[:, -1].argmax(-1)
        seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], axis=1)
    np.testing.assert_array_equal(out, seq)
