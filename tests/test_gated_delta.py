"""The gated delta rule (`ops/gated_delta.py`): its chunkwise form against
its recurrent form, the convolution and its tail, and the two state kernels
(`native/pallas/gated_delta.py`) in interpret mode against both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.native.pallas import gated_delta as kernels
from accelerate_tpu.native.pallas.dispatch import force_kernels
from accelerate_tpu.ops import gated_delta as gd

H, DK, DV = 3, 8, 16


def _operands(seed, B, T, *, beta=None, g=None):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(B, T, H, DK))) * DK**-0.5
    k = unit(rng.normal(size=(B, T, H, DK)))
    v = rng.normal(size=(B, T, H, DV))
    g = -0.3 * np.abs(rng.normal(size=(B, T, H))) if g is None else np.full((B, T, H), g)
    beta = 2.0 / (1.0 + np.exp(-3.0 * rng.normal(size=(B, T, H)))) if beta is None else np.full((B, T, H), beta)
    state = rng.normal(size=(B, H, DK, DV))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, state))


@pytest.mark.parametrize("T", [1, 63, 64, 65, 128, 150, 256])
def test_chunkwise_form_equals_recurrent_form(T):
    """Chunk boundaries at and off multiples of 64, one token, several chunks."""
    ops = _operands(T, 2, T)
    o_r, s_r = gd.recurrent_gated_delta(*ops)
    o_c, s_c = gd.chunk_gated_delta(*ops)
    np.testing.assert_allclose(o_c, o_r, atol=2e-5)
    np.testing.assert_allclose(s_c, s_r, atol=2e-5)


@pytest.mark.parametrize(
    "beta,g", [(1.999, None), (None, -12.0), (None, -1e-4), (1.999, -1e-4), (1e-3, None)],
    ids=["beta-near-2", "alpha-near-0", "alpha-near-1", "beta-near-2-no-decay", "beta-near-0"],
)
def test_chunkwise_form_at_the_ends_of_the_ranges(beta, g):
    ops = _operands(7, 1, 192, beta=beta, g=g)
    o_r, s_r = gd.recurrent_gated_delta(*ops)
    o_c, s_c = gd.chunk_gated_delta(*ops)
    scale = max(1.0, float(jnp.abs(s_r).max()))
    np.testing.assert_allclose(o_c, o_r, atol=1e-4 * scale)
    np.testing.assert_allclose(s_c, s_r, atol=1e-4 * scale)


def test_correlated_keys_with_beta_near_two_stay_exact():
    """Keys that nearly repeat and a write strength near 2: the triangular
    inverse's entries are large, and block substitution still agrees."""
    q, k, v, g, beta, state = _operands(3, 1, 128, beta=1.9, g=-0.01)
    k = k[:, :1] * 0.9 + 0.1 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    o_r, s_r = gd.recurrent_gated_delta(q, k, v, g, beta, state)
    o_c, s_c = gd.chunk_gated_delta(q, k, v, g, beta, state)
    np.testing.assert_allclose(o_c, o_r, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(s_c, s_r, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("valid", [1, 40, 64, 100])
def test_a_pad_tail_advances_nothing(valid):
    """Rows with g = 0 and beta = 0 (a bucket's pad tail) leave the state
    where the real rows left it."""
    q, k, v, g, beta, state = _operands(11, 1, 128)
    real = (jnp.arange(128) < valid)[None, :, None]
    o_p, s_p = gd.chunk_gated_delta(q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0), state)
    o_r, s_r = gd.recurrent_gated_delta(q[:, :valid], k[:, :valid], v[:, :valid], g[:, :valid], beta[:, :valid], state)
    np.testing.assert_allclose(s_p, s_r, atol=2e-5)
    np.testing.assert_allclose(o_p[:, :valid], o_r, atol=2e-5)


@pytest.mark.parametrize("n", [8, 16, 64])
def test_unit_lower_inverse(n):
    # entries of the size the rule makes: beta (k_i . k_j) decay, |.| < 2 and mostly far under 1
    a = np.tril(0.3 * np.random.default_rng(n).normal(size=(2, 3, n, n)), k=-1).astype(np.float32)
    t = gd.unit_lower_inverse(jnp.asarray(a))
    exact = np.linalg.inv(np.eye(n) + a.astype(np.float64))
    np.testing.assert_allclose(t, exact, rtol=1e-3, atol=1e-4 * np.abs(exact).max())


def test_convolution_in_pieces_equals_the_whole():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 20, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    zero = jnp.zeros((2, 3, 6), jnp.float32)
    whole = gd.causal_conv(x, zero, w)
    # by hand: y_t = sum_j w_j x_{t-3+j}
    padded = np.concatenate([np.zeros((2, 3, 6)), np.asarray(x)], axis=1)
    np.testing.assert_allclose(whole, sum(padded[:, j : j + 20] * np.asarray(w)[j] for j in range(4)), atol=1e-6)
    first = gd.causal_conv(x[:, :7], zero, w)
    tail = gd.conv_tail(x[:, :7], zero)
    np.testing.assert_array_equal(tail, x[:, 4:7])
    second = gd.causal_conv(x[:, 7:], tail, w)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole, atol=1e-6)
    # only the first `valid` rows are real: the tail is cut behind them
    np.testing.assert_array_equal(gd.conv_tail(x[:, :7], zero, jnp.int32(5)), x[:, 2:5])
    np.testing.assert_array_equal(gd.conv_tail(x[:, :7], zero, jnp.int32(2))[:, 1:], x[:, :2])


DECODING = {
    "all": [True] * 4, "none": [False] * 4, "some": [True, False, True, False],
    "last": [False, False, False, True], "first": [True, False, False, False],
}


@pytest.mark.parametrize("which", sorted(DECODING))
def test_gdn_decode_kernel_updates_only_the_decoding_rows_in_place(which):
    rng = np.random.default_rng(1)
    B, L = 4, 3
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = f(B, H, DK), f(B, H, DK), f(B, H, DV)
    alpha, beta = jnp.asarray(rng.uniform(size=(B, H)), jnp.float32), jnp.asarray(2 * rng.uniform(size=(B, H)), jnp.float32)
    stack = f(L, B, H, DK, DV)
    decoding = np.array(DECODING[which])
    o, new = kernels.gdn_decode(q, k, v, alpha, beta, stack, 1, jnp.asarray(decoding), interpret=True)
    want_o, want_s = gd.recurrent_step(q, k, v, alpha, beta, stack[1])
    expect = np.asarray(stack).copy()
    expect[1] = np.where(decoding[:, None, None, None], want_s, stack[1])
    np.testing.assert_allclose(new, expect, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new)[[0, 2]], np.asarray(stack)[[0, 2]])  # the other layers, bit for bit
    np.testing.assert_array_equal(np.asarray(new)[1][~decoding], np.asarray(stack)[1][~decoding])
    np.testing.assert_allclose(o, np.where(decoding[:, None, None], want_o, 0.0), atol=1e-5)
    touched = kernels.slots_touched(jnp.asarray(decoding), B, in_place=True)
    assert int(touched) == max(int(decoding.sum()), 1)
    assert kernels.slots_touched(jnp.asarray(decoding), B, in_place=False) == B


@pytest.mark.parametrize("T", [64, 256])
def test_gdn_chunk_kernel_equals_the_scan(T):
    q, k, v, g, beta, state = _operands(5, 2, T)
    parts = gd.chunk_prepare(q, k, v, g, beta)
    o_k, s_k = kernels.gdn_chunk(parts, state, interpret=True)
    o_x, s_x = gd.chunk_scan(parts, state)
    np.testing.assert_allclose(o_k, o_x, atol=1e-5)
    np.testing.assert_allclose(s_k, s_x, atol=1e-5)
    with force_kernels("interpret"):
        o, s = gd.chunk_gated_delta(q, k, v, g, beta, state)
    o_r, s_r = gd.recurrent_gated_delta(q, k, v, g, beta, state)
    np.testing.assert_allclose(o, o_r, atol=2e-5)
    np.testing.assert_allclose(s, s_r, atol=2e-5)


def test_the_kernels_are_registered_and_named():
    from accelerate_tpu.native.pallas import kernel_status

    names = {k["kernel"] for k in kernel_status()}
    assert {"gdn_decode", "gdn_chunk"} <= names
    assert kernels.decode_supported(jnp.zeros((2, 2, 30, 96, 192), jnp.float32), compiled=True)
    assert not kernels.decode_supported(jnp.zeros((2, 2, 30, 96, 192), jnp.bfloat16))
    assert not kernels.decode_supported(jnp.zeros((2, 30, 96, 192), jnp.float32))
