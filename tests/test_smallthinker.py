"""The SmallThinker family against its plain reference, at tiny widths.

`benchmarks/reference/smallthinker.py` writes the layer's equations with no
kernel, cache or scan and imports nothing from `accelerate_tpu`; everything
here is held to it in float32 on the CPU: the dropless expert layer alone,
the cache-free forward, `Generator` and `serving.Engine` through a cache of
two kinds of layer whose 16-row rings the prompts wrap. Then the pieces
around the model: the cache's bytes by kind, the prefix-cache refusal, the
config mapping from the catalog's keys, the engine's counters, the new
traffic kind and metric arithmetic, and a rehearsal of the benchmark cell.
"""

import copy
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import serving
from accelerate_tpu.generation import GenerationConfig, Generator
from accelerate_tpu.models import hf, layers, smallthinker
from accelerate_tpu.native.pallas import force_kernels
from accelerate_tpu.ops import moe
from benchmarks import flops_moe, harness
from benchmarks.reference import smallthinker as reference
from benchmarks.systems import engine_smallthinker as system
from benchmarks.traffic import closed_loop_regimes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = smallthinker.SmallThinkerConfig.tiny()  # two periods, 16-row window
KERNELS = pytest.mark.parametrize("kernels", ["off", "interpret"])


def published(cfg):
    """The tiny config under the catalog's key names."""
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim, "vocab_size": cfg.vocab_size,
        "moe_num_primary_experts": cfg.n_experts, "moe_num_active_primary_experts": cfg.moe_top_k,
        "moe_ffn_hidden_size": cfg.d_expert, "moe_primary_router_apply_softmax": True,
        "norm_topk_prob": True, "sliding_window_size": cfg.sliding_window,
        "sliding_window_layout": list(cfg.window_layout), "rope_layout": list(cfg.rope_layout),
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "max_position_embeddings": cfg.max_seq_len, "tie_word_embeddings": False,
    }


@pytest.fixture(scope="module")
def params():
    return smallthinker.init(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def oracle(params):
    """tokens (S,) -> the reference's logits at every position."""
    arch = reference.Arch.from_config(published(CFG))
    get_layer, top = system.reference_weights(params, CFG)
    decoder = reference.Decoder(arch, q_block=8, vocab_block=128)

    def logits(tokens):
        tokens = np.asarray(tokens)
        return decoder.forward_logits(get_layer, top, tokens[None], [slice(0, len(tokens))])[0]

    return logits


def prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n, dtype=np.int32)


# --------------------------------------------------------------- expert layer
@KERNELS
@pytest.mark.parametrize("rows", [16, 300])
def test_dropless_experts_match_the_reference_layer(rows, kernels):
    """16 rows (a decode step) and 300 (a chunk), the router reading another
    input than the experts: every assignment is computed, none dropped."""
    E, D, F, k = 8, 64, 32, 3
    p = moe.init_moe(jax.random.PRNGKey(1), D, F, E)
    m = jax.random.normal(jax.random.PRNGKey(2), (rows, D))
    h = jax.random.normal(jax.random.PRNGKey(3), (rows, D))
    arch = reference.Arch.from_config(published(CFG))
    layer = {"experts_gate": p["w_gate"], "experts_up": p["w_up"], "experts_down": p["w_down"]}
    want = reference.experts(arch, layer, m, h @ p["router"])
    with force_kernels(kernels):
        got, counts = jax.jit(lambda p, m, h: moe.moe_dropless(p, m, h, top_k=k))(p, m, h)
    np.testing.assert_allclose(got, want, atol=2e-5)
    counts = {name: int(v) for name, v in counts.items()}
    assert counts["moe_assignments"] == rows * k
    assert counts["moe_rows_computed"] >= rows * k  # padding is counted, nothing is dropped
    assert counts["moe_rows_computed"] == rows * k or kernels == "interpret"
    assert 1 <= counts["moe_experts_touched"] <= E
    assert counts["moe_expert_rows_max"] * E >= rows * k
    # Another router input gives another routing.
    same, _ = moe.moe_dropless(p, m, m, top_k=k)
    assert float(jnp.abs(same - got).max()) > 1e-3


def test_the_shares_of_a_deployment_add_up_to_the_whole_layer():
    """An expert layer told which experts it holds routes over all of them
    and computes its own experts' part: two halves add up to the layer."""
    E, D, F, k, rows = 8, 64, 32, 3, 40
    p = moe.init_moe(jax.random.PRNGKey(4), D, F, E)
    m = jax.random.normal(jax.random.PRNGKey(5), (rows, D))
    h = jax.random.normal(jax.random.PRNGKey(6), (rows, D))
    whole, _ = moe.moe_dropless(p, m, h, top_k=k)
    parts, held = [], 0
    for first in (0, 4):
        share = {name: w if name == "router" else w[first : first + 4] for name, w in p.items()}
        out, counts = moe.moe_dropless(share, m, h, top_k=k, first_expert=first)
        parts.append(out)
        held += int(counts["moe_assignments"])
    assert held == rows * k
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=2e-5)


def test_stacked_experts_are_read_at_the_layer_given():
    E, D, F, k, rows = 8, 64, 32, 3, 24
    p = moe.init_moe(jax.random.PRNGKey(7), D, F, E)
    x = jax.random.normal(jax.random.PRNGKey(8), (rows, D))
    want, _ = moe.moe_dropless(p, x, top_k=k)
    stacked = {name: jnp.stack([jnp.full_like(w, jnp.nan), w]) for name, w in p.items()}
    with force_kernels("interpret"):
        got, _ = jax.jit(lambda p, x, l: moe.moe_dropless(p, x, top_k=k, layer=l))(stacked, x, 1)
    np.testing.assert_allclose(got, want, atol=2e-5)


# --------------------------------------------------------------------- model
def test_forward_matches_the_reference(params, oracle):
    """Two periods of the pattern (full + no rotary, then three windowed +
    rotary layers), 48 tokens: three windows long."""
    tokens = prompt(0, 48)
    logits = smallthinker.forward(params, tokens[None], CFG)[0]
    np.testing.assert_allclose(logits, oracle(tokens), atol=1e-4)


def test_the_pattern_is_scanned_by_periods():
    assert CFG.period == 4 and CFG.n_window_layers == 6
    assert smallthinker.SmallThinkerConfig.tiny(window_layout=(), rope_layout=()).period == 1
    with pytest.raises(ValueError, match="8 layers"):
        smallthinker.SmallThinkerConfig.tiny(window_layout=(0, 1))


@pytest.mark.parametrize("n_prompt", [9, 40])
def test_generator_matches_the_reference(params, oracle, n_prompt):
    """`Generator` prefills the whole prompt at once (40 tokens into 16-row
    rings) and decodes on: every token it chose is the reference's argmax
    over prompt + what was served before it."""
    gen = Generator(
        lambda p, t, c: smallthinker.forward_with_cache(p, t, c, CFG),
        lambda b, m: smallthinker.init_cache(CFG, b, m, dtype=jnp.float32),
        GenerationConfig(max_new_tokens=12),
    )
    tokens = prompt(1, n_prompt)
    out = np.asarray(gen(params, jnp.asarray(tokens)[None]))[0]
    logits = oracle(out)
    assert (out[n_prompt:] == logits[n_prompt - 1 : -1].argmax(-1)).all()


def test_cached_forward_equals_the_cache_free_forward(params):
    """Chunks of 8 through the cache, the second and later ones wrapping the
    ring; a chunk whose last rows are a pad tail leaves the ring intact."""
    tokens = prompt(2, 40)
    want = smallthinker.forward(params, tokens[None], CFG)[0]
    cache = smallthinker.init_cache(CFG, 1, 64, dtype=jnp.float32)
    got = []
    for start in range(0, 40, 8):
        chunk = np.zeros((1, 12), np.int32)  # 8 real rows and a pad tail of 4
        chunk[0, :8] = tokens[start : start + 8]
        logits, cache = smallthinker.forward_with_cache(
            params, chunk, dict(cache, length=jnp.int32(start), valid=jnp.int32(8)), CFG
        )
        got.append(logits[0, :8])
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=1e-4)


@KERNELS
def test_engine_matches_the_reference_and_the_generator(params, oracle, kernels):
    """Chunked prefill that wraps a 16-row ring (buckets 8 and 16), decode
    across the wrap, four requests on three slots with different cursors."""
    requests = [(5, 10), (23, 9), (40, 12), (14, 20)]  # 14 + 20 crosses the window while decoding
    with force_kernels(kernels):
        engine = serving.Engine(
            lambda p, t, c: smallthinker.forward_with_cache(p, t, c, CFG),
            lambda b, m: smallthinker.init_cache(CFG, b, m, dtype=jnp.float32),
            params, GenerationConfig(), slots=3, buckets=(8, 16), max_len=64,
        )
        prompts = [prompt(10 + i, n) for i, (n, _) in enumerate(requests)]
        for p, (_, new) in zip(prompts, requests):
            engine.submit(p, max_new_tokens=new)
        done = {c.rid: c for c in engine.run_until_idle()}
        assert engine.stats["decode_in_place"] == (kernels == "interpret")
        for rid, (p, (n, new)) in enumerate(zip(prompts, requests)):
            served = done[rid].tokens[:new]
            logits = oracle(np.concatenate([p, served]))
            assert (served == logits[n - 1 : -1].argmax(-1)).all(), rid
            gen = Generator(
                lambda p, t, c: smallthinker.forward_with_cache(p, t, c, CFG),
                lambda b, m: smallthinker.init_cache(CFG, b, m, dtype=jnp.float32),
                GenerationConfig(max_new_tokens=new),
            )
            assert (np.asarray(gen(params, jnp.asarray(p)[None]))[0, n:] == served).all(), rid
    assert engine._decode._cache_size() == 1 and engine._prefill._cache_size() == 2


def test_engine_counts_routing_and_live_rows(params):
    """The expert layer's counts ride the decode step's fetch into
    `Engine.stats`; the live rows come from the cursors, a ring's capped."""
    engine = serving.Engine(
        lambda p, t, c: smallthinker.forward_with_cache(p, t, c, CFG),
        lambda b, m: smallthinker.init_cache(CFG, b, m, dtype=jnp.float32),
        params, GenerationConfig(), slots=2, buckets=(8, 16), max_len=64,
    )
    engine.submit(prompt(20, 30), max_new_tokens=5)
    engine.run_until_idle()
    s = engine.stats
    steps = s["decode_steps"]
    assert steps == 4  # the first token comes from the prefill
    # Both slots' rows are routed in every layer of every step (static shapes).
    assert s["moe_assignments"] == steps * CFG.n_layers * 2 * CFG.moe_top_k
    assert s["moe_rows_computed"] >= s["moe_assignments"]
    assert steps * CFG.n_layers <= s["moe_experts_touched"] <= steps * CFG.n_layers * 2 * CFG.moe_top_k
    assert s["kv_rows_live_full"] == sum(30 + i + 1 for i in range(steps))
    assert s["kv_rows_live_window"] == steps * CFG.sliding_window


def test_init_cache_bytes_by_kind():
    cache = smallthinker.init_cache(CFG, 3, 64)
    lanes = CFG.num_kv_heads * CFG.head_dim
    assert cache["k"].shape == cache["v"].shape == (2, 3, 64, lanes)
    assert cache["k_win"].shape == cache["v_win"].shape == (6, 3, 16, lanes)
    nbytes = sum(v.nbytes for name, v in cache.items() if name != "length")
    assert nbytes == 3 * 2 * lanes * 2 * (2 * 64 + 6 * 16)
    # A slot shorter than the window has no ring to speak of.
    assert smallthinker.init_cache(CFG, 1, 8)["k_win"].shape[2] == 8
    # At the published sizes: 176 MB a slot of 16,384, not 403.
    full = smallthinker.SmallThinkerConfig(
        n_layers=12, window_layout=(0, 1, 1, 1) * 3, rope_layout=(0, 1, 1, 1) * 3
    )
    shapes = jax.eval_shape(lambda: smallthinker.init_cache(full, 1, 16384))
    slot = sum(np.prod(s.shape) * 2 for name, s in shapes.items() if name != "length")
    assert slot == 3 * 16384 * 2048 + 9 * 4096 * 2048 == 176_160_768


def test_prefix_cache_is_refused_over_ring_leaves(params):
    make = lambda **kw: serving.Engine(
        lambda p, t, c: smallthinker.forward_with_cache(p, t, c, CFG),
        lambda b, m: smallthinker.init_cache(CFG, b, m),
        params, GenerationConfig(), slots=2, buckets=(8,), **kw,
    )
    with pytest.raises(ValueError, match="ring"):
        make(max_len=64, prefix_cache=True)
    engine = make(max_len=64)  # by default it is off, and says so
    assert engine.prefix_cache is None and engine.stats["prefix_cache_off_for_ring"] == 1
    # No leaf is shorter than a slot of 16: nothing wraps, the cache may stay.
    short = make(max_len=16, prefix_cache=True)
    assert short.prefix_cache is not None and short.stats["prefix_cache_off_for_ring"] == 0


# ---------------------------------------------------------------- ring leaves
def test_ring_positions_and_writes():
    W = 8
    held = np.asarray(layers.ring_positions(jnp.asarray([0, 3, 8, 13]), W, 4))
    assert (held[0] == -1).all()
    assert held[1].tolist() == [0, 1, 2, -1, -1, -1, -1, -1]
    assert held[2].tolist() == list(range(8))
    assert held[3].tolist() == [8, 9, 10, 11, 12, 5, 6, 7]
    buf = jnp.full((2, 1, W, 1), -1.0)
    rows = jnp.arange(100, 106, dtype=jnp.float32).reshape(1, 6, 1)
    # Six rows at cursor 5 wrap; only four are real.
    out = layers.cache_write_stacked(buf, 1, rows, jnp.int32(5), ring=True, valid=jnp.int32(4))
    assert (np.asarray(out[0]) == -1).all()
    assert np.asarray(out[1, 0, :, 0]).tolist() == [103, -1, -1, -1, -1, 100, 101, 102]
    # More real rows than the ring holds: the last W survive.
    rows = jnp.arange(20, dtype=jnp.float32).reshape(1, 20, 1)
    out = layers.cache_write_stacked(buf, 0, rows, jnp.int32(0), ring=True)
    assert np.asarray(out[0, 0, :, 0]).tolist() == [16, 17, 18, 19, 12, 13, 14, 15]
    # One row (a decode step), per-row cursors.
    out = layers.cache_write_stacked(
        jnp.zeros((1, 2, W, 1)), 0, jnp.ones((2, 1, 1)), jnp.asarray([9, 16]), ring=True
    )
    assert np.asarray(out[0, :, :, 0]).argmax(-1).tolist() == [1, 0]


# -------------------------------------------------------------------- config
def test_config_maps_from_the_catalog_keys():
    with open(os.path.join(REPO, "benchmarks", "configs", "smallthinker-21b-a3b-12l.json")) as f:
        config = json.load(f)
    family, cfg = hf.from_hf_config({**config, "model_type": "smallthinker"})
    assert family == "smallthinker"
    # Only the depth is cut: the published layouts stay whole, their first 12 entries apply.
    assert config["reduced"] == ["num_hidden_layers"] and len(config["sliding_window_layout"]) == 52
    assert reference.Arch.from_config(config).sliding_window_layout == cfg.window_layout
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (2560, 28, 4, 128)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.d_expert) == (64, 6, 768)
    assert (cfg.sliding_window, cfg.vocab_size, cfg.rope_theta) == (4096, 151936, 1.5e6)
    assert cfg.n_layers == 12 and cfg.period == 4 and not cfg.tie_embeddings
    assert cfg.window_layout == cfg.rope_layout == (0, 1, 1, 1) * 3
    assert cfg.param_count() == config["program"]["parameters"] == 5_561_448_960
    # Every expert key the source has is consumed; there is no secondary one.
    assert {k for k in config if k.startswith("moe_")} == {
        "moe_ffn_hidden_size", "moe_num_active_primary_experts", "moe_num_primary_experts",
        "moe_primary_router_apply_softmax",
    }
    with pytest.raises(ValueError, match="softmax"):
        hf.from_hf_config({**config, "model_type": "smallthinker", "moe_primary_router_apply_softmax": False})


# ----------------------------------------------------------------- benchmark
def test_regime_traffic_is_one_fixed_set():
    with open(os.path.join(REPO, "benchmarks", "workloads", "smallthinker-serve-mixed.json")) as f:
        traffic = json.load(f)["traffic"]
    prompts, news, which = closed_loop_regimes.request_lengths(traffic)
    again = closed_loop_regimes.request_lengths(traffic)
    assert all((a == b).all() for a, b in zip((prompts, news, which), again))
    n = traffic["clients"] * traffic["requests_per_client"]
    assert len(prompts) == n and (which == 1).sum() == n - round(0.7 * n)
    short, long = prompts[which == 0], prompts[which == 1]
    assert short.min() >= 32 and short.max() <= 2048 and 280 <= np.median(short) <= 320
    assert long.min() >= 4096 and long.max() <= 14336
    assert (prompts + news).max() <= 16384
    other = closed_loop_regimes.request_lengths({**traffic, "schedule_seed": 30})
    assert sorted(other[0]) == sorted(prompts) and (other[0] != prompts).any()
    sched = closed_loop_regimes.schedule(traffic, 30.0)
    assert len(sched.initial) == 32 and sched.measured_by == "completion"
    assert sched.after(sched.initial[0], 31.0) is None


def test_the_arithmetic_of_the_rooflines():
    with open(os.path.join(REPO, "benchmarks", "configs", "smallthinker-21b-a3b-12l.json")) as f:
        config = json.load(f)
    assert flops_moe.expert_params(config) * 64 == 377_487_360
    assert flops_moe.dense_layer_params(config) == 20_971_520 + 163_840
    assert flops_moe.kv_row_bytes(config) == 2048 and flops_moe.window_layers(config) == 9
    step = flops_moe.decode_step_bytes(config, 12 * 50, 16 * 9000, 16 * 4096)
    weights = 12 * 21_135_360 * 2 + 12 * 50 * 5_898_240 * 2 + 2560 * 151_936 * 2
    assert step == weights + 2048 * (3 * 16 * 9000 + 9 * 16 * 4096)
    assert flops_moe.expert_flops(config, 1024 * 6 * 12) == 1024 * 6 * 12 * 2 * 5_898_240


def test_new_readers_find_nothing_in_a_program_without_the_counters():
    """Laid over the parent commit, the new metrics' readers return None."""
    from benchmarks.metrics.readers import counter_complement, moe_decode_step_roofline

    reading = harness.Reading(
        outcome={"counters": {"decode_steps": 10}}, trace=None, spans=None, cell={}, config={},
        peaks={}, chips=1,
    )
    assert counter_complement.read(reading, "moe_assignments", "moe_rows_computed") is None
    assert moe_decode_step_roofline.read(reading, "^jit_decode_fn") is None
    reading.outcome["counters"].update(moe_assignments=96, moe_rows_computed=768)
    assert counter_complement.read(reading, "moe_assignments", "moe_rows_computed") == 87.5


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_through_the_harness(trace, monkeypatch, tmp_path):
    """`smallthinker-serve-mixed` end to end at tiny widths: files found by
    name, the probe judged by the family's reference, the window's
    invariants, the last line's shape."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    from benchmarks.check_correct import REHEARSAL_TOLERANCES

    line = harness.run_cell(
        "smallthinker-serve-mixed", 7, 1.0, trace, time.perf_counter(),
        shrink=system.shrink, tolerances=REHEARSAL_TOLERANCES,
    )
    assert line["correct"] is True and line["failed"] == 0 and line["rehearsal"] is True
    assert line["attempted"] > 0
    names = {m["name"] for m in harness.benchmark_file()["per_layer" if trace else "end_to_end"]
             if "smallthinker-serve-mixed" in m.get("workloads", ["smallthinker-serve-mixed"])}
    if trace:
        # Counters are exact on any backend; device times need a device trace.
        assert {"slots_busy_share.mixed", "moe_padding_share.mixed"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == names == {"serve_tokens_per_s", "setup_s"}
