"""Speculative decoding (`accelerate_tpu/speculative.py`): draft-K +
single-verify generation with exactness guarantees.

Beyond-reference capability (the reference's generate() is transformers',
`big_modeling.py:511` — no speculative path). The invariants tested here
are the ones that make the feature safe to enable blindly:

- greedy speculative output is BIT-IDENTICAL to target-only greedy
  decoding for any draft model;
- sampling follows the target's warped distribution (total-variation
  check against vanilla sampling);
- EOS/pad discipline matches the vanilla generator's exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = [pytest.mark.heavy, pytest.mark.slow]  # speculative-decode compiles; excluded from the tier-1 smoke lane

from accelerate_tpu.generation import GenerationConfig, Generator
from accelerate_tpu.models import gpt, llama
from accelerate_tpu.speculative import SpeculativeGenerator, generate_speculative

TCFG = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=256)
DCFG = llama.LlamaConfig.tiny(
    vocab_size=61, max_seq_len=256, n_layers=1, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=64,
)


@pytest.fixture(scope="module")
def models():
    return llama.init(jax.random.PRNGKey(1), TCFG), llama.init(jax.random.PRNGKey(2), DCFG)


def _llama_pair(cfg):
    return (
        lambda p, t, c: llama.forward_with_cache(p, t, c, cfg),
        lambda b, m: llama.init_cache(cfg, b, m),
    )


def _spec(config, K, tcfg=TCFG, dcfg=DCFG):
    ta, tc = _llama_pair(tcfg)
    da, dc = _llama_pair(dcfg)
    return SpeculativeGenerator(ta, tc, da, dc, config, draft_tokens=K)


def _vanilla(config, params, prompt, cfg=TCFG):
    ta, tc = _llama_pair(cfg)
    return Generator(ta, tc, config)(params, prompt)


class TestGreedyExactness:
    @pytest.mark.parametrize("K", [1, 3, 4])
    def test_matches_vanilla_for_any_draft(self, models, K):
        tp, dp = models
        config = GenerationConfig(max_new_tokens=17)
        prompt = jnp.asarray(np.arange(10, dtype=np.int32).reshape(2, 5) % 61)
        want = _vanilla(config, tp, prompt)
        got = _spec(config, K)(tp, dp, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_self_draft_accepts_everything(self, models):
        tp, _ = models
        config = GenerationConfig(max_new_tokens=16)
        prompt = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4) % 61)
        ta, tc = _llama_pair(TCFG)
        spec = SpeculativeGenerator(ta, tc, ta, tc, config, draft_tokens=4)
        got = spec(tp, tp, prompt)
        want = _vanilla(config, tp, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert spec.last_accept_rate == pytest.approx(1.0)

    def test_budget_respected_mid_iteration(self, models):
        """max_new_tokens not divisible by K+1: the tail iteration's extra
        committed tokens must be dropped, not emitted."""
        tp, dp = models
        config = GenerationConfig(max_new_tokens=7)
        prompt = jnp.asarray(np.arange(6, dtype=np.int32).reshape(2, 3) % 61)
        got = _spec(config, 4)(tp, dp, prompt)
        want = _vanilla(config, tp, prompt)
        assert got.shape == (2, 3 + 7)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestPerRowCommit:
    def test_batched_iterations_track_slowest_row_not_min_commit(self, models):
        """Per-row cache lengths: each row commits its own
        accepted count, so a batched call needs no more verify iterations
        than its slowest row would alone. Under the old shared-scalar
        length, every iteration committed the MINIMUM across rows and the
        batch was strictly slower than its worst member."""
        tp, dp = models
        config = GenerationConfig(max_new_tokens=21)
        rows = np.stack(
            [
                np.arange(5, dtype=np.int32) % 61,
                (np.arange(5, dtype=np.int32) * 7 + 3) % 61,
                (np.arange(5, dtype=np.int32) * 11 + 1) % 61,
            ]
        )
        singles = []
        for r in range(rows.shape[0]):
            spec = _spec(config, 3)
            spec(tp, dp, jnp.asarray(rows[r : r + 1]))
            singles.append(spec.last_iterations)
        batched = _spec(config, 3)
        got = batched(tp, dp, jnp.asarray(rows))
        assert batched.last_iterations <= max(singles)
        # And the batch rows are each bit-identical to their solo greedy run.
        want = _vanilla(config, tp, jnp.asarray(rows))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_all_rows_eos_stops_early(self, models):
        """Once every row is frozen (EOS), the host loop must stop
        dispatching instead of burning the token budget on pad commits."""
        tp, dp = models
        base = GenerationConfig(max_new_tokens=64)
        # Identical rows -> identical greedy continuations -> both rows hit
        # the chosen EOS at the same (early) position.
        prompt = jnp.asarray(np.tile(np.arange(5, dtype=np.int32)[None] % 61, (2, 1)))
        free_run = np.asarray(_vanilla(base, tp, prompt))
        eos = int(free_run[0, 5 + 2])
        config = GenerationConfig(max_new_tokens=64, eos_token_id=eos, pad_token_id=0)
        want = np.asarray(_vanilla(config, tp, prompt))
        assert (want == eos).any(axis=1).all(), "both rows must hit EOS"
        spec = _spec(config, 3)
        got = np.asarray(spec(tp, dp, prompt))
        np.testing.assert_array_equal(got, want)
        # Both rows finished well before 64 tokens; the loop must not have
        # dispatched the full ceil(63/4)=16 iterations' worth of batches
        # beyond the first optimistic dispatch.
        first_dispatch = -(-63 // 4)
        assert spec.last_iterations <= first_dispatch


class TestAcceptRateRegression:
    """A chip run once reported `specdecode_accept_rate 0.0` with a real
    draft model; the suspected accept-comparison misalignment was diagnosed and
    CLEARED (speculative.py module docstring). These tests pin the two
    facts that diagnosis rests on, so a future positional regression in
    the draft or verify path cannot hide behind 'the draft is just bad'."""

    def test_external_draft_equal_params_accepts_everything(self, models):
        """draft == target THROUGH THE EXTERNAL-DRAFT PATH (separate apply
        fns and separately-built caches, bf16 params, GQA): accept rate
        must be ~1.0. A position misalignment anywhere in the draft scan,
        verify forward, or rollback bookkeeping would reject drafts every
        iteration and drop this toward 0."""
        cfg = llama.LlamaConfig.tiny(
            vocab_size=61, max_seq_len=256, num_heads=4, num_kv_heads=2
        )
        tp = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), llama.init(jax.random.PRNGKey(7), cfg)
        )
        ta, tc = _llama_pair(cfg)
        da, dc = _llama_pair(cfg)  # distinct closures: the external-draft path
        config = GenerationConfig(max_new_tokens=24)
        spec = SpeculativeGenerator(ta, tc, da, dc, config, draft_tokens=4)
        prompt = jnp.asarray(np.arange(12, dtype=np.int32).reshape(2, 6) % 61)
        got = spec(tp, tp, prompt)
        want = Generator(ta, tc, config)(tp, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert spec.last_accept_rate == pytest.approx(1.0)

    def test_k1_accept_rate_equals_teacher_forced_agreement(self, models):
        """At K=1 every iteration proposes exactly one draft token, so the
        engine's accept rate must equal the fraction of positions (on the
        target's own greedy stream) where draft argmax == target argmax —
        computed here independently with fresh full-prefill forwards. An
        off-by-one in the accept comparison would send the engine's rate
        to ~1/vocab while the teacher-forced rate stays high."""
        # A layer-prefix draft (first 2 of 4 layers, shared embed/head)
        # keeps teacher-forced agreement well off the floor — random
        # unrelated drafts would make both rates ~1/vocab and the check
        # vacuous.
        tcfg = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=256, n_layers=4)
        dcfg = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=256, n_layers=2)
        tp = llama.init(jax.random.PRNGKey(1), tcfg)
        dp = dict(tp, blocks=jax.tree.map(lambda x: x[:2], tp["blocks"]))
        N = 48
        config = GenerationConfig(max_new_tokens=N)
        prompt = jnp.asarray(np.arange(7, dtype=np.int32)[None] % 61)
        spec = _spec(config, 1, tcfg=tcfg, dcfg=dcfg)
        spec(tp, dp, prompt)
        engine_rate = spec.last_accept_rate
        stream = np.asarray(_vanilla(config, tp, prompt, cfg=tcfg))[0]
        agree = total = 0
        for i in range(prompt.shape[1], len(stream) - 1):
            ctx = jnp.asarray(stream[None, :i])
            tl, _ = llama.forward_with_cache(tp, ctx, llama.init_cache(tcfg, 1, i), tcfg)
            dl, _ = llama.forward_with_cache(dp, ctx, llama.init_cache(dcfg, 1, i), dcfg)
            agree += int(jnp.argmax(tl[0, -1]) == jnp.argmax(dl[0, -1]))
            total += 1
        # The engine proposes on the same greedy stream; rates match up to
        # the boundary effect of the final (budget-capped) iterations.
        assert engine_rate == pytest.approx(agree / total, abs=0.15)
        assert engine_rate > 0.2  # and is far from the ~1/61 misalignment floor


class TestEos:
    def test_eos_truncates_like_vanilla(self, models):
        tp, dp = models
        base = GenerationConfig(max_new_tokens=14)
        prompt = jnp.asarray(np.arange(10, dtype=np.int32).reshape(2, 5) % 61)
        # Pick an eos the greedy continuation genuinely emits so the pad
        # path is exercised, not vacuously green.
        free_run = np.asarray(_vanilla(base, tp, prompt))
        eos = int(free_run[0, 5 + 3])
        config = GenerationConfig(max_new_tokens=14, eos_token_id=eos, pad_token_id=0)
        want = np.asarray(_vanilla(config, tp, prompt))
        got = np.asarray(_spec(config, 3)(tp, dp, prompt))
        np.testing.assert_array_equal(got, want)
        # And the truncation actually happened: after the first generated
        # eos, every position is pad.
        row = got[0, 5:]
        hits = np.where(row == eos)[0]
        assert hits.size > 0
        assert (row[hits[0] + 1:] == 0).all()


class TestSampling:
    def test_accept_rate_nontrivial_and_output_valid(self, models):
        tp, dp = models
        config = GenerationConfig(max_new_tokens=24, do_sample=True, temperature=0.9)
        prompt = jnp.asarray(np.array([[1, 2, 3]], dtype=np.int32))
        spec = _spec(config, 3)
        out = np.asarray(spec(tp, dp, prompt, rng=jax.random.PRNGKey(0)))
        assert out.shape == (1, 3 + 24)
        assert ((0 <= out) & (out < 61)).all()
        # Unrelated random models still overlap substantially at this
        # temperature; exactly-0 would mean the accept test is broken,
        # exactly-1 would mean it isn't testing anything.
        assert 0.05 < spec.last_accept_rate < 0.99

    def test_distribution_matches_target(self):
        """Total-variation check: the marginal of a spec-verified position
        must match vanilla target sampling to sampling noise."""
        tcfg = llama.LlamaConfig.tiny(
            vocab_size=11, d_model=32, n_layers=1, num_heads=2,
            num_kv_heads=2, d_ff=64, max_seq_len=64,
        )
        dcfg = llama.LlamaConfig.tiny(
            vocab_size=11, d_model=16, n_layers=1, num_heads=2,
            num_kv_heads=2, d_ff=32, max_seq_len=64,
        )
        tp = llama.init(jax.random.PRNGKey(1), tcfg)
        dp = llama.init(jax.random.PRNGKey(2), dcfg)
        config = GenerationConfig(max_new_tokens=3, do_sample=True, temperature=0.9)
        B = 768
        prompt = jnp.asarray(np.tile(np.array([[1, 2, 3]], np.int32), (B, 1)))
        ta, tc = _llama_pair(tcfg)
        da, dc = _llama_pair(dcfg)
        van = Generator(ta, tc, config)
        spec = SpeculativeGenerator(ta, tc, da, dc, config, draft_tokens=2)
        vs, ss = [], []
        for i in range(3):
            vs.append(np.asarray(van(tp, prompt, rng=jax.random.PRNGKey(i))))
            ss.append(np.asarray(spec(tp, dp, prompt, rng=jax.random.PRNGKey(100 + i))))
        v, s = np.concatenate(vs), np.concatenate(ss)
        for pos in (4, 5):  # spec-verified positions (2nd/3rd new tokens)
            vf = np.bincount(v[:, pos], minlength=11) / len(v)
            sf = np.bincount(s[:, pos], minlength=11) / len(s)
            tv = 0.5 * np.abs(vf - sf).sum()
            # Noise floor for n=2304 over 11 bins is ~0.03; a pairing or
            # residual bug shows up at 0.1+.
            assert tv < 0.07, f"position {pos}: TV {tv:.3f}"


class TestGptFamily:
    def test_greedy_exact_on_gpt_variant(self):
        """The harness is family-agnostic: same contract works for the gpt
        family (here a rotary GPT-J-style variant)."""
        tcfg = gpt.GPTConfig.tiny(
            vocab_size=61, max_seq_len=256, hf_layout="gptj",
            positional="rotary", rotary_dim=8, rotary_interleaved=True,
            parallel_residual=True, shared_parallel_norm=True,
            attn_bias=False, tie_embeddings=False, head_bias=True,
        )
        dcfg = gpt.GPTConfig.tiny(vocab_size=61, max_seq_len=256, n_layers=1)
        tp = gpt.init(jax.random.PRNGKey(3), tcfg)
        dp = gpt.init(jax.random.PRNGKey(4), dcfg)
        config = GenerationConfig(max_new_tokens=13)
        prompt = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4) % 61)
        want = Generator(
            lambda p, t, c: gpt.forward_with_cache(p, t, c, tcfg),
            lambda b, m: gpt.init_cache(tcfg, b, m), config,
        )(tp, prompt)
        got = generate_speculative(
            tp, dp, prompt,
            target_apply=lambda p, t, c: gpt.forward_with_cache(p, t, c, tcfg),
            target_init_cache=lambda b, m: gpt.init_cache(tcfg, b, m),
            draft_apply=lambda p, t, c: gpt.forward_with_cache(p, t, c, dcfg),
            draft_init_cache=lambda b, m: gpt.init_cache(dcfg, b, m),
            config=config, draft_tokens=3,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_invalid_gpt_variant_combinations_rejected():
    with pytest.raises(ValueError, match="shared_parallel_norm"):
        gpt.GPTConfig.tiny(shared_parallel_norm=True)
    with pytest.raises(ValueError, match="positional"):
        gpt.GPTConfig.tiny(positional="alibi")


def test_zero_budget_returns_prompt_and_keeps_attributes(models):
    tp, dp = models
    config = GenerationConfig(max_new_tokens=4)
    spec = _spec(config, 2)
    prompt = jnp.asarray(np.arange(6, dtype=np.int32).reshape(2, 3) % 61)
    out = spec(tp, dp, prompt, max_new_tokens=0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(prompt))
    assert spec.last_accept_rate == 0.0  # initialized, not AttributeError


def test_pinned_cache_len_shares_compiles(models):
    """Distinct budgets with a pinned cache_len must reuse one compiled
    graph set (the bench methodology depends on this)."""
    tp, dp = models
    config = GenerationConfig(max_new_tokens=12)
    spec = _spec(config, 3)
    prompt = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4) % 61)
    cap = 4 + 12 + 2 * (3 + 1)
    want = _vanilla(config, tp, prompt)
    got_long = spec(tp, dp, prompt, max_new_tokens=12, cache_len=cap)
    np.testing.assert_array_equal(np.asarray(got_long), np.asarray(want))
    # Same capacity, smaller budget: prefix must match; and the jitted
    # steps must not retrace (same cache shapes).
    traces_before = spec._spec_step._cache_size()
    got_short = spec(tp, dp, prompt, max_new_tokens=5, cache_len=cap)
    assert spec._spec_step._cache_size() == traces_before
    np.testing.assert_array_equal(
        np.asarray(got_short), np.asarray(want)[:, : 4 + 5]
    )
    with pytest.raises(ValueError, match="cache_len"):
        spec(tp, dp, prompt, max_new_tokens=40, cache_len=cap)
