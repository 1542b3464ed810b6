"""Generation loop early exit (`generation.Generator`).

With an ``eos_token_id`` configured, the host decode loop polls the carried
``done`` mask every ``eos_check_every`` steps and stops once every row has
finished — so short completions cost fewer decode steps than the
``max_new_tokens`` budget — while staying BIT-IDENTICAL to the always-run-
the-full-budget loop (the skipped tail is pure pad by construction).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import GenerationConfig, Generator
from accelerate_tpu.models import llama

CFG = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=256)


@pytest.fixture(scope="module")
def params():
    return llama.init(jax.random.PRNGKey(1), CFG)


def _pair():
    return (
        lambda p, t, c: llama.forward_with_cache(p, t, c, CFG),
        lambda b, m: llama.init_cache(CFG, b, m),
    )


def _free_run(params, prompt, n):
    ap, ic = _pair()
    return np.asarray(Generator(ap, ic, GenerationConfig(max_new_tokens=n))(params, prompt))


class TestEarlyExit:
    def test_shorter_completions_cost_fewer_steps_and_match(self, params):
        """Both rows hit EOS early -> the loop exits well under budget, and
        the padded output equals the full-budget loop's bit-for-bit."""
        ap, ic = _pair()
        budget = 48
        prompt = jnp.asarray(np.tile(np.arange(5, dtype=np.int32)[None] % 61, (2, 1)))
        free = np.asarray(_free_run(params, prompt, budget))[0, 5:]
        # The first token (from index 2 on) the stream has not emitted before:
        # a random model may repeat itself, and an earlier occurrence of the
        # chosen token would be the EOS instead. Identical rows -> both hit it.
        k = next(i for i in range(2, budget) if free[i] not in free[:i])
        assert k < 20  # early enough for the loop to exit well under budget
        eos = int(free[k])
        config = GenerationConfig(max_new_tokens=budget, eos_token_id=eos, pad_token_id=0)
        early = Generator(ap, ic, config, eos_check_every=4)
        full = Generator(ap, ic, config, eos_check_every=10_000)
        got = np.asarray(early(params, prompt))
        want = np.asarray(full(params, prompt))
        assert full.last_steps == budget
        assert early.last_steps < budget
        np.testing.assert_array_equal(got, want)
        assert got.shape == (2, 5 + budget)

    def test_exit_waits_for_slowest_row(self, params):
        """Rows finishing at different steps: the loop must run until the
        LAST row's EOS (rounded up to the check interval), not the first's."""
        ap, ic = _pair()
        budget = 48
        rows = np.stack(
            [np.arange(5, dtype=np.int32) % 61, (np.arange(5, dtype=np.int32) * 7 + 3) % 61]
        )
        prompt = jnp.asarray(rows)
        free = _free_run(params, prompt, budget)
        # An eos row 0 emits early; row 1's stream may hit it later (or
        # never — then the full budget runs, which the assertion allows).
        eos = int(free[0, 5 + 1])
        config = GenerationConfig(max_new_tokens=budget, eos_token_id=eos, pad_token_id=0)
        gen = Generator(ap, ic, config, eos_check_every=4)
        got = np.asarray(gen(params, prompt))
        want = np.asarray(Generator(ap, ic, config, eos_check_every=10_000)(params, prompt))
        np.testing.assert_array_equal(got, want)
        row1_new = want[1, 5:]
        if (row1_new == eos).any():
            last_eos_step = int(np.argmax(row1_new == eos)) + 1
            assert gen.last_steps >= last_eos_step
        eos_steps = [
            int(np.argmax(want[r, 5:] == eos)) + 1 if (want[r, 5:] == eos).any() else budget
            for r in range(2)
        ]
        assert gen.last_steps >= max(e for e in eos_steps)

    def test_no_eos_dispatches_full_budget_without_syncs(self, params):
        ap, ic = _pair()
        config = GenerationConfig(max_new_tokens=9)
        gen = Generator(ap, ic, config)
        prompt = jnp.asarray(np.arange(6, dtype=np.int32).reshape(2, 3) % 61)
        out = np.asarray(gen(params, prompt))
        assert gen.last_steps == 9
        assert out.shape == (2, 3 + 9)

    def test_eos_never_hit_runs_full_budget(self, params):
        ap, ic = _pair()
        budget = 12
        prompt = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4) % 61)
        free = _free_run(params, prompt, budget)
        unused = next(t for t in range(61) if t not in set(free[:, 4:].ravel()))
        config = GenerationConfig(max_new_tokens=budget, eos_token_id=unused, pad_token_id=0)
        gen = Generator(ap, ic, config, eos_check_every=3)
        out = np.asarray(gen(params, prompt))
        assert gen.last_steps == budget
        np.testing.assert_array_equal(out[:, 4:], free[:, 4:])
