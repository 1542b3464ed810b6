"""Continuous-batching serving engine (`accelerate_tpu/serving/`).

The invariants that make iteration-level scheduling safe to put in front
of traffic:

- slot lifecycle (admit -> chunked prefill -> decode -> EOS/budget evict ->
  slot REUSE) produces greedy outputs BIT-IDENTICAL to running each request
  alone through `generate()`;
- the decode step compiles exactly once and bucketed prefill compiles at
  most once per bucket, whatever request mix arrives (the ATX302 drift
  checker sees the bucket set as the only shape drift);
- long prompts are chunked and interleaved with decode steps, so a new
  arrival never stalls in-flight decodes for its whole prompt;
- per-request sampling is stateless in (seed, step): a request's sampled
  tokens don't depend on which other requests share the batch.

The Poisson smoke test here is the `make smoke-serve` contract: 16
mixed-length requests, all complete, all match solo generate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import serving
from accelerate_tpu.generation import GenerationConfig, Generator
from accelerate_tpu.models import gpt, layers, llama
from accelerate_tpu.utils.environment import patch_environment

CFG = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=256, num_heads=4, num_kv_heads=2)


@pytest.fixture(scope="module")
def params():
    return llama.init(jax.random.PRNGKey(1), CFG)


def _apply(p, t, c):
    return llama.forward_with_cache(p, t, c, CFG)


def _init_cache(b, m):
    return llama.init_cache(CFG, b, m)


def _engine(params, config=None, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("buckets", (8, 16))
    kw.setdefault("max_len", 96)
    return serving.Engine(_apply, _init_cache, params, config or GenerationConfig(), **kw)


def _solo(params, prompt, max_new, config=None):
    config = config or GenerationConfig(max_new_tokens=max_new)
    gen = Generator(_apply, _init_cache, config)
    out = np.asarray(gen(params, jnp.asarray(np.asarray(prompt)[None])))
    return out[0, len(prompt):]


def _first_fresh(stream, start, width=1):
    """Smallest ``i >= start`` whose ``width``-token window of the greedy
    ``stream`` has not occurred at an earlier position. A random-weight
    model may repeat itself, so a fixed index can pick a token that already
    occurred — and an EOS / stop match would then fire early."""
    windows = [tuple(int(t) for t in stream[i : i + width]) for i in range(len(stream) - width + 1)]
    return next(i for i in range(start, len(windows)) if windows[i] not in windows[:i])


def _mixed_requests(n, *, seed=0, max_prompt=40, budgets=(4, 12)):
    rng = np.random.RandomState(seed)
    return [
        serving.Request(
            prompt=rng.randint(0, 61, (int(rng.randint(3, max_prompt + 1)),)).astype(np.int32),
            max_new_tokens=int(rng.choice(budgets)),
            rid=i,
            seed=i,
        )
        for i in range(n)
    ]


class TestBitIdentity:
    def test_single_request_matches_generate(self, params):
        eng = _engine(params)
        prompt = np.arange(13, dtype=np.int32) % 61
        rid = eng.submit(prompt, 9)
        (c,) = eng.run_until_idle()
        assert c.rid == rid and c.n_new == 9
        np.testing.assert_array_equal(c.tokens, _solo(params, prompt, 9))

    @pytest.mark.parametrize("decode_block", [1, 3])
    def test_slot_lifecycle_reuse_bit_identical(self, params, decode_block):
        """More requests than slots: admit -> decode -> evict -> REUSE every
        slot several times; each request's greedy stream must equal its solo
        `generate()` run exactly."""
        eng = _engine(params, decode_block=decode_block, slots=2)
        reqs = _mixed_requests(8)
        outs = {c.rid: c for c in eng.serve(reqs)}
        assert eng.stats["admitted"] == 8 > eng.n_slots  # slots were recycled
        assert eng.stats["completed"] == 8
        for r in reqs:
            np.testing.assert_array_equal(
                outs[r.rid].tokens, _solo(params, r.prompt, r.max_new_tokens)
            )

    def test_eos_eviction_matches_generate_and_frees_slot(self, params):
        """A request that hits EOS mid-budget is evicted early (n_new <
        max_new_tokens), its output matches solo generate's eos+pad layout,
        and its slot is reused by a queued request."""
        prompt = np.arange(5, dtype=np.int32) % 61
        free_run = _solo(params, prompt, 16)
        k = _first_fresh(free_run, 3)
        eos = int(free_run[k])
        config = GenerationConfig(max_new_tokens=16, eos_token_id=eos, pad_token_id=0)
        eng = _engine(params, config, slots=1)
        for i in range(3):  # one slot, three requests: forced reuse
            eng.submit(prompt, 16, seed=i)
        outs = eng.run_until_idle()
        assert len(outs) == 3 and eng.stats["admitted"] == 3
        want = _solo(params, prompt, 16, config)
        for c in outs:
            assert c.n_new == k + 1 < 16  # k tokens + the eos
            np.testing.assert_array_equal(c.tokens, want)

    def test_sampled_stream_independent_of_batchmates(self, params):
        """Sampling is fold_in(seed, step)-stateless: the same request gets
        the same tokens whether it runs alone or with companions."""
        config = GenerationConfig(max_new_tokens=8, do_sample=True, temperature=0.9)
        prompt = np.arange(11, dtype=np.int32) % 61
        solo_eng = _engine(params, config, slots=1)
        solo_eng.submit(prompt, 8, seed=123)
        (solo,) = solo_eng.run_until_idle()
        busy_eng = _engine(params, config, slots=3)
        rid = busy_eng.submit(prompt, 8, seed=123)
        for r in _mixed_requests(4, seed=5, budgets=(8,)):
            r.rid += 100  # keep clear of the auto-assigned rid above
            busy_eng.submit_request(r)
        busy = {c.rid: c for c in busy_eng.run_until_idle()}
        np.testing.assert_array_equal(solo.tokens, busy[rid].tokens)


class TestScheduler:
    def test_long_prompt_interleaves_with_decode(self, params):
        """While a multi-chunk prompt prefills, in-flight decodes keep
        stepping between its chunks (the no-stall property)."""
        eng = _engine(params, slots=2, prefill_interleave=1)
        eng.submit(np.arange(5, dtype=np.int32) % 61, 12)  # starts decoding
        while not any(s is not None and s.decoding for s in eng._slots):
            eng.step()
        eng.actions.clear()
        eng.submit(np.arange(48, dtype=np.int32) % 61, 4)  # 3 chunks of 16
        eng.run_until_idle()
        first_prefill = eng.actions.index("prefill")
        last_prefill = len(eng.actions) - 1 - eng.actions[::-1].index("prefill")
        between = eng.actions[first_prefill:last_prefill]
        assert "decode" in between, (
            f"no decode step between prefill chunks: {eng.actions}"
        )

    def test_prefill_interleave_zero_stalls_decodes(self, params):
        """prefill_interleave=0 is the fixed-batch behaviour: the whole
        prompt prefills back-to-back (documented as the anti-pattern)."""
        eng = _engine(params, slots=2, prefill_interleave=0)
        eng.submit(np.arange(5, dtype=np.int32) % 61, 12)
        while not any(s is not None and s.decoding for s in eng._slots):
            eng.step()
        eng.actions.clear()
        eng.submit(np.arange(48, dtype=np.int32) % 61, 4)
        eng.run_until_idle()
        first = eng.actions.index("prefill")
        assert eng.actions[first : first + 3] == ["prefill"] * 3

    def test_streaming_callback_and_detokenize(self, params):
        got = []
        eng = serving.Engine(
            _apply, _init_cache, params, GenerationConfig(),
            slots=1, buckets=(8,), max_len=64,
            detokenize=lambda ids: "".join(chr(65 + i % 26) for i in ids),
        )
        eng.submit(np.arange(6, dtype=np.int32) % 61, 5,
                   stream=lambda rid, tok, text: got.append((rid, tok, text)))
        (c,) = eng.run_until_idle()
        assert [t for _, t, _ in got] == c.tokens.tolist()
        assert all(isinstance(text, str) and len(text) == 1 for _, _, text in got)
        assert c.text == "".join(text for _, _, text in got)

    def test_submit_validation(self, params):
        eng = _engine(params, max_len=32)
        with pytest.raises(ValueError, match="capacity"):
            eng.submit(np.zeros((20,), np.int32), 20)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.zeros((4,), np.int32), 0)
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.zeros((0,), np.int32), 4)


class TestCompileDiscipline:
    def test_one_decode_compile_and_one_prefill_compile_per_bucket(self, params):
        """The serving promise: whatever mix of prompt lengths and budgets
        arrives, the decode step compiles ONCE and prefill compiles at most
        once per bucket."""
        eng = _engine(params, slots=3, buckets=(8, 16), decode_block=2)
        eng.serve(_mixed_requests(10, max_prompt=40))
        assert eng._decode._cache_size() == 1
        assert eng._prefill._cache_size() == len(set(eng.prefill_signatures)) == 2
        assert set(eng.prefill_signatures) == {8, 16}

    def test_atx302_sees_buckets_as_the_only_drift(self, params):
        """Reuse the ATX302 drift checker on the engine's REAL prefill fn:
        across buckets it must flag exactly the tokens argument (that drift
        is the bounded, by-design compile set); within one bucket there is
        no drift at all."""
        from accelerate_tpu import analysis

        eng = _engine(params)
        sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype)
        scalar = lambda dt: jax.ShapeDtypeStruct((), dt)

        def args_for(bucket):
            return (
                jax.tree.map(sds, params),
                jax.ShapeDtypeStruct((1, bucket), np.int32),
                jax.tree.map(sds, eng._kv),
                scalar(np.int32),
                scalar(np.int32),
                scalar(np.int32),
                scalar(np.uint32),
                scalar(np.bool_),
            )

        report = analysis.lint_step(
            eng._prefill_fn, *args_for(8),
            alternates=[args_for(16)], rules=["ATX302"],
        )
        (f,) = report.filter(family="ATX302")
        assert "args[1]" in f.path  # the bucketed tokens arg, nothing else
        clean = analysis.lint_step(
            eng._prefill_fn, *args_for(8),
            alternates=[args_for(8)], rules=["ATX302"],
        )
        assert not clean.findings

    def test_lint_decode_step_no_errors(self, params):
        """The smoke-serve lane gate: error-severity findings on the
        serving decode step fail the build (`atx lint serving`)."""
        from accelerate_tpu import analysis

        eng = _engine(params)
        report = analysis.lint_step(
            eng._decode_fn, *eng.abstract_decode_args(), donate_argnums=(3,)
        )
        assert not report.has_errors, [str(f) for f in report.findings]


    @pytest.mark.parametrize("mode, in_place", [("interpret", 1), ("off", 0)])
    def test_stats_say_which_attention_path_decode_compiled(self, params, mode, in_place):
        """A silent fall to the sliced lowering would bring the old speed
        back under the new code: the decode program records at trace time
        whether its attention reads the stacked cache in place (the
        flash-decode kernel) or slices a layer out, and tokens agree."""
        from accelerate_tpu.native.pallas.dispatch import force_kernels
        from accelerate_tpu import telemetry

        prompt = np.arange(1, 12, dtype=np.int32)
        eng = _engine(params, max_len=64)
        assert eng.stats["decode_in_place"] == 0  # nothing traced yet
        with force_kernels(mode):
            (done,) = eng.serve([serving.Request(prompt=prompt, max_new_tokens=5)])
        assert eng.stats["decode_in_place"] == in_place
        np.testing.assert_array_equal(done.tokens, _solo(params, prompt, 5))
        # ...and the registry exports it like the other counts.
        (metric,) = [
            m for m in telemetry.snapshot()["metrics"] if m["name"] == "serve_decode_in_place"
        ]
        (value,) = [
            series["value"] for series in metric["series"]
            if series["labels"] == eng.stats.labels
        ]
        assert value == in_place

    @pytest.mark.parametrize("mode", ["interpret", "off"])
    def test_stats_count_the_rows_decode_attention_fetches(self, params, mode, monkeypatch):
        """Beside the live rows of the decoding slots, the rows a step's
        attention copies out of a layer over every slot: whole blocks up to
        each cursor under the kernel (a free slot one block), every row of
        every slot under the sliced lowering."""
        from accelerate_tpu.native.pallas.dispatch import force_kernels

        monkeypatch.setenv("ATX_BLOCK_DECODE_ATTENTION", "16")  # four blocks a slot of 64
        eng = _engine(params, slots=3, max_len=64)
        with force_kernels(mode):
            eng.serve([serving.Request(prompt=np.arange(1, 31, dtype=np.int32), max_new_tokens=6)])
        live, fetched = eng.stats["kv_rows_live_full"], eng.stats["kv_rows_fetched_full"]
        assert eng.stats["decode_steps"] == 5  # the first token comes from the prefill
        whole = 5 * 3 * 64
        full = np.full(3, 64)
        assert live == sum(31 + i for i in range(5))  # cursor + 1 of the one decoding slot
        assert eng.stats["kv_rows_fetched_window"] == 0  # no ring leaves
        if mode == "off":
            assert fetched == whole
        else:
            # A step: a block of 16 for either free slot, cdiv(cursor + 1, 16) for the third.
            assert fetched == sum(2 * 16 + -(-(31 + i) // 16) * 16 for i in range(5)) == 2 * 64 + 3 * 80
            assert live <= fetched < whole
            assert eng._kv_rows_fetched(full, 64) == 3 * 64  # only when every slot is full
            assert eng._kv_rows_fetched(full - [0, 0, 16], 64) == 3 * 64 - 16

    @pytest.mark.parametrize(
        "weights, mode, buckets, in_place, sliced, quantized_once",
        [
            ("int8", "interpret", (8, 16), 4, 3, 0),
            ("int8", "off", (8, 16), 0, 7, 0),
            ("bf16", "interpret", (8, 16), 0, 0, 0),
            ("int8", "interpret", (1024,), 4, 3, 7),
        ],
    )
    def test_stats_count_how_programs_get_their_int8_weights(
        self, weights, mode, buckets, in_place, sliced, quantized_once
    ):
        """Every dispatch of a decode step or a prefill chunk adds how many of
        its quantized contractions read the layer stack in place and how many
        were handed a slice, as its trace recorded them: a silent fall to the
        slice path shows among the counters. A 1024-row chunk's seven take
        their rows as one row tile quantized once; a decode step's none."""
        from accelerate_tpu import telemetry
        from accelerate_tpu.native.pallas.dispatch import force_kernels
        from accelerate_tpu.ops.int8 import with_int8_compute
        from accelerate_tpu.utils.quantization import quantize_pytree

        max_len = max(64, *buckets)
        cfg = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=max_len, head_dim=32)
        params = llama.init(jax.random.PRNGKey(1), cfg)
        if weights == "int8":
            params = quantize_pytree(params, min_size=512)
        apply_fn = with_int8_compute(lambda p, t, c: llama.forward_with_cache(p, t, c, cfg))
        eng = serving.Engine(
            apply_fn, lambda b, m: llama.init_cache(cfg, b, m), params, GenerationConfig(),
            slots=2, buckets=buckets, max_len=max_len,
        )
        requests = [
            serving.Request(prompt=np.arange(1, 1 + n, dtype=np.int32), max_new_tokens=4)
            for n in (11, 20)
        ]
        with force_kernels(mode):
            done = eng.serve(requests)
        chunks = eng.stats["prefill_chunks"]
        dispatched = eng.stats["decode_steps"] + chunks
        assert chunks == (3 if buckets == (8, 16) else 2) and eng.stats["decode_steps"] >= 3
        assert eng.stats["weights_in_place"] == in_place * dispatched
        assert eng.stats["weights_sliced"] == sliced * dispatched
        assert eng.stats["int8_act_quantized_once"] == quantized_once * chunks  # 0 a decode step
        exported = [
            s["value"]
            for m in telemetry.snapshot()["metrics"] if m["name"] == "serve_int8_act_quantized_once"
            for s in m["series"] if s["labels"] == eng.stats.labels
        ]
        assert exported == [quantized_once * chunks]
        assert [len(d.tokens) for d in done] == [4, 4]


class TestPoissonSmoke:
    def test_poisson_16_requests_all_complete_and_match_solo(self, params):
        """The `make smoke-serve` contract: a 16-request Poisson trace of
        mixed prompt/output lengths fully completes and every request is
        bit-identical to its solo `generate()` run."""
        eng = _engine(params, slots=4, decode_block=2)
        trace = serving.poisson_trace(
            16, rate=200.0, vocab_size=61, prompt_lens=(3, 40),
            new_tokens=(4, 12), seed=0,
        )
        outs = {c.rid: c for c in eng.serve(trace)}
        assert len(outs) == 16 and eng.stats["completed"] == 16
        for r in trace:
            np.testing.assert_array_equal(
                outs[r.rid].tokens, _solo(params, r.prompt, r.max_new_tokens)
            )


class TestKnobsAndFamilies:
    def test_env_knobs(self, params):
        with patch_environment(ATX_SERVE_SLOTS="5", ATX_SERVE_BUCKETS="8,32"):
            eng = serving.Engine(
                _apply, _init_cache, params, GenerationConfig(), max_len=64
            )
            assert eng.n_slots == 5
            assert eng.buckets == (8, 32)
        with patch_environment(ATX_SERVE_BUCKETS="nope"):
            with pytest.raises(ValueError, match="ATX_SERVE_BUCKETS"):
                serving.default_buckets()

    def test_prefix_cache_env_knobs(self, params):
        with patch_environment(ATX_SERVE_PREFIX_CACHE="0"):
            eng = _engine(params)
            assert eng.prefix_cache is None
            assert eng.prefix_metrics() == {"prefix_cache": 0}
        with patch_environment(ATX_SERVE_PREFIX_CACHE_MIB="1"):
            eng = _engine(params)
            assert eng.prefix_cache is not None
        # A budget too small for one row disables the cache outright.
        eng = _engine(params, prefix_cache_mib=1e-6)
        assert eng.prefix_cache is None

    def test_gpt_family_contract(self):
        """The engine is family-agnostic: any cache whose non-length leaves
        are (L, B, T, ...) layer-stacked buffers works — here a GPT-2-style
        learned-positional model."""
        cfg = gpt.GPTConfig.tiny(vocab_size=61, max_seq_len=128)
        gparams = gpt.init(jax.random.PRNGKey(2), cfg)
        apply_fn = lambda p, t, c: gpt.forward_with_cache(p, t, c, cfg)
        init_fn = lambda b, m: gpt.init_cache(cfg, b, m)
        eng = serving.Engine(
            apply_fn, init_fn, gparams, GenerationConfig(),
            slots=2, buckets=(8,), max_len=48,
        )
        prompt = np.arange(7, dtype=np.int32) % 61
        eng.submit(prompt, 6)
        (c,) = eng.run_until_idle()
        want = np.asarray(
            Generator(apply_fn, init_fn, GenerationConfig(max_new_tokens=6))(
                gparams, jnp.asarray(prompt[None])
            )
        )[0, 7:]
        np.testing.assert_array_equal(c.tokens, want)


def _prefixed_requests(prefix, tails, budgets, *, rid0=0, seed0=0):
    return [
        serving.Request(
            prompt=np.concatenate([prefix, t]).astype(np.int32),
            max_new_tokens=int(b),
            rid=rid0 + i,
            seed=seed0 + i,
        )
        for i, (t, b) in enumerate(zip(tails, budgets))
    ]


class TestPrefixCache:
    """Automatic prefix caching (`serving/prefix_cache.py` + the engine's
    match/copy/promote hooks). The load-bearing claim everywhere: greedy
    outputs with the cache ON are bit-identical to the cache-off engine
    and to solo `generate()` — a hit changes where KV comes from, never
    what it contains."""

    def test_hit_is_bit_identical_llama_gqa(self, params):
        """Second request shares a 24-token prefix with the first: the
        engine copies the cached KV and prefills only the tail, and the
        output still matches solo generate token for token."""
        rng = np.random.RandomState(3)
        prefix = rng.randint(0, 61, (24,)).astype(np.int32)
        tails = [rng.randint(0, 61, (5,)).astype(np.int32) for _ in range(2)]
        eng = _engine(params, prefix_cache_rows=4)
        outs = {}
        for r in _prefixed_requests(prefix, tails, (8, 8)):
            eng.submit_request(r)
            # Serialize so the first completion PROMOTES before the second
            # request's admission runs its match.
            outs.update({c.rid: c for c in eng.run_until_idle()})
        pc = eng.prefix_cache
        assert pc.stats["hits"] >= 1 and pc.stats["tokens_matched"] >= 24
        assert eng.stats["prefill_tokens_saved"] >= 24
        for r in _prefixed_requests(prefix, tails, (8, 8)):
            np.testing.assert_array_equal(
                outs[r.rid].tokens, _solo(params, r.prompt, 8)
            )

    def test_admit_hit_evict_readmit_cycle_bit_identical(self, params):
        """One pool row: promote A, hit on A', evict A for B, re-admit a
        fresh A'' that must MISS (its row is gone) and re-prefill — every
        stage bit-identical to solo."""
        rng = np.random.RandomState(4)
        pa = rng.randint(0, 61, (24,)).astype(np.int32)
        pb = rng.randint(0, 61, (24,)).astype(np.int32)
        eng = _engine(params, slots=1, prefix_cache_rows=1)
        reqs, outs = [], {}
        for i, prefix in enumerate((pa, pa, pb, pa)):
            tail = rng.randint(0, 61, (4,)).astype(np.int32)
            (r,) = _prefixed_requests(prefix, [tail], [6], rid0=i, seed0=i)
            reqs.append(r)
            eng.submit_request(r)
            outs.update({c.rid: c for c in eng.run_until_idle()})
        pc = eng.prefix_cache
        assert pc.stats["hits"] >= 1  # request 1 hit on request 0's row
        assert pc.stats["evictions"] >= 1  # pb's promotion stole the row
        assert eng.stats["completed"] == 4
        # Request 3 (pa again) missed: its row was evicted in between.
        assert pc.stats["hits"] < pc.stats["lookups"]
        for r in reqs:
            np.testing.assert_array_equal(
                outs[r.rid].tokens, _solo(params, r.prompt, 6)
            )

    def test_cache_on_equals_cache_off_same_trace(self, params):
        """The whole-trace contract: identical Completion token streams
        from a cache-on and a cache-off engine over a shared-prefix trace."""
        trace = serving.shared_prefix_trace(
            10, 200.0, vocab_size=61, n_prefixes=2, prefix_len=32,
            tail_lens=(3, 8), new_tokens=(4, 10), seed=7,
        )
        on = _engine(params, slots=3, prefix_cache_rows=4)
        off = _engine(params, slots=3, prefix_cache=False)
        got_on = {c.rid: c.tokens for c in on.serve(trace)}
        got_off = {c.rid: c.tokens for c in off.serve(trace)}
        assert on.prefix_cache.stats["hits"] > 0
        assert off.prefix_cache is None
        for rid in got_off:
            np.testing.assert_array_equal(got_on[rid], got_off[rid])

    def test_multi_turn_promotion_hits_past_prompt(self, params):
        """Promotion caches prompt + committed GENERATED tokens, so a
        follow-up whose prompt extends the previous full stream (the
        multi-turn shape) matches deeper than the original prompt."""
        prompt = (np.arange(16, dtype=np.int32) * 7) % 61
        eng = _engine(params, slots=1, prefix_cache_rows=2)
        eng.submit(prompt, 12, seed=0)
        (first,) = eng.run_until_idle()
        turn2 = np.concatenate(
            [prompt, first.tokens, (np.arange(9) * 5 % 61)]
        ).astype(np.int32)
        eng.submit(turn2, 6, seed=1)
        (second,) = eng.run_until_idle()
        pc = eng.prefix_cache
        assert pc.stats["tokens_matched"] > len(prompt)
        np.testing.assert_array_equal(second.tokens, _solo(params, turn2, 6))

    def test_match_pin_blocks_eviction_until_copy(self, params):
        """Between admission (match pins the node) and the copy dispatch,
        a promotion cannot steal the matched row: insert is denied rather
        than evicting the pinned entry."""
        rng = np.random.RandomState(5)
        prefix = rng.randint(0, 61, (24,)).astype(np.int32)
        eng = _engine(params, slots=2, prefix_cache_rows=1)
        eng.submit(np.concatenate([prefix, [3, 4]]).astype(np.int32), 6, seed=0)
        eng.run_until_idle()
        pc = eng.prefix_cache
        assert pc.used_rows == 1
        eng.submit(np.concatenate([prefix, [9, 8]]).astype(np.int32), 6, seed=1)
        eng._admit()  # match() pins; the copy has NOT been dispatched yet
        slot = next(s for s in eng._slots if s is not None and s.pending_copy)
        node, matched = slot.pending_copy
        assert matched >= 24 and node.refs == 1
        assert pc.insert(rng.randint(0, 61, (16,)).astype(np.int32)) is None
        assert pc.stats["insert_denied"] == 1  # pinned row survived
        (c,) = eng.run_until_idle()
        assert node.refs == 0  # released at copy dispatch
        np.testing.assert_array_equal(
            c.tokens, _solo(params, np.concatenate([prefix, [9, 8]]), 6)
        )

    def test_gpt_family_hit_bit_identical(self):
        """Family-agnostic: the copy kernel tree-maps over whatever cache
        leaves the family allocates (GPT's learned-positional cache here)."""
        cfg = gpt.GPTConfig.tiny(vocab_size=61, max_seq_len=128)
        gparams = gpt.init(jax.random.PRNGKey(2), cfg)
        apply_fn = lambda p, t, c: gpt.forward_with_cache(p, t, c, cfg)
        init_fn = lambda b, m: gpt.init_cache(cfg, b, m)
        eng = serving.Engine(
            apply_fn, init_fn, gparams, GenerationConfig(),
            slots=2, buckets=(8,), max_len=48, prefix_cache_rows=2,
        )
        prefix = (np.arange(16, dtype=np.int32) * 3) % 61
        outs = []
        for tail in ([1, 2], [5, 6]):
            eng.submit(np.concatenate([prefix, tail]).astype(np.int32), 5)
            outs.extend(eng.run_until_idle())
        assert eng.prefix_cache.stats["hits"] == 1
        for c, tail in zip(outs, ([1, 2], [5, 6])):
            want = np.asarray(
                Generator(apply_fn, init_fn, GenerationConfig(max_new_tokens=5))(
                    gparams,
                    jnp.asarray(np.concatenate([prefix, tail]).astype(np.int32)[None]),
                )
            )[0, len(prefix) + 2 :]
            np.testing.assert_array_equal(c.tokens, want)

    def test_copy_compile_discipline(self, params):
        """Hits and promotions reuse <= 2 compiles per bucket (hit and
        promote directions differ in shape when pool rows != slots); decode
        and prefill counts are untouched by cache traffic."""
        trace = serving.shared_prefix_trace(
            12, 200.0, vocab_size=61, n_prefixes=2, prefix_len=32,
            tail_lens=(3, 8), new_tokens=(4, 8), seed=9,
        )
        eng = _engine(params, slots=3, prefix_cache_rows=4, decode_block=2)
        eng.serve(trace)
        assert eng.prefix_cache.stats["hits"] > 0
        assert eng._decode._cache_size() == 1
        assert eng._prefill._cache_size() <= len(eng.buckets)
        assert eng._copy._cache_size() <= 2 * len(eng.buckets)
        assert set(eng.copy_signatures) <= set(eng.buckets)

    def test_atx302_copy_fn_no_drift(self, params):
        """The lint-lane contract for the copy kernel: repeated calls at
        one bucket present identical signatures (no per-request drift)."""
        from accelerate_tpu import analysis

        eng = _engine(params, prefix_cache_rows=4)
        report = analysis.lint_step(
            eng.copy_fn_for_bucket(8),
            *eng.abstract_copy_args(),
            alternates=[eng.abstract_copy_args()],
            donate_argnums=(0,),
        )
        assert not report.filter(family="ATX302"), [str(f) for f in report.findings]
        assert not report.has_errors, [str(f) for f in report.findings]

    def test_shared_prefix_poisson_smoke(self, params):
        """The `make smoke-serve` prefix contract: a shared-system-prompt
        Poisson trace completes with hit_rate > 0, >= 50% of prompt tokens
        served from cache, and bit-identity against the cache-off engine."""
        trace = serving.shared_prefix_trace(
            12, 150.0, vocab_size=61, n_prefixes=1, prefix_len=32,
            tail_lens=(3, 8), new_tokens=(4, 8), seed=13,
        )
        eng = _engine(params, slots=3, prefix_cache_rows=4)
        outs = {c.rid: c for c in eng.serve(trace)}
        assert len(outs) == 12 and eng.stats["completed"] == 12
        m = eng.prefix_metrics()
        assert m["prefix_hit_rate"] > 0
        assert m["prefill_saved_frac"] >= 0.5, m
        off = _engine(params, slots=3, prefix_cache=False)
        for c in off.serve(trace):
            np.testing.assert_array_equal(outs[c.rid].tokens, c.tokens)


class TestStopAndBudget:
    def test_stop_sequence_truncates_and_matches_solo_prefix(self, params):
        """Pick a 2-token window from the solo greedy stream as the stop
        sequence: the served stream must equal the solo stream up to and
        including the stop match, with finish_reason 'stop'."""
        prompt = (np.arange(9, dtype=np.int32) * 11) % 61
        free = _solo(params, prompt, 12)
        k = _first_fresh(free, 4, width=2)
        stop = tuple(int(t) for t in free[k : k + 2])
        eng = _engine(params)
        eng.submit(prompt, 12, stop_sequences=[stop])
        (c,) = eng.run_until_idle()
        assert c.finish_reason == "stop"
        assert c.n_new == k + 2 < 12
        # tokens keeps the (max_new_tokens,) padded layout; the generated
        # region up to the stop match equals the solo stream.
        np.testing.assert_array_equal(c.tokens[: k + 2], free[: k + 2])
        assert not c.tokens[k + 2 :].any()  # pad after the stop

    def test_stop_sequence_not_hit_runs_to_budget(self, params):
        prompt = (np.arange(9, dtype=np.int32) * 11) % 61
        eng = _engine(params)
        eng.submit(prompt, 7, stop_sequences=[(60, 60, 60, 60)])
        (c,) = eng.run_until_idle()
        assert c.finish_reason == "length" and c.n_new == 7

    def test_eos_reports_eos_reason(self, params):
        prompt = np.arange(9, dtype=np.int32) % 61
        free = _solo(params, prompt, 8)
        k = _first_fresh(free, 2)
        eos = int(free[k])
        config = GenerationConfig(max_new_tokens=8, eos_token_id=eos, pad_token_id=0)
        eng = _engine(params, config)
        eng.submit(prompt, 8)
        (c,) = eng.run_until_idle()
        assert c.finish_reason == "eos" and c.n_new == k + 1 < 8

    def test_per_request_budget_override(self, params):
        """submit() without max_new_tokens falls back to the engine
        config's budget; an explicit value overrides it per request."""
        config = GenerationConfig(max_new_tokens=5)
        eng = _engine(params, config)
        prompt = np.arange(6, dtype=np.int32) % 61
        rid_default = eng.submit(prompt)
        rid_long = eng.submit(prompt, 9, seed=0)
        outs = {c.rid: c for c in eng.run_until_idle()}
        assert outs[rid_default].n_new == 5
        assert outs[rid_long].n_new == 9
        np.testing.assert_array_equal(
            outs[rid_long].tokens[:5], outs[rid_default].tokens
        )

    def test_empty_stop_sequence_rejected(self, params):
        eng = _engine(params)
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.arange(4, dtype=np.int32), 4, stop_sequences=[()])


class TestCancelAndValidation:
    """`Engine.cancel` (the Router's deadline/cancel primitive) and
    submit-time validation of the bucket-padded plan (ISSUE-8)."""

    def test_cancel_queued_request(self, params):
        eng = _engine(params, slots=1)
        blocker = np.arange(7, dtype=np.int32)
        eng.submit(blocker, 6, seed=0)
        victim = eng.submit(np.arange(5, dtype=np.int32), 6, seed=1)
        c = eng.cancel(victim)
        assert c is not None and c.finish_reason == "cancelled" and c.n_new == 0
        assert eng.stats["cancelled"] == 1
        (done,) = eng.run_until_idle()
        np.testing.assert_array_equal(done.tokens, _solo(params, blocker, 6))

    def test_cancel_mid_decode_keeps_partial_tokens_and_frees_slot(self, params):
        eng = _engine(params, slots=1)
        prompt = (np.arange(9, dtype=np.int32) * 5) % 61
        rid = eng.submit(prompt, 12, seed=0)
        for _ in range(5):  # prefill + a few decode steps
            eng.step()
        c = eng.cancel(rid)
        assert c is not None and c.finish_reason == "cancelled"
        assert 0 < c.n_new < 12
        # The partial stream is a prefix of the solo run (determinism holds
        # right up to the cancel)...
        np.testing.assert_array_equal(
            c.tokens[: c.n_new], _solo(params, prompt, 12)[: c.n_new]
        )
        # ...and the freed slot serves the next request bit-identically.
        other = np.arange(6, dtype=np.int32)
        eng.submit(other, 5, seed=3)
        (done,) = eng.run_until_idle()
        np.testing.assert_array_equal(done.tokens, _solo(params, other, 5))

    def test_cancel_unknown_or_finished_rid_returns_none(self, params):
        eng = _engine(params)
        rid = eng.submit(np.arange(4, dtype=np.int32), 3)
        eng.run_until_idle()
        assert eng.cancel(rid) is None
        assert eng.cancel(12345) is None
        assert eng.stats["cancelled"] == 0

    def test_padded_plan_overflow_rejected_at_submit(self, params):
        """A prompt whose BUCKET-PADDED prefill plan exceeds max_len is
        rejected at submit even when raw prompt + budget would fit: every
        chunk writes a full bucket of KV positions, pad included."""
        eng = _engine(params, buckets=(16,), max_len=42)
        with pytest.raises(ValueError, match="bucket-padded"):
            eng.submit(np.arange(36, dtype=np.int32) % 61, 6)
        # Raw fit check still reads as before.
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(np.arange(40, dtype=np.int32) % 61, 6)
        # Control: an exact-bucket prompt with the same budget is fine.
        rid = eng.submit(np.arange(32, dtype=np.int32) % 61, 6)
        (c,) = eng.run_until_idle()
        assert c.rid == rid and c.n_new == 6


# --------------------------------------------------------- state leaves
class TestStateLeaves:
    """A family whose cache holds recurrent states beside KV rows
    (`models/olmo_hybrid.py`): a state has no cursor to hide behind, so the
    engine tells the forward which rows are real and which slots decode, and
    zeroes a slot's states inside its first chunk's program."""

    from accelerate_tpu.models import olmo_hybrid as family

    CFG = family.OlmoHybridConfig.tiny(n_layers=4, vocab_size=97)

    @pytest.fixture(scope="class")
    def hybrid(self):
        params = self.family.init(jax.random.PRNGKey(2), self.CFG)
        # decays and write strengths that differ token by token
        params["linear"]["w_ab"] = params["linear"]["w_ab"] * 3.0
        return params

    def _engine(self, params, **kw):
        kw.setdefault("slots", 3)
        kw.setdefault("buckets", (16, 32))
        kw.setdefault("max_len", 160)
        cfg = self.CFG
        return serving.Engine(
            lambda p, t, c: self.family.forward_with_cache(p, t, c, cfg),
            lambda b, m: self.family.init_cache(cfg, b, m, jnp.float32),
            params, GenerationConfig(max_new_tokens=8), **kw,
        )

    @staticmethod
    def _prompts():
        rng = np.random.default_rng(0)
        return [rng.integers(0, 97, n) for n in (5, 20, 70, 33, 90)]

    def _serve(self, engine, prompts=None, max_new=8):
        for p in prompts or self._prompts():
            engine.submit(p, max_new_tokens=max_new)
        done = {c.rid: c for c in engine.run_until_idle()}
        return [done[i].tokens for i in sorted(done)]

    @pytest.mark.parametrize(
        "kw", [{"buckets": (64,)}, {"buckets": (16, 64), "decode_block": 4}, {"slots": 1}, {"prefill_interleave": 0}],
        ids=["one-bucket", "decode-block-4", "one-slot", "prefill-first"],
    )
    def test_same_tokens_whatever_the_bucket_split_block_or_company(self, hybrid, kw):
        """The bucket split of a prompt (and so where the state is handed
        over and how long the pad tail is), the decode block and which
        other requests share the batch change no token."""
        want = self._serve(self._engine(hybrid))
        got = self._serve(self._engine(hybrid, **kw))
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)

    def test_matches_solo_generate(self, hybrid):
        got = self._serve(self._engine(hybrid))
        for prompt, tokens in zip(self._prompts(), got):
            out = self.family.generate(
                hybrid, jnp.asarray(prompt)[None], self.CFG, generation_config=GenerationConfig(max_new_tokens=8)
            )
            np.testing.assert_array_equal(np.asarray(out)[0, len(prompt):], tokens)

    def test_a_neighbours_decode_steps_do_not_touch_a_slot_in_mid_prefill(self, hybrid):
        engine = self._engine(hybrid, slots=2, buckets=(16,))
        rng = np.random.default_rng(1)
        engine.submit(rng.integers(0, 97, 10), max_new_tokens=40)  # decodes from the second step on
        engine.step()
        engine.submit(rng.integers(0, 97, 80), max_new_tokens=4)  # five chunks, a decode step between each
        while engine._slots[1] is None or engine._slots[1].cursor == 0:
            engine.step()  # until its first chunk has run
        assert engine.actions[-1] == "prefill" and not engine._slots[1].decoding
        state = lambda: {n: np.asarray(engine._kv[n][:, 1]) for n in engine._state_names}
        before = state()
        assert any(np.abs(v).max() > 0 for v in before.values())
        engine.step()
        assert engine.actions[-1] == "decode"  # the neighbour's step; slot 1 rode along
        for name, value in state().items():
            np.testing.assert_array_equal(value, before[name])
        engine.step()
        assert engine.actions[-1] == "prefill"
        assert any(np.abs(state()[n] - before[n]).max() > 0 for n in before)

    def test_a_reused_slot_starts_from_zero(self, hybrid):
        """One slot, two requests one after the other: the second finds the
        first's state in its slot and must not see it."""
        prompts = self._prompts()[:2]
        engine = self._engine(hybrid, slots=1)
        both = self._serve(engine, prompts)
        alone = self._serve(self._engine(hybrid, slots=1), prompts[1:])
        np.testing.assert_array_equal(both[1], alone[0])
        assert engine.stats["state_resets"] == 2

    def test_a_finished_request_leaves_its_states_in_its_slot(self, hybrid):
        """`Completion.slot` names the slot and `Engine.slot_state` reads it:
        the states after the prompt and all but the last token, whatever the
        neighbours went on to do; a cache of rows only has none."""
        engine = self._engine(hybrid)
        for p in self._prompts():
            engine.submit(p, max_new_tokens=8)
        done = sorted(engine.run_until_idle(), key=lambda c: c.rid)
        assert all(0 <= c.slot < 3 for c in done) and len({c.slot for c in done[:3]}) == 3
        last = done[-1]  # nobody took its slot after it
        left = engine.slot_state(last.slot)
        assert set(left) == {"state_conv", "state_gdn"}
        seen = np.concatenate([last.prompt, last.tokens[:-1]])[None]
        cache = self.family.init_cache(self.CFG, 1, 160, jnp.float32)
        _, cache = self.family.forward_with_cache(hybrid, jnp.asarray(seen), cache, self.CFG)
        for name, value in left.items():
            np.testing.assert_allclose(value, np.asarray(cache[name])[:, 0], atol=1e-4)

    def test_counters_and_the_prefix_cache(self, hybrid, monkeypatch):
        engine = self._engine(hybrid)
        assert engine._state_names == ("state_conv", "state_gdn") and engine._ring_len == 0
        assert engine.prefix_cache is None and engine.stats["prefix_cache_off_for_state"] == 1
        self._serve(engine)
        s = engine.stats
        assert s["state_slots_live"] == 3 * s["decode_slot_steps"]  # three linear layers
        assert s["state_slots_touched"] == 3 * 3 * s["decode_steps"]  # the XLA lowering selects over every slot
        assert s["state_rows_real"] == s["prompt_tokens"] == 218
        assert s["state_rows_padded"] == sum(engine.prefill_signatures) and s["state_resets"] == 5
        assert len(engine.abstract_decode_args()) == 7
        with pytest.raises(ValueError, match="state leaves"):
            self._engine(hybrid, prefix_cache=True)
        from accelerate_tpu.native.pallas.dispatch import force_kernels

        with force_kernels("interpret"):
            kernel = self._engine(hybrid)
            got = self._serve(kernel)
        assert kernel.stats["state_slots_touched"] == kernel.stats["state_slots_live"] == s["state_slots_live"]
        for a, b in zip(self._serve(self._engine(hybrid)), got):
            np.testing.assert_array_equal(a, b)

    def test_a_cache_without_state_leaves_traces_what_it_did(self, params):
        """No new operand and no state key for the families that keep rows
        only; a prefill chunk names the one row it samples (``logits_at``)."""
        engine = _engine(params)
        assert engine._state_names == () and len(engine.abstract_decode_args()) == 6
        assert engine.slot_state(0) == {}
        seen = {}

        def spy(p, t, c):
            seen[t.shape[1]] = set(c)
            return _apply(p, t, c)

        spied = serving.Engine(spy, _init_cache, params, GenerationConfig(max_new_tokens=3), slots=2, buckets=(8,), max_len=32)
        spied.submit(np.arange(5), max_new_tokens=3)
        spied.run_until_idle()
        assert seen == {8: {"k", "v", "length", "logits_at"}, 1: {"k", "v", "length"}}
        assert spied.stats["state_slots_live"] == spied.stats["state_resets"] == spied.stats["prefix_cache_off_for_state"] == 0


def _family_engines():
    """name -> (engine factory, vocabulary, (in place, sliced) a chunk under
    the kernels): the tiny llama, SmallThinker and Olmo-Hybrid configs."""
    from accelerate_tpu.models import olmo_hybrid, smallthinker

    def llama_engine():
        cfg = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=256, n_layers=3)
        return (
            lambda p, t, c: llama.forward_with_cache(p, t, c, cfg),
            lambda b, m: llama.init_cache(cfg, b, m, dtype=jnp.float32),
            llama.init(jax.random.PRNGKey(1), cfg),
        )

    def smallthinker_engine():
        cfg = smallthinker.SmallThinkerConfig.tiny()  # two full layers, six rings of 16 rows
        return (
            lambda p, t, c: smallthinker.forward_with_cache(p, t, c, cfg),
            lambda b, m: smallthinker.init_cache(cfg, b, m, dtype=jnp.float32),
            smallthinker.init(jax.random.PRNGKey(1), cfg),
        )

    def gpt_engine():
        cfg = gpt.GPTConfig.tiny(vocab_size=67, max_seq_len=256)
        return (
            lambda p, t, c: gpt.forward_with_cache(p, t, c, cfg),
            lambda b, m: gpt.init_cache(cfg, b, m, dtype=jnp.float32),
            gpt.init(jax.random.PRNGKey(1), cfg),
        )

    def hybrid_engine():
        cfg = olmo_hybrid.OlmoHybridConfig.tiny(n_layers=8, vocab_size=97)  # two full layers of eight
        return (
            lambda p, t, c: olmo_hybrid.forward_with_cache(p, t, c, cfg),
            lambda b, m: olmo_hybrid.init_cache(cfg, b, m, jnp.float32),
            olmo_hybrid.init(jax.random.PRNGKey(2), cfg),
        )

    return {
        "llama": (llama_engine, 61, (3, 0)),
        "gpt": (gpt_engine, 67, (0, 2)),  # its chunks hand `cached_attention` no cursor
        "smallthinker": (smallthinker_engine, 256, (2, 6)),
        "olmo_hybrid": (hybrid_engine, 97, (2, 0)),
    }


@pytest.mark.parametrize("family", ["llama", "gpt", "smallthinker", "olmo_hybrid"])
def test_prefill_chunks_attend_through_the_kernel_and_the_stats_say_so(family):
    """Prompts of several chunks through an engine whose chunks attend
    through `flash_prefill` (interpret mode) and one with the kernels off:
    the same greedy tokens, and ``prefill_attn_in_place`` /
    ``prefill_attn_sliced`` grow at every chunk by the layers whose attention
    read the stack in place and those that sliced theirs out (a ring
    layer's chunk is sliced by hand and counts as sliced)."""
    from accelerate_tpu import telemetry
    from accelerate_tpu.native.pallas.dispatch import force_kernels

    make, vocab, (in_place, sliced) = _family_engines()[family]
    apply_fn, init_cache, weights = make()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in (70, 21, 45)]
    served = {}
    for mode in ("interpret", "off"):
        # Slots of 96 rows are three blocks of 32; chunks of 16 and 32 rows.
        engine = serving.Engine(
            apply_fn, init_cache, weights, GenerationConfig(), slots=2, buckets=(16, 32), max_len=96
        )
        with force_kernels(mode):
            for prompt in prompts:
                engine.submit(prompt, max_new_tokens=6)
            done = {c.rid: c.tokens for c in engine.run_until_idle()}
        served[mode] = [done[i] for i in sorted(done)]
        chunks = engine.stats["prefill_chunks"]
        assert chunks == 3 + 1 + 2  # 32 + 32 + 16; 32; 32 + 16
        want = (in_place, sliced) if mode == "interpret" else (0, in_place + sliced)
        assert (engine.stats["prefill_attn_in_place"], engine.stats["prefill_attn_sliced"]) == tuple(
            n * chunks for n in want
        )
        exported = {
            m["name"]: [s["value"] for s in m["series"] if s["labels"] == engine.stats.labels]
            for m in telemetry.snapshot()["metrics"]
            if m["name"].startswith("serve_prefill_attn_")
        }
        assert exported == {
            "serve_prefill_attn_in_place": [want[0] * chunks],
            "serve_prefill_attn_sliced": [want[1] * chunks],
        }
    for a, b in zip(served["interpret"], served["off"]):
        np.testing.assert_array_equal(a, b)


def _vocab_dots(jaxpr, vocab, in_branch=False):
    """For every contraction in ``jaxpr`` whose result is ``vocab`` wide:
    whether it sits inside a branch of a `cond`."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and eqn.outvars[0].aval.shape[-1] == vocab:
            found.append(in_branch)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _vocab_dots(sub, vocab, in_branch or eqn.primitive.name == "cond")
    return found


@pytest.mark.parametrize("family", ["llama", "gpt", "smallthinker", "olmo_hybrid"])
def test_a_cached_forward_projects_only_the_row_the_cache_names(family):
    """``cache['logits_at']`` (`layers.head_logits`): a batch row's index
    gives that row's logits alone, equal to that row of the every-row
    forward, and -1 gives zeros. Where no row wants one, the vocabulary-wide
    contraction sits only in the branch that is not taken; the every-row
    forward holds it outside any branch, so the check has teeth."""
    make, vocab, _ = _family_engines()[family]
    apply_fn, init_cache, weights = make()
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, vocab, (2, 16)), jnp.int32)
    cache = init_cache(2, 32)
    forward = jax.jit(apply_fn)
    full = np.asarray(forward(weights, tokens, cache)[0])
    with layers.record_head_rows() as rows:  # one trace for the three calls below
        for at in ([5, 15], [-1, 3]):
            one, new = forward(weights, tokens, dict(cache, logits_at=jnp.asarray(at, jnp.int32)))
            assert one.shape == (2, 1, vocab) and "logits_at" not in new
            want = np.where(np.array(at)[:, None] < 0, 0.0, full[np.arange(2), np.maximum(at, 0)])
            np.testing.assert_allclose(np.asarray(one)[:, 0], want, rtol=1e-5, atol=1e-5)
        none = dict(cache, logits_at=jnp.full((2,), -1, jnp.int32))
        np.testing.assert_array_equal(forward(weights, tokens, none)[0], np.zeros((2, 1, vocab)))
    assert rows == [2]
    assert _vocab_dots(jax.make_jaxpr(apply_fn)(weights, tokens, none).jaxpr, vocab) == [True]
    assert _vocab_dots(jax.make_jaxpr(apply_fn)(weights, tokens, cache).jaxpr, vocab) == [False]


@pytest.mark.parametrize("family", ["llama", "gpt", "smallthinker", "olmo_hybrid"])
def test_a_prefill_chunk_projects_the_head_only_where_it_samples(family):
    """Prompts of several chunks: an engine whose forward honours
    ``cache['logits_at']`` emits the greedy tokens of one whose ``apply_fn``
    withholds the key and so returns every row, from which the engine takes
    the row it samples. A chunk asks for its last real row on a prompt's
    last chunk and for none (-1) on the others, as the program runs it.
    ``prefill_head_rows`` (exported ``serve_prefill_head_rows``) counts one
    row a finished prefill with the key, and every row of every chunk
    without it."""
    from accelerate_tpu import telemetry

    make, vocab, _ = _family_engines()[family]
    apply_fn, init_cache, weights = make()
    asked = []

    def honoured(p, t, c):
        if "logits_at" in c:
            jax.debug.callback(lambda at: asked.append(int(at[0])), c["logits_at"])
        return apply_fn(p, t, c)

    withheld = lambda p, t, c: apply_fn(p, t, {k: v for k, v in c.items() if k != "logits_at"})
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in (70, 21, 45)]
    served, head_rows = {}, {}
    for name, fn in (("honoured", honoured), ("withheld", withheld)):
        engine = serving.Engine(
            fn, init_cache, weights, GenerationConfig(), slots=2, buckets=(16, 32), max_len=96
        )
        for prompt in prompts:
            engine.submit(prompt, max_new_tokens=6)
        done = {c.rid: c.tokens for c in engine.run_until_idle()}
        served[name] = [done[i] for i in sorted(done)]
        head_rows[name] = engine.stats["prefill_head_rows"]
        exported = [
            s["value"]
            for m in telemetry.snapshot()["metrics"] if m["name"] == "serve_prefill_head_rows"
            for s in m["series"] if s["labels"] == engine.stats.labels
        ]
        assert exported == [head_rows[name]]
        assert engine._prefill._cache_size() == 2  # one program a bucket either way
    assert head_rows == {"honoured": len(prompts), "withheld": 32 + 32 + 16 + 32 + 32 + 16}
    jax.effects_barrier()
    assert sorted(asked) == [-1, -1, -1, 5, 12, 20]  # 70 = 32 + 32 + 6, 21, 45 = 32 + 13
    for a, b in zip(served["honoured"], served["withheld"]):
        np.testing.assert_array_equal(a, b)
