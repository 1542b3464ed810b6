"""Speculative decoding: a small draft model proposes K tokens, the target
model verifies all of them in ONE forward pass.

The reference has no speculative path (its `generate()` is transformers',
reference `big_modeling.py:511`); this is a beyond-parity decode
accelerator that falls straight out of the TPU cost model: single-token
decode is HBM-bandwidth-bound (every step streams all weights for one
token), so a verify pass over K+1 positions costs nearly the same wall
time as one decode step. Each accepted draft token is therefore a decode
step the target never pays for — throughput multiplies by the mean number
of committed tokens per iteration (≈ K·acceptance + 1).

Shape discipline (XLA): K is static; one jitted `spec_step` per iteration
runs the draft loop as a `lax.scan` over K single-token steps plus one
(B, K+1) target verify, with both KV caches donated. Only the per-iteration
commit count syncs to the host — the same host-loop design as
`generation.Generator`, amortized K+1 tokens at a time.

Cache bookkeeping rides the models' shared cache contract
(`{"k","v","length"}`, e.g. `models/llama.py:forward_with_cache`): entries
past ``length`` are never attended (the mask is position-based), so
rejecting draft tokens is just writing a smaller ``length`` back — no data
movement.

Batching: acceptance AND commit are per-row. The caches carry per-row
``length`` cursors (shape (B,) — the model cache contract supports both,
`models/layers.py:cache_write`), so each row commits exactly its own
accepted count every iteration: one unlucky row no longer throttles the
batch to the minimum. Rows that hit EOS or their token budget freeze
(commit 0, cursor pinned) while the rest keep going, and the host loop
stops as soon as every row is frozen — no wasted target forwards after
early termination.

Acceptance diagnostics: a ``specdecode_accept_rate`` of 0.0 with a
layer-prefix draft was investigated as a suspected logit/position
misalignment in the accept comparison and CLEARED: at K=1 the engine's
accept rate equals the teacher-forced draft/target argmax-agreement rate,
and draft == target through the external-draft path accepts everything
(tests/test_speculative.py::TestAcceptRateRegression pins both). The 0.0
was draft QUALITY — a 2-layer prefix of random weights shares no
distribution with its 24-layer target — so an accept rate says something
about the mechanism only for a draft/target pair that is correlated.

Guarantees (both tested):
- greedy (``do_sample=False``): output is bit-identical to target-only
  greedy decoding, for ANY draft model;
- sampling: tokens are distributed exactly per the target's (warped)
  distribution — the Leviathan et al. accept/residual scheme with
  ``min(1, p/q)`` acceptance and a ``max(0, p-q)`` residual draw, applied
  after `generation.warp_logits` so temperature/top-k/top-p shape both
  distributions identically.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .generation import GenerationConfig, warp_logits

__all__ = ["SpeculativeGenerator", "generate_speculative"]

ApplyFn = Callable[[Any, jax.Array, Any], tuple[jax.Array, Any]]


def _probs(logits: jax.Array, config: GenerationConfig) -> jax.Array:
    return jax.nn.softmax(warp_logits(logits, config), axis=-1)


class SpeculativeGenerator:
    """Reusable speculative-decoding harness over two cached forwards.

    ``target_apply``/``draft_apply`` follow the family cache contract
    ``(params, tokens, cache) -> (logits, cache)``;
    ``*_init_cache(batch, max_len)`` build the empty caches. ``params`` is
    the pair ``(target_params, draft_params)`` at call time.
    """

    def __init__(
        self,
        target_apply: ApplyFn,
        target_init_cache: Callable[[int, int], Any],
        draft_apply: ApplyFn,
        draft_init_cache: Callable[[int, int], Any],
        config: GenerationConfig | None = None,
        *,
        draft_tokens: int = 4,
        jit_loop: bool = True,
    ) -> None:
        if draft_tokens < 1:
            raise ValueError(f"draft_tokens must be >= 1, got {draft_tokens}")
        self.config = config or GenerationConfig()
        self.draft_tokens = K = draft_tokens
        self.target_init_cache = target_init_cache
        self.draft_init_cache = draft_init_cache
        config_ = self.config
        eos, pad = config_.eos_token_id, config_.pad_token_id

        def prefill(pt, pd, prompt, t_cache, d_cache, rng):
            """Run the prompt through both models; sample the first token
            from the target (identical to non-speculative prefill)."""
            B = prompt.shape[0]
            t_logits, t_cache = target_apply(pt, prompt, t_cache)
            _, d_cache = draft_apply(pd, prompt, d_cache)
            rng, sub = jax.random.split(rng)
            from .generation import sample_tokens

            first = sample_tokens(t_logits[:, -1, :], sub, config_)
            done = (
                first == eos
                if eos is not None
                else jnp.zeros((B,), bool)
            )
            return first, t_cache, d_cache, rng, done

        def spec_step(pt, pd, last, t_cache, d_cache, rng, done, committed, quota):
            """One draft-K + verify iteration with PER-ROW commits.

            Returns ``tokens`` (B, K+1) with row r's committed tokens in its
            first ``n_row[r]`` columns (the host slices per row), caches
            rolled back to each row's committed length, the EOS state, and
            the per-row committed totals. Rows that are done (EOS) or have
            reached ``quota`` committed tokens are FROZEN: they commit 0 and
            their cache cursors stay put (bounding cache writes to
            ``[len, len+K+1)`` regardless of how long the batch's slowest
            row takes)."""
            B = last.shape[0]
            frozen = done | (committed >= quota)
            rng, r_draft, r_accept, r_fix = jax.random.split(rng, 4)

            # --- draft phase: K+1 single-token steps under lax.scan. Only
            # the first K proposals are verified; the extra step exists so
            # the draft CACHE covers position base+K (reached when all K
            # drafts are accepted) — without it the next iteration would
            # attend an unwritten cache row there.
            def draft_body(carry, r):
                tok, cache = carry
                logits, cache = draft_apply(pd, tok[:, None], cache)
                logits = logits[:, -1, :]
                if config_.do_sample:
                    nxt = jax.random.categorical(
                        r, warp_logits(logits, config_), axis=-1
                    ).astype(jnp.int32)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (nxt, cache), (nxt, _probs(logits, config_))

            (_, d_cache), (drafted, q_probs) = jax.lax.scan(
                draft_body, (last, d_cache), jax.random.split(r_draft, K + 1)
            )
            drafted = jnp.moveaxis(drafted, 0, 1)[:, :K]  # (B, K)
            q_probs = jnp.moveaxis(q_probs, 0, 1)[:, :K]  # (B, K, V)

            # --- verify phase: ONE target forward over [last, d_1..d_K].
            verify_in = jnp.concatenate([last[:, None], drafted], axis=1)
            t_logits, t_cache = target_apply(pt, verify_in, t_cache)
            p_probs = _probs(t_logits, config_)  # (B, K+1, V)

            # --- acceptance: per-row count of leading drafts that pass.
            if config_.do_sample:
                # Leviathan accept test: u < p(x)/q(x) per drafted token.
                p_at = jnp.take_along_axis(
                    p_probs[:, :K, :], drafted[:, :, None], axis=-1
                )[..., 0]
                q_at = jnp.take_along_axis(q_probs, drafted[:, :, None], axis=-1)[..., 0]
                u = jax.random.uniform(r_accept, (B, K))
                ok = u * q_at < p_at
            else:
                ok = drafted == jnp.argmax(t_logits[:, :K, :], axis=-1)
            accepted = jnp.cumprod(ok.astype(jnp.int32), axis=1)  # still-accepted mask
            a_row = accepted.sum(axis=1)  # (B,) accepted drafts in [0, K]

            # --- the (a+1)-th token, PER ROW: at a == K it's the bonus
            # draw from the target's K-th distribution; at a < K the draft
            # at slot a was rejected, so draw from the residual
            # max(0, p - q) (sampling) / take the target's argmax (greedy).
            a_idx = a_row[:, None, None]
            p_a = jnp.take_along_axis(p_probs, a_idx, axis=1)[:, 0, :]  # (B, V)
            if config_.do_sample:
                q_a = jnp.where(
                    (a_row < K)[:, None],
                    jnp.take_along_axis(
                        q_probs, jnp.minimum(a_row, K - 1)[:, None, None], axis=1
                    )[:, 0, :],
                    jnp.zeros_like(p_a),
                )
                resid = jnp.maximum(p_a - q_a, 0.0)
                resid_sum = resid.sum(axis=-1, keepdims=True)
                # Degenerate p<=q everywhere can't happen with exact math
                # (both sum to 1) but guard the fp32 edge: fall back to p.
                resid = jnp.where(resid_sum > 1e-9, resid / resid_sum, p_a)
                next_tok = jax.random.categorical(
                    r_fix, jnp.log(jnp.maximum(resid, 1e-38)), axis=-1
                ).astype(jnp.int32)
            else:
                next_tok = jnp.argmax(
                    jnp.take_along_axis(t_logits, a_idx, axis=1)[:, 0, :], axis=-1
                ).astype(jnp.int32)

            # --- per-row commit count: the a accepted drafts + next_tok,
            # capped at the row's remaining quota; frozen rows commit 0.
            n_row = jnp.where(
                frozen, 0, jnp.minimum(a_row + 1, jnp.maximum(quota - committed, 0))
            )

            # --- commit buffer: row r holds [d_1..d_a, next_tok] with
            # next_tok in column a_row[r]; the host takes the first n_row[r].
            cols = jnp.arange(K + 1)[None, :]
            buf = jnp.concatenate([drafted, jnp.zeros((B, 1), jnp.int32)], axis=1)
            buf = jnp.where(cols == a_row[:, None], next_tok[:, None], buf)
            committed_mask = cols < n_row[:, None]
            # EOS/pad discipline over each row's committed prefix.
            if eos is not None:
                is_eos = (buf == eos) & committed_mask
                seen = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) - is_eos.astype(jnp.int32)
                dead = done[:, None] | (seen > 0)
                buf = jnp.where(dead & committed_mask, pad, buf)
                done = done | (is_eos & ~dead).any(axis=1)

            # --- roll both caches back to each row's committed length. The
            # verify wrote K+1 entries at the row's base; committed are the
            # first n_row (last + n_row-1 drafts), with `next_tok` pending.
            # Frozen rows stay at base (their writes land in [base, base+K+1)
            # every iteration and are never read).
            base = t_cache["length"] - (K + 1)
            t_cache = dict(t_cache, length=base + n_row)
            d_cache = dict(d_cache, length=base + n_row)
            committed = committed + n_row
            # A row that just committed EOS (or exhausted its quota) is
            # frozen from the next iteration on; keep its pending token
            # stable so the draft input stays a valid id.
            next_tok = jnp.where(done | (committed >= quota), last, next_tok)
            # Observability: PER-ROW acceptance over live rows (what a
            # draft-model choice controls).
            live = ~frozen
            accept_frac = jnp.where(
                live.any(),
                (jnp.where(live, a_row, 0).sum() / jnp.maximum(live.sum(), 1)) / K,
                jnp.asarray(1.0),
            )
            return buf, n_row, next_tok, accept_frac, t_cache, d_cache, rng, done, committed

        if jit_loop:
            prefill = jax.jit(prefill, donate_argnums=(3, 4))
            spec_step = jax.jit(spec_step, donate_argnums=(3, 4))
        self._prefill = prefill
        self._spec_step = spec_step
        self.last_accept_rate = 0.0
        # Iterations whose commits were actually consumed by the last call
        # (excludes trailing over-dispatched ones) — the wall-clock driver
        # for batched decoding: per-row commits make this track the SLOWEST
        # row's own need instead of the min-commit count.
        self.last_iterations = 0

    def __call__(
        self,
        target_params: Any,
        draft_params: Any,
        prompt: jax.Array,
        *,
        rng: jax.Array | None = None,
        max_new_tokens: int | None = None,
        cache_len: int | None = None,
    ) -> jax.Array:
        """(B, S) int32 -> (B, S + max_new_tokens); EOS rows padded.

        ``max_new_tokens`` overrides the config's per call. The jitted
        steps specialize on CACHE SHAPE, which defaults to
        ``S + budget + 2*(K+1)`` — so distinct budgets retrace unless
        ``cache_len`` pins one capacity (any value >= the default bound)
        across calls.

        Also records ``self.last_accept_rate`` (mean drafted-token
        acceptance over the call) for observability/benching."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        budget = (
            max_new_tokens if max_new_tokens is not None else self.config.max_new_tokens
        )
        if budget <= 0:
            return prompt
        B, S = prompt.shape
        K = self.draft_tokens
        # Slack: optimistic dispatch (below) can overshoot the budget by at
        # most one iteration's K+1 commits, plus the K+1-wide verify write
        # region past the final committed position.
        needed = S + budget + 2 * (K + 1)
        max_len = cache_len if cache_len is not None else needed
        if max_len < needed:
            raise ValueError(
                f"cache_len={max_len} is too small for prompt {S} + "
                f"max_new_tokens {budget} with draft_tokens {K}; need >= {needed}."
            )
        t_cache = self.target_init_cache(B, max_len)
        d_cache = self.draft_init_cache(B, max_len)
        last, t_cache, d_cache, rng, done = self._prefill(
            target_params, draft_params, prompt, t_cache, d_cache, rng
        )
        # Switch to per-row length cursors AFTER prefill (the model cache
        # contract accepts scalar or (B,)): prefill — the largest KV write
        # of the whole call — keeps the scalar dynamic_update_slice fast
        # path; from here on each row advances by its own commits.
        t_cache = dict(t_cache, length=jnp.broadcast_to(t_cache["length"], (B,)))
        d_cache = dict(d_cache, length=jnp.broadcast_to(d_cache["length"], (B,)))
        # The iteration chain lives on device; the host only needs per-row
        # commit COUNTS (and EOS flags) to know when to stop. A sync per
        # iteration would serialize every step on the host<->device round
        # trip, so dispatch iterations OPTIMISTICALLY in batches of
        # ceil(remaining / (K+1)) — enough to finish the slowest live row if
        # every draft is accepted — then read the whole batch's counts in
        # one sync. Rejections just trigger another (smaller) batch; the
        # token stream is identical either way. Rows that hit EOS or their
        # budget freeze on device, and the loop ends as soon as no live row
        # remains (no wasted target forwards after early termination).
        quota = budget - 1  # per-row tokens still needed after `first_tok`
        first_tok = last
        committed = jnp.zeros((B,), jnp.int32)
        quota_dev = jnp.asarray(quota, jnp.int32)
        bufs: list[Any] = []  # device (B, K+1) commit buffers, in order
        counts: list[Any] = []  # host (B,) per-iteration commit counts
        accepts: list[float] = []
        totals = np.zeros((B,), np.int64)
        done_h = np.asarray(jax.device_get(done))
        while True:
            live = ~done_h & (totals < quota)
            if not live.any():
                break
            m = -(-int(quota - totals[live].min()) // (K + 1))
            batch_n, batch_af = [], []
            for _ in range(m):
                buf, n, last, accept_frac, t_cache, d_cache, rng, done, committed = (
                    self._spec_step(
                        target_params, draft_params, last, t_cache, d_cache, rng,
                        done, committed, quota_dev,
                    )
                )
                bufs.append(buf)
                batch_n.append(n)
                batch_af.append(accept_frac)
            ns, afs, done_h = jax.device_get(
                (jnp.stack(batch_n), jnp.stack(batch_af), done)
            )
            counts.extend(np.asarray(row) for row in ns)
            accepts.extend(float(v) for v in afs)
            totals += np.asarray(ns).sum(axis=0)
            done_h = np.asarray(done_h)
        # Assemble on host: one pipelined fetch of every commit buffer, then
        # per-row placement at each row's running offset. Rows frozen by EOS
        # underfill their budget; the remainder stays pad (matching the
        # vanilla generator's pad discipline).
        out = np.full((B, quota), self.config.pad_token_id, np.int32)
        pos = np.zeros((B,), np.int64)
        used = 0
        host_bufs = jax.device_get(bufs)
        for hb, n in zip(host_bufs, counts):
            if (pos >= np.minimum(totals, quota)).all():
                break
            for r in range(B):
                take = int(min(n[r], quota - pos[r]))
                if take > 0:
                    out[r, pos[r] : pos[r] + take] = hb[r, :take]
                    pos[r] += take
            used += 1
        self.last_accept_rate = sum(accepts[:used]) / max(used, 1)
        self.last_iterations = used
        first_h = np.asarray(jax.device_get(first_tok))[:, None].astype(np.int32)
        return jnp.concatenate(
            [prompt, jnp.asarray(first_h), jnp.asarray(out)], axis=1
        )


def generate_speculative(
    target_params: Any,
    draft_params: Any,
    prompt: jax.Array,
    *,
    target_apply: ApplyFn,
    target_init_cache: Callable[[int, int], Any],
    draft_apply: ApplyFn,
    draft_init_cache: Callable[[int, int], Any],
    config: GenerationConfig | None = None,
    draft_tokens: int = 4,
    rng: jax.Array | None = None,
    jit_loop: bool = True,
) -> jax.Array:
    """One-shot convenience over `SpeculativeGenerator` (rebuilds the jitted
    steps per call — construct the generator once for repeated use)."""
    gen = SpeculativeGenerator(
        target_apply, target_init_cache, draft_apply, draft_init_cache,
        config, draft_tokens=draft_tokens, jit_loop=jit_loop,
    )
    return gen(target_params, draft_params, prompt, rng=rng)
