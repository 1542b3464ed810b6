"""A serve cell: `serving.Engine` on int8 weights, driven by one thread.

Set-up: weights from the seed, then the probe — a handful of seeded prompts,
one for each prefill bucket and one that takes several chunks, submitted
together — whose served tokens the reference judges. The probe is also the
warm-up: it runs the decode program and every prefill bucket once. Window:
the traffic generator's jobs, each submitted when due, the engine stepped by
the same thread (an `Engine` is not thread-safe), every token timed by the
client's own `Request.stream` callback.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from .. import correctness, program
from ..reference.decoder import Arch, Decoder
from ..traffic.jobs import prompt_tokens


@dataclasses.dataclass
class _Record:
    job: Any
    due: float  # absolute, time.perf_counter
    submitted: float = 0.0
    times: list = dataclasses.field(default_factory=list)  # one per streamed token
    completion: Any = None
    refused: bool = False

    @property
    def ok(self) -> bool:
        c = self.completion
        return (
            c is not None
            and c.n_new == self.job.new_tokens
            and c.finish_reason == "length"
            and len(self.times) == self.job.new_tokens
        )


class EngineCell:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.traffic = ctx.cell["traffic"]

    # ---------------------------------------------------------------- set-up
    def build(self) -> None:
        import jax

        ctx = self.ctx
        self.engine, self.params, self.lcfg = program.build_engine(
            ctx.config, ctx.cell, ctx.seed, ctx.devices[0]
        )
        jax.block_until_ready(self.params)
        self.vocab = ctx.config["vocab_size"]
        self.probe_seed = ctx.seed

    def reseed(self, seed: int) -> None:
        """Weights from another seed in the same engine (the compiled
        programs stay: `check_correct.py` walks many seeds in one process)."""
        import jax

        self.engine.params = self.params = None
        self.params = program.init_int8_params(
            jax.random.PRNGKey(program.jax_seed(seed)), self.lcfg
        )
        self.engine.params = jax.block_until_ready(self.params)
        self.engine.prefill_signatures.clear()
        self.probe_seed = seed

    def serve_probe(self):
        """The probe's prompts through the engine, all submitted at once:
        the engine's order of work does not depend on the clock, so the
        batches are composed the same way in every run."""
        probe = self.ctx.cell["probe"]
        rng = np.random.default_rng([int(self.probe_seed), 0x7072])
        prompts = [rng.integers(0, self.vocab, n, dtype=np.int32) for n in probe["prompt_tokens"]]
        for prompt in prompts:
            self.engine.submit(prompt, max_new_tokens=probe["new_tokens"])
        done = {c.rid: c for c in self.engine.run_until_idle()}
        served = [done[i] for i in sorted(done)]
        buckets = set(self.engine.buckets)
        if set(self.engine.prefill_signatures) != buckets:
            raise RuntimeError(
                f"the probe ran prefill buckets {sorted(set(self.engine.prefill_signatures))} "
                f"of {sorted(buckets)}: give `probe.prompt_tokens` one prompt for each"
            )
        return prompts, served

    def probe(self, what_if=None) -> dict[str, Any]:
        """Serve the probe and judge it. With ``what_if`` (`check_correct.py`)
        the tokens already served are judged again, by an altered reference."""
        t0 = time.perf_counter()
        if what_if is None:
            self._served_probe = self.serve_probe()
        prompts, served = self._served_probe
        t1 = time.perf_counter()
        n_new = self.ctx.cell["probe"]["new_tokens"]
        bad = [c.rid for c in served if c.n_new != n_new or c.finish_reason != "length"]
        # A full forward of the reference over prompt + served tokens, each
        # row right-padded to a multiple of 128 (causal: padding is never
        # seen), so that a handful of shapes is compiled, not one a prompt.
        arch = Arch.from_config(self.ctx.config)
        get_layer, top = program.reference_weights(self.params, self.lcfg, self.ctx.devices[0])
        if what_if is not None:  # `check_correct.py`: alter what the reference sees
            arch, get_layer = what_if(arch, get_layer)
        by_width: dict[int, list[int]] = {}
        for r, prompt in enumerate(prompts):
            by_width.setdefault(-(-(len(prompt) + n_new) // 128) * 128, []).append(r)
        decoder = Decoder.of(arch)
        logits: list = [None] * len(prompts)
        for width, members in by_width.items():
            rows = np.zeros((len(members), width), np.int32)
            positions = []
            for k, r in enumerate(members):
                n = len(prompts[r])
                rows[k, :n] = prompts[r]
                rows[k, n : n + n_new] = served[r].tokens[:n_new]
                # logits after prompt + served[:i] predict served[i]
                positions.append(slice(n - 1, n + n_new - 1))
            for r, l in zip(members, decoder.forward_logits(get_layer, top, rows, positions)):
                logits[r] = l
        gaps = [correctness.short_of_top(l, np.asarray(c.tokens[:n_new])) for l, c in zip(logits, served)]
        distances = correctness.serve_distances(gaps)
        distances["wrong_length"] = bad
        distances["probe_serve_s"] = t1 - t0
        distances["reference_s"] = time.perf_counter() - t1
        return distances

    def warm(self) -> None:
        """Nothing more: the probe ran every program the traffic uses."""

    def compiled_programs(self) -> int:
        e = self.engine
        return program.jit_cache_sizes(e._decode, e._prefill, e._copy)

    # ---------------------------------------------------------------- window
    def _submit(self, record: _Record, index: int) -> None:
        from accelerate_tpu.serving import Request

        job = record.job
        prompt = prompt_tokens(self.ctx.seed, index, job.prompt_tokens, self.vocab)
        times, clock = record.times, time.perf_counter

        def on_token(rid, token, piece, _append=times.append, _clock=clock):
            _append(_clock())

        record.submitted = clock()
        try:
            rid = self.engine.submit_request(
                Request(prompt=prompt, max_new_tokens=job.new_tokens, stream=on_token)
            )
        except ValueError:
            record.refused = True
            return
        self._by_rid[rid] = record

    def window(self, seconds: float, tracer) -> dict[str, Any]:
        ctx, engine, spans = self.ctx, self.engine, self.ctx.spans
        sched = ctx.traffic_module.schedule(self.traffic, seconds)
        drain_limit = self.traffic["drain_seconds"]
        pending = sorted(sched.initial, key=lambda j: j.due)[::-1]  # pop() takes the earliest
        records: list[_Record] = []
        self._by_rid: dict[int, _Record] = {}
        compiled_before = self.compiled_programs()
        clock = time.perf_counter
        t_open = clock() + sched.warm_seconds
        stats_open = stats_close = None
        live_sum = live_n = 0
        open_jobs = 0  # measured jobs not yet finished (open loop)
        while True:
            rel = clock() - t_open
            if stats_open is None and rel >= 0.0:
                stats_open = dict(engine.stats)
            if stats_close is None and rel >= seconds:
                stats_close = dict(engine.stats)
            tracer.poll(rel)
            if pending and pending[-1].due <= rel:
                with spans("submit"):
                    while pending and pending[-1].due <= rel:
                        job = pending.pop()
                        record = _Record(job=job, due=t_open + job.due)
                        records.append(record)
                        self._submit(record, len(records))
                        if job.phase == "window" and not record.refused:
                            open_jobs += 1
            if engine.busy:
                with spans("engine-step"):
                    completions = engine.step()
                if 0.0 <= rel < seconds:
                    live_sum += sum(
                        r.job.prompt_tokens + len(r.times)
                        for r in self._by_rid.values()
                        if r.times and r.completion is None
                    )
                    live_n += 1
                for c in completions:
                    record = self._by_rid[c.rid]
                    record.completion = c
                    if record.job.phase == "window":
                        open_jobs -= 1
                    if sched.after is not None:
                        nxt = sched.after(record.job, clock() - t_open)
                        if nxt is not None:
                            pending.append(nxt)
                            pending.sort(key=lambda j: -j.due)
            elif pending and rel < seconds + drain_limit:
                with spans("sleep-until-due"):
                    time.sleep(max(pending[-1].due - (clock() - t_open), 0.0))
                continue
            elif not pending:
                break  # nothing in flight and nothing left to send
            rel = clock() - t_open
            if rel >= seconds:
                measured_done = open_jobs == 0 if sched.measured_by == "due" else not engine.busy
                if measured_done or rel >= seconds + drain_limit:
                    break
        t_end = clock()
        tracer.finish()
        if stats_open is None:
            stats_open = dict(engine.stats)
        if stats_close is None:
            stats_close = dict(engine.stats)
        t_close = t_open + seconds
        compiled = self.compiled_programs() - compiled_before

        if sched.measured_by == "due":
            measured = [r for r in records if r.job.phase == "window"]
            attempted = measured
        else:
            measured = [
                r for r in records if r.completion is not None and t_open <= r.times[-1] <= t_close
            ]
            attempted = records
        failed = [r for r in attempted if not r.ok]
        finished = [r for r in records if r.completion is not None]
        good = [r for r in measured if r.ok]
        itl = [g * 1e3 for r in good for g in np.diff(r.times)]
        # Throughput counts every token a client received inside the window,
        # whichever request it belongs to: all the work, all the time.
        streamed = sum(1 for r in records for t in r.times if t_open <= t <= t_close)
        counters = {k: stats_close[k] - stats_open[k] for k in stats_close}
        counters.update(
            slots=engine.n_slots,
            live_kv_tokens_mean=(live_sum / live_n) if live_n else 0.0,
            requests_measured=len(measured),
        )
        return {
            "attempted": len(attempted),
            "failed": len(failed),
            # A request still unfinished at the drain limit counts in `failed`
            # and not here: whether it finished in time is the clock's doing,
            # and the clock decides nothing about `correct`.
            "invariants": {
                "every_completion_has_its_length": all(r.ok for r in finished),
                "none_refused": not any(r.refused for r in records),
                "no_compilation_in_window": compiled == 0,
            },
            "compilations_in_window": compiled,
            "samples": {
                "ttft_ms": [(r.times[0] - r.due) * 1e3 for r in good],
                "itl_ms": itl,
                "generated_tokens": streamed,
                "window_seconds": seconds,
                "late_ms": [(r.submitted - r.due) * 1e3 for r in measured],
                "drain_seconds": t_end - t_close,
            },
            "counters": counters,
            "t_open": t_open,
            "t_close": t_close,
        }
CELL = EngineCell
