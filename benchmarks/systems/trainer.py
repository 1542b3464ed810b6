"""A train cell: `Accelerator`'s compiled step fed by the repo's loader.

Set-up: state from the seed, the reference comparison on one probe batch
(before the first step, which donates the state), the first step on that
batch, a few warm steps. Window: steps on fresh batches with one step in
flight — step n is dispatched, then the host blocks on step n-1's loss, as a
loop that logs one step behind does — so the device never waits for the
host's read and the host cannot run past the window's end.
"""

from __future__ import annotations

import math
import time
from typing import Any

from .. import correctness, program
from ..reference.decoder import Arch, Decoder
from ..spans import Heartbeat
from ..stats import median


class TrainerCell:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.traffic = ctx.cell["traffic"]

    # ---------------------------------------------------------------- set-up
    def build(self) -> None:
        import jax

        from accelerate_tpu.parallel.mesh import data_parallel_size

        ctx = self.ctx
        self.acc, self.state, self.new_state, self.step, self.lcfg = program.build_trainer(
            ctx.config, ctx.cell, ctx.seed, ctx.devices
        )
        self.batch_size, self.seq_len = self.traffic["batch_size"], self.traffic["seq_len"]
        self.tokens_per_step = self.batch_size * self.seq_len
        self.per_process = self.batch_size // data_parallel_size(self.acc.mesh)
        self._draw(ctx.seed)

    def _loader(self, tokens, shuffle: bool, seed: int):
        from accelerate_tpu.data.array_dataset import ArrayDataset

        return self.acc.prepare_data_loader(
            ArrayDataset({"input_ids": tokens}),
            batch_size=self.per_process,
            shuffle=shuffle,
            seed=program.jax_seed(seed),
            drop_last=True,
        )

    def reseed(self, seed: int) -> None:
        """State, token stream and probe batch from ``seed`` (the compiled
        programs stay: `check_correct.py` walks many seeds in one process)."""
        self.state = None  # let the old state go before the new one is made
        self.state = self.new_state(seed)
        self._draw(seed)

    def _draw(self, seed: int) -> None:
        import jax

        jax.block_until_ready(self.state.params)
        stream = self.ctx.traffic_module.token_stream(
            self.traffic, seed, self.ctx.config["vocab_size"]
        )
        self.probe_tokens = stream["probe"]
        self.loader = self._loader(stream["sequences"], True, seed)
        self.probe_batch = next(iter(self._loader(self.probe_tokens, False, seed)))

    def reference(self, what_if=None) -> dict[str, float]:
        """Loss and gradient norm of the probe batch by the plain decoder,
        from the state's own parameters (on the first device). ``what_if``
        (`check_correct.py`) alters the weights the reference sees."""
        arch = Arch.from_config(self.ctx.config)
        get_layer, top = program.reference_weights(
            self.state.params, self.lcfg, self.ctx.devices[0]
        )
        if what_if is not None:
            arch, get_layer = what_if(arch, get_layer)
        return Decoder.of(arch).loss_and_grad_norm(get_layer, top, self.probe_tokens)

    def probe(self) -> dict[str, float]:
        """Reference first, then the real step's first step on the same
        batch: its loss is computed on the parameters before the update.
        Where the cell's ``probe.update_signs`` is set, the norm weights are
        read around the step (`correctness.update_sign_flip_share`)."""
        t0 = time.perf_counter()
        reference = self.reference()
        t1 = time.perf_counter()
        signs = self.ctx.cell["probe"]["update_signs"]
        before = program.norm_scales(self.state.params) if signs else None
        self.state, metrics = self.step(self.state, self.probe_batch)
        self.first_step = {"loss": float(metrics["loss"]), "grad_norm": float(metrics["grad_norm"])}
        if signs:
            self.first_step.update(before=before, after=program.norm_scales(self.state.params))
        distances = correctness.train_distances(self.first_step, reference)
        distances["reference_s"] = t1 - t0
        distances["first_step_s"] = time.perf_counter() - t1
        return distances

    def batches(self):
        epoch = 0
        while True:
            self.loader.set_epoch(epoch)
            yield from self.loader
            epoch += 1

    def warm(self) -> None:
        import jax

        self._batches = self.batches()
        for _ in range(self.ctx.cell["warm_steps"]):
            self.state, metrics = self.step(self.state, next(self._batches))
        jax.block_until_ready(metrics["loss"])

    def compiled_programs(self) -> int:
        return program.jit_cache_sizes(self.step)

    # ---------------------------------------------------------------- window
    def window(self, seconds: float, tracer) -> dict[str, Any]:
        import jax

        spans = self.ctx.spans
        losses, shapes, done_at, cpu_at = [], set(), [], []
        pending = None  # the loss of the step in flight
        first_dispatch = None
        compiled_before = self.compiled_programs()
        beat = Heartbeat().start()
        t_open = time.perf_counter()
        steps = 0
        while True:
            with spans("next-batch"):
                batch = next(self._batches)
            now = time.perf_counter()
            if now - t_open >= seconds:
                break
            tracer.poll(now - t_open)
            shapes.add(tuple(batch["input_ids"].shape))
            with spans("step-dispatch"):
                if first_dispatch is None:
                    first_dispatch = time.perf_counter()
                self.state, metrics = self.step(self.state, batch)
            steps += 1
            if pending is not None:
                with spans("block"):
                    losses.append(float(pending))
                done_at.append(time.perf_counter())
                cpu_at.append(time.process_time())
            pending = metrics["loss"]
        if pending is not None:
            with spans("block"):
                losses.append(float(jax.block_until_ready(pending)))
            done_at.append(time.perf_counter())
            cpu_at.append(time.process_time())
        t_close = time.perf_counter()
        beat.stop()
        tracer.finish()
        finite = [math.isfinite(x) for x in losses]
        compiled = self.compiled_programs() - compiled_before
        # From one step's loss reaching the host to the next one's. A step
        # that took over 1.5 times the median is a stall, set out for a
        # reader so that a stalled run can be told from a slow one: how long,
        # how much processor time the process used in it (about none: it
        # waited; about all: a thread of its own was busy) and the longest
        # late tick of the heartbeat in it (the process stood still).
        step_ms = [(b - a) * 1e3 for a, b in zip(done_at, done_at[1:])]
        usual = median(step_ms) if step_ms else 0.0
        stalls = [
            {"at_s": done_at[i] - t_open, "ms": ms, "cpu_ms": (cpu_at[i + 1] - cpu_at[i]) * 1e3,
             "late_tick_ms": beat.longest_ms(done_at[i], done_at[i + 1])}
            for i, ms in enumerate(step_ms) if ms > 1.5 * usual
        ]
        return {
            "attempted": steps,
            "failed": steps - sum(finite),
            "invariants": {
                "every_loss_finite": all(finite) and len(losses) == steps,
                "one_batch_shape": len(shapes) == 1,
                "no_compilation_in_window": compiled == 0,
            },
            "compilations_in_window": compiled,
            "samples": {
                "train_tokens": steps * self.tokens_per_step,
                "train_seconds": (t_close - first_dispatch) if first_dispatch else 0.0,
                "losses": losses,
                "step_ms": step_ms,
            },
            "counters": {
                "steps": steps,
                "tokens_per_step": self.tokens_per_step,
                "batch_size": self.batch_size,
                "seq_len": self.seq_len,
                "stalled_steps": len(stalls),
                "stalled_ms": sum(x["ms"] - usual for x in stalls),
                "usual_step_cpu_ms": median([(b - a) * 1e3 for a, b in zip(cpu_at, cpu_at[1:])] or [0.0]),
                "stalls": stalls,
            },
            "t_open": t_open,
            "t_close": t_close,
        }
CELL = TrainerCell
