"""What the engine's fetch accounting costs the host a fetch: `Engine._fetch`
(the tally of what the fetch waits on, the ``serve_fetch`` span with its
``seq`` / ``chunks`` / ``decodes``, the clocks and the stall rule) against
the bare ``jax.device_get`` it wraps and against the span without
attributes that the fetch had before, on a value already on the host's
side of the wait, so that what is left is the accounting alone; and what a
fetch the rule flags adds (the two counters and the ``fetch_stall``
record). Each form is timed ``--rounds`` times over ``--fetches`` fetches,
with no capture live and with a profiler capture live; the median round is
printed, in microseconds a fetch, as one JSON line.

    python3 perf/fetch_accounting_cost.py
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--fetches", type=int, default=5000)
    parser.add_argument("--rounds", type=int, default=7)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=64, num_heads=4, num_kv_heads=2)
    engine = serving.Engine(
        lambda p, t, c: llama.forward_with_cache(p, t, c, cfg),
        lambda b, m: llama.init_cache(cfg, b, m),
        llama.init(jax.random.PRNGKey(0), cfg),
        GenerationConfig(),
        slots=2, buckets=(8,), max_len=32, prefix_cache=False,
    )
    # what a decode step's fetch is handed: its tokens and its counts
    value = [(jnp.zeros((32,), jnp.int32), {})]
    jax.block_until_ready(value)

    def bare():
        jax.device_get(value)

    def span_only():
        with jax.profiler.TraceAnnotation("serve_fetch"):
            jax.device_get(value)

    def accounted():
        engine._dispatched("decode")
        engine._fetch(value)

    def flagged():  # every wait a stall: the counters and the flight record too
        engine._stalls.factor = 0.0
        try:
            accounted()
        finally:
            engine._stalls.factor = float("inf")

    # A ready value's wait jitters by more than half of itself: the rule is
    # kept from flagging in the forms that time the path every fetch takes.
    engine._stalls.factor = float("inf")
    forms = {
        "device_get": bare,
        "span_without_attributes": span_only,
        "engine_fetch": accounted,
        "engine_fetch_flagged": flagged,
    }

    def timed(fn) -> float:
        rounds = []
        for _ in range(args.rounds):
            t0 = time.perf_counter()
            for _ in range(args.fetches):
                fn()
            rounds.append((time.perf_counter() - t0) / args.fetches * 1e6)
        return statistics.median(rounds)

    # Its parts, one at a time. Before the wait (the tally, the span's entry,
    # the clocks) the device is still running what the fetch waits for in a
    # serving loop; after it (the span's exit, the key, the rule) the next
    # dispatch waits.
    from accelerate_tpu import telemetry

    def span_with_attributes():
        with telemetry.span("serve_fetch", seq=1, chunks=1, decodes=1):
            pass

    def span_bare():
        with telemetry.span("serve_fetch"):
            pass

    tally = {1024: 1, "decode": 1}
    parts = {
        "process_time": time.process_time,
        "perf_counter": time.perf_counter,
        "span_with_attributes": span_with_attributes,
        "span_without_attributes": span_bare,
        "key": lambda: frozenset(tally.items()),
        "rule": lambda: engine._stalls.judge("part", 0.001),
        "tally": lambda: engine._dispatched("decode"),
    }
    engine._waiting_on = {}

    out = {"device": jax.devices()[0].device_kind, "fetches": args.fetches, "rounds": args.rounds}
    out["parts_us"] = {name: timed(fn) for name, fn in parts.items()}
    engine._waiting_on = {}
    for fn in forms.values():
        fn()  # first calls outside the clock
    out["no_capture_us"] = {name: timed(fn) for name, fn in forms.items()}
    with tempfile.TemporaryDirectory() as log_dir:
        options = jax.profiler.ProfileOptions()  # as the benchmark's tracer starts one
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            out["capture_live_us"] = {name: timed(fn) for name, fn in forms.items()}
        finally:
            jax.profiler.stop_trace()
    for key in ("no_capture_us", "capture_live_us"):
        us = out[key]
        us["accounting"] = us["engine_fetch"] - us["device_get"]
        us["added_over_the_span_before"] = us["engine_fetch"] - us["span_without_attributes"]
        us["a_stall_flagged"] = us["engine_fetch_flagged"] - us["engine_fetch"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
