"""`accelerate-tpu serve` / `atx serve` — continuous-batching micro-server.

A benchmarking driver for `serving.Engine` (docs/serving.md): builds a
model-zoo preset with random weights (or loads a local HF repo), replays a
Poisson arrival trace of mixed-length requests through the engine, and
prints one JSON line of serving metrics (`serve_tokens_per_sec`,
`serve_p50_ms`, `serve_p99_ms`, occupancy), runnable standalone on any host:

    atx serve --model llama-tiny --slots 8 --requests 64 --rate 16

``--compare-b1`` additionally runs the same request set sequentially
through batch-1 `generate()` and reports the speedup (the ISSUE-3
acceptance bar is >= 3x on a real chip).

``--replicas N`` (N >= 2) serves the trace through the multi-replica
`serving.Router` instead — N identically configured engines behind
prefix-affinity routing, a bounded EDF/priority admission queue
(``--queue-depth``, ``--affinity``, ``--scheduling``), and optional
replica re-admission after quarantine (``--readmit-secs``); router
fleet metrics join the JSON line as
``serve_router_*`` keys, and a SIGTERM mid-trace drains gracefully and
exits 75 (the elastic-launcher resume contract — docs/serving.md).
"""

from __future__ import annotations

import argparse
import json
import time


def register(subparsers: argparse._SubParsersAction) -> None:
    p = subparsers.add_parser(
        "serve",
        help="Continuous-batching serving benchmark (Poisson request trace)",
    )
    p.add_argument(
        "--model",
        default="llama-tiny",
        help="model preset (see `atx estimate --list`) or a local HF repo path",
    )
    p.add_argument("--slots", type=int, default=None, help="KV slot pool size (ATX_SERVE_SLOTS)")
    p.add_argument(
        "--buckets",
        default=None,
        help="comma-separated prefill bucket lengths (ATX_SERVE_BUCKETS)",
    )
    p.add_argument("--max-len", type=int, default=None, help="per-slot KV capacity")
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--rate", type=float, default=16.0, help="Poisson arrivals/sec")
    p.add_argument("--prompt-lens", default="8:96", help="min:max prompt length")
    p.add_argument("--new-tokens", default="8:48", help="min:max tokens per request")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--do-sample", action="store_true")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument(
        "--realtime",
        action="store_true",
        help="honour arrival times on the wall clock (latency mode); "
        "default replays the trace as fast as the engine drains it",
    )
    p.add_argument(
        "--prefix-cache",
        dest="prefix_cache",
        action="store_true",
        default=None,
        help="force the prefix cache ON (default: on unless "
        "ATX_SERVE_PREFIX_CACHE=0)",
    )
    p.add_argument(
        "--no-prefix-cache",
        dest="prefix_cache",
        action="store_false",
        help="disable the prefix cache",
    )
    p.add_argument(
        "--prefix-cache-mib",
        type=float,
        default=None,
        help="prefix-cache pool byte budget in MiB (ATX_SERVE_PREFIX_CACHE_MIB)",
    )
    p.add_argument(
        "--shared-prefix",
        type=int,
        default=0,
        metavar="LEN",
        help="give every request one of --shared-prefixes common system "
        "prompts of LEN tokens (the prefix-cache workload shape); "
        "prompt-lens then sizes only the unique tails",
    )
    p.add_argument(
        "--shared-prefixes",
        type=int,
        default=2,
        help="number of distinct shared system prompts (with --shared-prefix)",
    )
    p.add_argument(
        "--stop",
        default=None,
        metavar="IDS",
        help="comma-separated token ids used as one multi-token stop "
        "sequence on every request (host-side tail match)",
    )
    p.add_argument(
        "--compare-b1",
        action="store_true",
        help="also run the request set sequentially through batch-1 "
        "generate() and report the speedup",
    )
    p.add_argument(
        "--replicas",
        type=int,
        default=1,
        help="serve through the multi-replica Router with N engine "
        "replicas (1 = single engine, no router)",
    )
    p.add_argument(
        "--queue-depth",
        type=int,
        default=None,
        help="router admission-queue bound (ATX_SERVE_QUEUE_DEPTH; "
        "default 4x total fleet slots)",
    )
    p.add_argument(
        "--affinity",
        choices=("prefix", "least-loaded"),
        default="prefix",
        help="router placement policy: prefix-affinity steering with "
        "least-loaded fallback, or pure least-loaded",
    )
    p.add_argument(
        "--scheduling",
        choices=("edf", "fifo"),
        default="edf",
        help="router admission order: earliest-deadline-first over "
        "priority classes with load shedding (edf, default) or plain "
        "arrival order (fifo — the pre-self-healing behaviour)",
    )
    p.add_argument(
        "--readmit-secs",
        type=float,
        default=None,
        metavar="SECS",
        help="probe a quarantined replica after SECS (capped-exponential "
        "backoff) and re-admit it under probation once its canary replays "
        "bit-identically (ATX_SERVE_READMIT_SECS; default: off — a lost "
        "replica stays quarantined)",
    )
    p.add_argument(
        "--metrics-port",
        type=int,
        default=None,
        metavar="PORT",
        help="expose a Prometheus /metrics endpoint on PORT for the whole "
        "run (0 = pick a free port; the bound URL is printed to stderr). "
        "The endpoint stays up until the trace — and the router drain, "
        "with --replicas — has finished (docs/observability.md)",
    )
    p.set_defaults(func=run)


def _span(text: str) -> tuple[int, int]:
    lo, _, hi = text.partition(":")
    return int(lo), int(hi or lo)


def _build_model(name: str):
    """(apply_fn, init_cache_fn, params, vocab_size) for a preset or local
    HF repo. Presets initialize random bf16 weights — throughput is
    weight-agnostic."""
    import os

    import jax
    import jax.numpy as jnp

    if os.path.isdir(name):
        import accelerate_tpu as atx
        from accelerate_tpu.models import llama

        loaded = atx.load_pretrained(name, dtype=jnp.bfloat16)
        cfg = loaded.config
        return (
            lambda p, t, c: llama.forward_with_cache(p, t, c, cfg),
            lambda b, m: llama.init_cache(cfg, b, m),
            loaded.params,
            cfg.vocab_size,
        )
    from .estimate import _MODEL_PRESETS

    if name not in _MODEL_PRESETS:
        raise SystemExit(
            f"unknown model {name!r}; pick from `atx estimate --list` or "
            "pass a local HF repo path"
        )
    family_name, preset = _MODEL_PRESETS[name]
    import importlib

    family = importlib.import_module(f"accelerate_tpu.models.{family_name}")
    if not hasattr(family, "forward_with_cache"):
        raise SystemExit(
            f"{name} is a {family_name} model — no decode cache path; pick "
            "a decoder preset (llama-*, gpt*)"
        )
    config_cls = {"llama": "LlamaConfig", "gpt": "GPTConfig"}[family_name]
    cfg = getattr(getattr(family, config_cls), preset)()
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        family.init(jax.random.PRNGKey(0), cfg),
    )
    return (
        lambda p, t, c: family.forward_with_cache(p, t, c, cfg),
        lambda b, m: family.init_cache(cfg, b, m),
        params,
        cfg.vocab_size,
    )


def replica_params(params, replica: int):
    """Replica ``replica``'s weights, committed to local device
    ``replica mod n``: an `Engine` keeps its KV pools where its weights live,
    so N replicas spread over the host's chips instead of stacking on chip 0
    (on one device they share one copy)."""
    import jax

    devices = jax.local_devices()
    return jax.device_put(params, devices[replica % len(devices)])


def run(args: argparse.Namespace) -> int:
    import numpy as np

    from ..generation import GenerationConfig, Generator
    from ..serving import Engine, poisson_trace, shared_prefix_trace

    apply_fn, init_cache_fn, params, vocab = _build_model(args.model)
    prompt_lens = _span(args.prompt_lens)
    new_tokens = _span(args.new_tokens)
    buckets = (
        tuple(int(b) for b in args.buckets.split(",")) if args.buckets else None
    )
    config = GenerationConfig(
        do_sample=args.do_sample, temperature=args.temperature
    )
    stop_sequences = (
        [tuple(int(t) for t in args.stop.split(","))] if args.stop else None
    )
    max_len = args.max_len
    if max_len is None:
        # Fit the worst-case request: prompt rounded up to a bucket + budget.
        from ..serving import default_buckets

        bs = buckets or default_buckets()
        longest = prompt_lens[1] + args.shared_prefix
        rounded = min((b for b in bs if b >= longest), default=None)
        top = rounded if rounded is not None else -(-longest // bs[-1]) * bs[-1]
        max_len = top + new_tokens[1]
    def mk_engine(replica: int = 0) -> Engine:
        return Engine(
            apply_fn,
            init_cache_fn,
            replica_params(params, replica),
            config,
            slots=args.slots,
            buckets=buckets,
            max_len=max_len,
            prefix_cache=args.prefix_cache,
            prefix_cache_mib=args.prefix_cache_mib,
        )

    router = None
    if args.replicas > 1:
        from .. import resilience
        from ..serving import Router

        # SIGTERM now means "drain, then exit 75" instead of dying mid-token.
        resilience.install_preemption_handler()
        engines = [mk_engine(i) for i in range(args.replicas)]
        engine = engines[0]
        router = Router(
            engines,
            queue_depth=args.queue_depth,
            affinity=args.affinity,
            scheduling=args.scheduling,
            readmit_secs=args.readmit_secs,
            # A fatally wedged replica is rebuilt from scratch (on its own
            # device) at probe time rather than trusting mid-step engine
            # state.
            engine_factory=mk_engine,
        )
    else:
        engine = mk_engine()
    # Startup capacity line: the static planner verdict for the replica-0
    # engine (atx estimate --serve gives the full table).
    import sys as _sys

    from ..analysis.capacity import plan_for_engine

    _cap_engine = router.replicas[0].engine if router is not None else engine
    try:
        print(
            f"[atx serve] {plan_for_engine(_cap_engine).format()}",
            file=_sys.stderr,
        )
    except Exception:
        pass  # planner is advisory; never block serving on it
    if args.shared_prefix > 0:
        trace = shared_prefix_trace(
            args.requests,
            args.rate,
            vocab_size=vocab,
            n_prefixes=args.shared_prefixes,
            prefix_len=args.shared_prefix,
            tail_lens=prompt_lens,
            new_tokens=new_tokens,
            seed=args.seed,
            stop_sequences=stop_sequences,
        )
    else:
        trace = poisson_trace(
            args.requests,
            args.rate,
            vocab_size=vocab,
            prompt_lens=prompt_lens,
            new_tokens=new_tokens,
            seed=args.seed,
            stop_sequences=stop_sequences,
        )
    metrics_server = None
    if args.metrics_port is not None:
        import sys

        from .. import telemetry

        metrics_server = telemetry.MetricsServer(port=args.metrics_port)
        print(
            f"[atx serve] /metrics listening on {metrics_server.url}",
            file=sys.stderr,
        )
    try:
        t0 = time.perf_counter()
        if router is not None:
            completions = router.serve(trace, realtime=args.realtime)
            router.close()
        else:
            completions = engine.serve(trace, realtime=args.realtime)
        wall = time.perf_counter() - t0

        total_new = sum(c.n_new for c in completions)
        # Latency stats over requests that actually finished (a drained or
        # deadline-cancelled request has no meaningful TTFT/e2e).
        finished = [
            c for c in completions if c.finish_reason not in ("cancelled", "failed")
        ] or completions
        lat_ms = sorted(1e3 * (c.finished_at - c.submitted_at) for c in finished)
        ttft_ms = sorted(1e3 * (c.first_token_at - c.submitted_at) for c in finished)
        pick = lambda xs, q: xs[min(len(xs) - 1, int(q * len(xs)))] if xs else 0.0
        result = {
            "serve_requests": len(completions),
            "serve_tokens_per_sec": round(total_new / max(wall, 1e-9), 1),
            "serve_wall_s": round(wall, 2),
            "serve_p50_ms": round(pick(lat_ms, 0.50), 1),
            "serve_p99_ms": round(pick(lat_ms, 0.99), 1),
            "serve_ttft_p50_ms": round(pick(ttft_ms, 0.50), 1),
            "serve_ttft_p99_ms": round(pick(ttft_ms, 0.99), 1),
            "serve_slots": engine.n_slots,
            "serve_buckets": list(engine.buckets),
            "serve_prefill_compiles": engine._prefill._cache_size(),
            "serve_decode_compiles": engine._decode._cache_size(),
            "serve_occupancy": round(
                engine.stats["decode_slot_steps"]
                / max(engine.stats["decode_steps"] * engine.n_slots, 1),
                3,
            ),
        }
        if router is None:
            # Single-engine runs report the engine's own exact percentiles
            # (its `request` records): the samples `/metrics` exports in
            # buckets (docs/observability.md).
            lat = engine.latency_summary()
            for out_key, reg_key in (
                ("serve_p50_ms", "p50_ms"),
                ("serve_p99_ms", "p99_ms"),
                ("serve_ttft_p50_ms", "ttft_p50_ms"),
                ("serve_ttft_p99_ms", "ttft_p99_ms"),
            ):
                if lat[reg_key] is not None:
                    result[out_key] = round(lat[reg_key], 1)
        for key, val in engine.prefix_metrics().items():
            result["serve_" + key] = val
        if args.compare_b1:
            gens: dict[int, Generator] = {}
            t0 = time.perf_counter()
            for r in trace:
                g = gens.setdefault(
                    r.max_new_tokens,
                    Generator(
                        apply_fn,
                        init_cache_fn,
                        GenerationConfig(
                            max_new_tokens=r.max_new_tokens,
                            do_sample=args.do_sample,
                            temperature=args.temperature,
                        ),
                    ),
                )
                out = g(params, np.asarray(r.prompt)[None])
                int(np.asarray(out[0, -1]))  # fetch barrier
            b1_wall = time.perf_counter() - t0
            result["serve_b1_sequential_s"] = round(b1_wall, 2)
            result["serve_vs_b1_speedup"] = round(b1_wall / max(wall, 1e-9), 2)
        if router is not None:
            from .. import resilience

            fleet = router.metrics()
            per = fleet.pop("per_replica")
            for key, val in fleet.items():
                result["serve_router_" + key] = val
            result["serve_router_occupancy"] = [p["occupancy"] for p in per]
            result["serve_router_hit_rates"] = [p["prefix_hit_rate"] for p in per]
            result["serve_router_quarantined"] = [p["quarantined"] for p in per]
            print(json.dumps(result))
            if router.draining and router.drain_reason == "preemption":
                # The launcher resume contract (docs/fault_tolerance.md):
                # in-flight work finished above; 75 = resume me, free of charge.
                from ..telemetry import flight as _flight

                _flight.dump_postmortem(
                    "preemption_drain_75",
                    extra={"drain_reason": router.drain_reason},
                )
                return resilience.PREEMPTION_EXIT_CODE
            return 0
        print(json.dumps(result))
        return 0
    finally:
        # The endpoint outlives the trace (and the router drain above) so a
        # late scrape still sees the final counters; closed only on exit.
        if metrics_server is not None:
            metrics_server.close()
