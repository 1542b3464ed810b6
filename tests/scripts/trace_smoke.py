"""End-to-end tracing smoke (Makefile smoke-trace lane).

Drives a 16-request Poisson trace through a 2-replica Router twice —
``ATX_TRACE_REQUESTS=0`` then ``1`` with the spans JSONL mirror and a
postmortem bundle armed — and checks the ISSUE-15 acceptance bars:

- greedy outputs are BIT-IDENTICAL with tracing on vs off;
- `atx trace <bundle> --check 0.05` passes: every request's
  queue/prefill/decode/emit phase spans sum to its e2e within 5%, and
  the waterfall + attribution table render;
- the live-trace-dir form (`atx trace <dir>`) reassembles the same
  requests from the mirrored ``spans_*.jsonl``.

Usage: python trace_smoke.py
"""

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

REQUESTS = 16
RATE = 50.0  # Poisson arrivals/sec — ~0.3 s of arrival spread


def _requests(rng_seed: int = 0):
    import numpy as np

    from accelerate_tpu import serving

    rng = np.random.RandomState(rng_seed)
    arrivals = np.cumsum(rng.exponential(1.0 / RATE, REQUESTS))
    return [
        serving.Request(
            prompt=rng.randint(0, 61, (int(rng.randint(3, 24)),)).astype(np.int32),
            max_new_tokens=int(rng.choice((3, 6))),
            rid=i,
            seed=i,
            arrival=float(arrivals[i]),
        )
        for i in range(REQUESTS)
    ]


def _serve_once(params, cfg):
    import jax  # noqa: F401  (imported for side effects before llama use)

    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import Router

    def _apply(p, t, c):
        return llama.forward_with_cache(p, t, c, cfg)

    def _init_cache(b, m):
        return llama.init_cache(cfg, b, m)

    def _engine():
        return serving.Engine(
            _apply, _init_cache, params, GenerationConfig(),
            slots=2, buckets=(8,), max_len=96, prefix_cache=True,
        )

    # Same Poisson trace each run: requests are rebuilt because the router
    # rewrites per-request fields (stream wrapper, submitted_at).
    with Router([_engine(), _engine()], queue_depth=64) as router:
        completions = router.serve(_requests(), realtime=True)
    assert len(completions) == REQUESTS, router.metrics()
    return {c.rid: [int(t) for t in c.tokens[: c.n_new]] for c in completions}


def _atx_trace(argv) -> tuple[int, str, str]:
    from accelerate_tpu.commands.cli import main

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = main(["trace"] + argv)
    return rc, out.getvalue(), err.getvalue()


def main() -> int:
    argparse.ArgumentParser(description=__doc__).parse_args()
    import jax

    from accelerate_tpu.models import llama
    from accelerate_tpu.telemetry import flight, spans
    from accelerate_tpu.utils.environment import patch_environment

    cfg = llama.LlamaConfig.tiny(
        vocab_size=61, max_seq_len=256, num_heads=4, num_kv_heads=2
    )
    params = llama.init(jax.random.PRNGKey(1), cfg)

    with patch_environment(ATX_TRACE_REQUESTS="0"):
        baseline = _serve_once(params, cfg)
    off = [r["name"] for r in flight.recorder().last()]
    assert off == ["request"] * REQUESTS, (
        f"tracing off must record one `request` a completion and nothing finer: {off}"
    )

    with tempfile.TemporaryDirectory() as td:
        trace_dir = os.path.join(td, "trace")
        os.makedirs(trace_dir)
        with patch_environment(
            ATX_TRACE_REQUESTS="1", ATX_POSTMORTEM_DIR=os.path.join(td, "pm")
        ):
            flight.reset_recorder()
            spans.start_trace_log(os.path.join(trace_dir, "spans_0.jsonl"))
            try:
                traced = _serve_once(params, cfg)
            finally:
                spans.stop_trace_log()
            bundle = flight.dump_postmortem("trace_smoke")
        assert bundle, "postmortem bundle was not written"

        # -- bit-identity: tracing must not perturb a single token --------
        assert set(traced) == set(baseline) == set(range(REQUESTS))
        for rid in baseline:
            assert traced[rid] == baseline[rid], (
                f"rid {rid}: tracing changed tokens "
                f"{baseline[rid]} -> {traced[rid]}"
            )

        # -- bundle renders + phase attribution sums to e2e within 5% -----
        rc, out, err = _atx_trace([bundle, "--check", "0.05", "--limit", "4"])
        assert rc == 0, f"atx trace --check failed ({rc}):\n{out}\n{err}"
        assert "rid 0" in out and "tail-latency attribution" in out, out
        sys.stderr.write(out)

        rc, out, _ = _atx_trace([bundle, "--json"])
        assert rc == 0
        report = json.loads(out)
        assert len(report["requests"]) == REQUESTS
        shares = {r["phase"]: r["share"] for r in report["attribution"]}
        assert set(shares) == {"queue", "prefill", "decode", "emit"}
        assert abs(sum(shares.values()) - 1.0) < 0.02, shares

        # -- live trace dir (the JSONL mirror) tells the same story -------
        rc, out, err = _atx_trace([trace_dir, "--check", "0.05", "--json"])
        assert rc == 0, f"atx trace on the trace dir failed ({rc}): {err}"
        assert len(json.loads(out)["requests"]) == REQUESTS

    print(
        json.dumps(
            {
                "trace_smoke": "ok",
                "requests": REQUESTS,
                "bit_identical": True,
                "spans_recorded": flight.recorder().total,
                "phase_shares": shares,
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
