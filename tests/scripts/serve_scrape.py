"""Scrape check for `atx serve --metrics-port` (Makefile smoke-telemetry lane).

Runs the serving benchmark in-process with the Prometheus endpoint armed on
an ephemeral port, scrapes ``/metrics`` (and ``/metrics.json`` +
``/healthz``) live mid-trace, then cross-checks the final registry render —
byte-for-byte what a post-trace scrape serves — against the JSON summary the
command printed: the ``serve_*`` histogram series and the JSON line must
describe the same trace (docs/observability.md acceptance).

Usage: python serve_scrape.py
"""

import argparse
import contextlib
import io
import json
import os
import re
import sys
import threading
import time
import urllib.request

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

REQUESTS = 16


def parse_prometheus(text: str) -> dict:
    """Tiny text-format 0.0.4 parser: {'name': [(labels_dict, value)]},
    plus {'#types': {name: type}} for the TYPE lines."""
    series: dict = {"#types": {}}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(None, 3)
            series["#types"][name] = kind
            continue
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$", line)
        assert m, f"unparseable exposition line: {line!r}"
        name, raw_labels, raw_value = m.groups()
        labels = {}
        if raw_labels:
            for part in re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"', raw_labels):
                labels[part[0]] = part[1]
        series.setdefault(name, []).append((labels, float(raw_value)))
    return series


def bucket_quantile(buckets: list, q: float) -> float:
    """Same linear interpolation the registry uses, reimplemented from the
    exposition text alone — the round-trip proof."""
    entries = sorted(
        ((float("inf") if le == "+Inf" else float(le)), c) for le, c in buckets
    )
    total = entries[-1][1]
    assert total > 0
    rank = q * total
    prev_bound, prev_cum = 0.0, 0.0
    for bound, cum in entries:
        if cum >= rank:
            if bound == float("inf"):
                return prev_bound
            span = cum - prev_cum
            frac = (rank - prev_cum) / span if span else 1.0
            return prev_bound + (bound - prev_bound) * frac
        prev_bound, prev_cum = bound, cum
    return prev_bound


def main() -> int:
    from accelerate_tpu.commands import serve as serve_cmd

    parser = argparse.ArgumentParser()
    serve_cmd.register(parser.add_subparsers())
    args = parser.parse_args(
        [
            "serve",
            "--model",
            "llama-tiny",
            "--requests",
            str(REQUESTS),
            "--rate",
            "64",
            "--slots",
            "4",
            "--metrics-port",
            "0",
        ]
    )

    stderr, stdout = io.StringIO(), io.StringIO()
    live: dict = {}

    def scrape_live() -> None:
        # Poll stderr for the bound URL, then take one mid-trace scrape of
        # every route. Failures land in `live` and fail the check below.
        for _ in range(600):
            m = re.search(r"http://[\d.]+:\d+", stderr.getvalue())
            if m:
                base = m.group(0)
                try:
                    live["prom"] = (
                        urllib.request.urlopen(base + "/metrics", timeout=5)
                        .read()
                        .decode()
                    )
                    live["json"] = json.loads(
                        urllib.request.urlopen(base + "/metrics.json", timeout=5)
                        .read()
                        .decode()
                    )
                    live["health"] = (
                        urllib.request.urlopen(base + "/healthz", timeout=5)
                        .read()
                        .decode()
                    )
                except Exception as e:  # surfaces as a missing key below
                    live["error"] = f"{type(e).__name__}: {e}"
                return
            time.sleep(0.02)
        live["error"] = "metrics URL never appeared on stderr"

    scraper = threading.Thread(target=scrape_live)
    scraper.start()
    with contextlib.redirect_stderr(stderr), contextlib.redirect_stdout(stdout):
        rc = args.func(args)
    scraper.join()
    assert rc == 0, f"atx serve exited {rc}"
    summary = json.loads(stdout.getvalue())

    # -- live mid-trace scrape worked and was parseable --------------------
    assert "error" not in live, f"live scrape failed: {live.get('error')}"
    mid = parse_prometheus(live["prom"])
    assert live["health"].strip() == "ok"
    assert any(e["name"] == "serve_admitted" for e in live["json"]["metrics"])
    assert mid["#types"].get("serve_e2e_ms") == "histogram"
    assert sum(v for _, v in mid.get("serve_admitted", [])) >= 1

    # -- final render (what a post-trace scrape serves) vs the JSON line ---
    from accelerate_tpu import telemetry

    final = parse_prometheus(telemetry.render_prometheus())
    count = sum(v for _, v in final["serve_e2e_ms_count"])
    assert count == summary["serve_requests"] == REQUESTS, (
        count,
        summary["serve_requests"],
    )
    admitted = sum(v for _, v in final["serve_admitted"])
    completed = sum(v for _, v in final["serve_completed"])
    assert admitted == completed == REQUESTS, (admitted, completed)

    for hist, field in (("serve_e2e_ms", "serve_p50_ms"), ("serve_ttft_ms", "serve_ttft_p50_ms")):
        buckets = [
            (labels["le"], value)
            for labels, value in final[f"{hist}_bucket"]
        ]
        cums = [v for _, v in sorted(
            ((float("inf") if le == "+Inf" else float(le)), c) for le, c in buckets
        )]
        assert all(a <= b for a, b in zip(cums, cums[1:])), "buckets not cumulative"
        assert cums[-1] == count, "+Inf bucket != count"
        # The JSON line is the exact median of the samples the histogram
        # holds in buckets: it lies in the bucket the estimate falls in.
        est = bucket_quantile(buckets, 0.50)
        bounds = [0.0] + sorted(float(le) for le, _ in buckets if le != "+Inf")
        lo = max(b for b in bounds if b <= est)
        hi = min((b for b in bounds if b >= est and b > lo), default=float("inf"))
        got = summary[field]
        assert lo - 0.05 <= got <= hi + 0.05, (hist, lo, got, hi)

    print(
        json.dumps(
            {
                "serve_scrape": "ok",
                "requests": REQUESTS,
                "p50_ms": summary["serve_p50_ms"],
                "mid_trace_admitted": sum(v for _, v in mid.get("serve_admitted", [])),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
