"""Find the rate an open-loop serve cell sustains: the cell built once, then
at each of ``--rates`` (requests/s) one window of ``--seconds`` for each of
``--schedule-seeds`` through the benchmark's own driver
(`benchmarks/systems/engine.py:EngineCell.window`, the cell's lengths, its
trace drawn from that seed at that rate). A window holds the rate if the
mean time to first token of its second half is within 20% of the first
half's and no request was submitted later than ``--late-ms`` after it was
due; a rate is sustained if every one of its windows holds it. One trace
puts its long prompts in the same half at every rate: hence the seeds.
Prints one line a window and one a rate; on the chip only.

    python3 perf/serve_rate_sweep.py --workload olmohybrid-serve-chat --rates 4.5,5,5.5 --schedule-seeds 34,35
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from benchmarks import harness
    from benchmarks.stats import percentile

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True, help="comma-separated requests/s")
    parser.add_argument("--seconds", type=float, default=60.0)
    parser.add_argument("--seed", type=int, default=1234)
    parser.add_argument("--schedule-seeds", default="", help="comma-separated; the cell's own when empty")
    parser.add_argument(
        "--late-ms", type=float, default=40.0,
        help="the driver submits between engine steps: a 256-row chunk, the decode step queued "
        "ahead of it and their two host gaps (20.5 + 14.6 + 2 x 2.2 ms in the hybrid cell)",
    )
    args = parser.parse_args()
    ctx = harness.prepare(args.workload, args.seed)
    cell = harness.build_cell(ctx)
    cell.build()
    cell.serve_probe()  # compiles every program the traffic uses
    seeds = [int(x) for x in args.schedule_seeds.split(",") if x] or [cell.traffic["schedule_seed"]]
    for rate in (float(r) for r in args.rates.split(",")):
        held = []
        for schedule_seed in seeds:
            cell.engine.abort_inflight()  # the last window's cool-down requests
            cell.traffic.update(rate=rate, schedule_seed=schedule_seed)
            tracer = harness.Tracer(False, 0.0, 0.0, "", ctx.spans)
            o = cell.window(args.seconds, tracer)
            ttft, itl, late = (o["samples"][k] for k in ("ttft_ms", "itl_ms", "late_ms"))
            half = len(ttft) // 2  # in the order the requests were due
            first, second = sum(ttft[:half]) / max(half, 1), sum(ttft[half:]) / max(len(ttft) - half, 1)
            c = o["counters"]
            held.append(bool(ttft and not o["failed"] and second <= 1.2 * first and max(late) <= args.late_ms))
            print("[sweep] " + json.dumps({
                "rate": rate, "schedule_seed": schedule_seed, "requests": o["attempted"], "failed": o["failed"],
                "ttft_mean_first_half_ms": first, "ttft_mean_second_half_ms": second,
                "ttft_p95_ms": percentile(ttft, 95) if ttft else None,
                "itl_p50_ms": percentile(itl, 50) if itl else None,
                "itl_p95_ms": percentile(itl, 95) if itl else None,
                "late_max_ms": max(late) if late else None,
                "held": held[-1],
                "slots_busy_share": c["decode_slot_steps"] / max(c["decode_steps"] * c["slots"], 1),
                "decode_steps": c["decode_steps"], "prefill_chunks": c["prefill_chunks"],
                "drain_seconds": o["samples"]["drain_seconds"],
            }), flush=True)
        print("[sweep] " + json.dumps({"rate": rate, "windows": len(held), "sustained": all(held)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
