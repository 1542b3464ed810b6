"""The correctness part of a cell alone, and the rehearsals without a chip.

    python3 -m benchmarks.check_correct --workload <name> --seeds 0-15

runs, on the chip and in one process (one compile), only the set-up
comparison with the reference for each seed, and prints each seed's
distances and the worst. The tolerances in `correctness.py` are set from
this output (PERF.md has it for every shipped cell). ``--what-if <seeds>``
adds, for those seeds, what the comparison would have read had the program
skipped a layer or computed with fp8 weights: the reference is given the
altered weights, and the distance between the two is the same either way.

    python3 -m benchmarks.check_correct --workload <name> --rehearse [--trace 1]

runs the whole cell on any backend at tiny widths (`JAX_PLATFORMS=cpu`;
add ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for the four-chip
cell): control flow, file lookup and the last line's shape. Its output says
it is a rehearsal, and none of its numbers is a device metric.

    python3 -m benchmarks.check_correct --workload <name> --compile

compiles a train cell's step at its real size for a described (not
attached) v5e and prints the compiler's `memory_analysis()`: what does not
fit or does not lower is refused here and costs no chip time.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import correctness, harness  # noqa: E402

# Tiny widths for the rehearsal: every ratio that steers control flow is
# kept (GQA groups, several prefill buckets, a prompt of several chunks).
TINY_CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "vocab_size": 512,
}


def shrink(cell: dict, config: dict) -> None:
    config.update(TINY_CONFIG)
    config["num_hidden_layers"] = min(config["num_hidden_layers"], 2)
    traffic = cell["traffic"]
    if "recipe" in cell:
        traffic.update(seq_len=128, sequences=16)
        cell["recipe"]["loss_chunk_size"] = 64
        cell["warm_steps"] = 1
    else:
        cell["engine"].update(slots=4, max_len=160, buckets=[16, 32])
        cell["probe"] = {"prompt_tokens": [12, 30, 70], "new_tokens": 8}
        small = {"dist": "uniform", "min": 8, "max": 60}
        traffic.update(prompt_tokens=small, new_tokens={"dist": "uniform", "min": 4, "max": 12},
                       warm_seconds=0.5, drain_seconds=30.0)
        if "rate" in traffic:
            traffic.update(rate=4.0, cool_seconds=5.0)
        else:
            traffic.update(clients=3, requests_per_client=400)
    cell["trace"]["seconds"] = 0.5


# At tiny widths rounding is a larger share of everything. Only the
# rehearsal is judged by these, and it shows no number.
REHEARSAL_TOLERANCES = {
    "train_loss_atol": 0.1, "train_grad_norm_rtol": 0.1, "train_update_sign_flip_max": 0.2,
    "serve_logit_short_rtol": 0.5, "serve_exact_argmax_min": 0.3,
}


def parse_seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


# ---------------------------------------------------------------- what-ifs
def skip_layer(arch, get_layer):
    """The reference without its middle layer."""
    gone = arch.num_hidden_layers // 2
    fewer = dataclasses.replace(arch, num_hidden_layers=arch.num_hidden_layers - 1)
    return fewer, (lambda i: get_layer(i if i < gone else i + 1))


def fp8_weights(arch, get_layer):
    """The reference with every matrix rounded to float8_e4m3 first."""
    import jax
    import jax.numpy as jnp

    def rounded(i):
        return jax.tree.map(
            lambda w: w.astype(jnp.float8_e4m3fn).astype(jnp.float32) if w.ndim == 2 else w,
            get_layer(i),
        )

    return arch, rounded


WHAT_IFS = {"skip_layer": skip_layer, "fp8_weights": fp8_weights}


def _altered_reference(cell, alter) -> dict:
    """A train cell's reference on altered weights, its per-layer norm
    gradients padded back to the program's layers where one was skipped
    (zeros: those weights count as wrong)."""
    import numpy as np

    out = cell.reference(alter)
    layers = cell.ctx.config["num_hidden_layers"]
    for name, grad in out["norm_grads"].items():
        if grad.ndim == 2 and grad.shape[0] == layers - 1:
            out["norm_grads"][name] = np.insert(grad, layers // 2, 0.0, axis=0)
    return out


def sweep(name: str, seeds: list[int], what_if: list[int]) -> int:
    ctx = harness.prepare(name, seeds[0])
    cell = harness.build_cell(ctx)
    cell.build()
    system = ctx.traffic_module.SYSTEM
    train = system == "trainer"
    worst, all_ok = {}, True
    for n, seed in enumerate(seeds):
        if n:
            cell.reseed(seed)
        altered = {}
        if train and seed in what_if:
            # before the step donates the state: the same step, other references
            altered = {label: _altered_reference(cell, alter) for label, alter in WHAT_IFS.items()}
        d = cell.probe()
        ok = correctness.judge(system, d)
        all_ok &= ok
        harness.say("seed", seed=seed, correct=ok, **d)
        for k, v in d.items():
            if isinstance(v, float) and k.endswith(("diff", "short_of_top", "share")):
                worst[k] = (min if k == "exact_argmax_share" else max)(worst.get(k, v), v)
        if not train and seed in what_if:
            altered = dict(WHAT_IFS)  # the tokens already served, judged by altered references
        for label, other in altered.items():
            d = correctness.train_distances(cell.first_step, other) if train else cell.probe(other)
            harness.say("what_if", seed=seed, what=label, refused=not correctness.judge(system, d),
                        **{k: v for k, v in d.items() if k.endswith(("diff", "short_of_top", "share"))})
    harness.say("sweep", workload=name, seeds=len(seeds), all_correct=all_ok, worst=worst,
                tolerances=correctness.TOLERANCES,
                device_kind=ctx.devices[0].device_kind, platform=ctx.devices[0].platform)
    return 0 if all_ok else 1


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="0-15")
    parser.add_argument("--what-if", default="", metavar="SEEDS")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--compile", action="store_true")
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if args.compile:
        from benchmarks import rehearse_compile

        return rehearse_compile.main(args.workload)
    if args.rehearse:
        line = harness.run_cell(
            args.workload, parse_seeds(args.seeds)[0], args.seconds, bool(args.trace), _T_START,
            shrink=shrink, tolerances=REHEARSAL_TOLERANCES,
        )
        print("REHEARSAL " + json.dumps(line), flush=True)
        return 0
    return sweep(args.workload, parse_seeds(args.seeds), parse_seeds(args.what_if) if args.what_if else [])


if __name__ == "__main__":
    sys.exit(main())
