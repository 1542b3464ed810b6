"""A serve cell of the Olmo-Hybrid family: `serving.Engine` on bf16 weights,
driven as `systems/engine.py` drives it (the window, the records and the
invariants are `EngineCell`'s). What touches the model is here: the build
(bf16 parameters from the seed, the family's `forward_with_cache` and
`init_cache`), the parameter mapping for `reference/olmo_hybrid.py`, and the
probe with tolerances of its own.

`correctness.judge_serve`'s 0.12 / 0.78 were set for int8 weights and stay
the outer check; bf16 against float32 is tighter, so this system judges by
`TOLERANCES` below (the mean shortfall over every served token, the
exact-argmax share, and how far the float32 states the probe left in its
slots lie from the reference's) and reports the verdict as an invariant of
the window (``probe_within_bf16_tolerance``), which `harness.run_cell` folds
into ``correct``.

    python3 -m benchmarks.systems.engine_olmo_hybrid --seeds 100-115 --what-if 100-115

walks the seeds in one process on the chip and, for the ``--what-if`` seeds,
judges the tokens already served, and the states already read, by references
that differ in one thing each (`WHAT_IFS`): every one of them must be refused
on every seed. ``--rehearse`` runs the whole cell at tiny widths on any
backend.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any

import numpy as np

from .. import correctness, program
from .engine import EngineCell
from .engine_smallthinker import within as logits_within  # the same judgement of the logits, this family's limits

# Set from the 16-seed sweep on the chip (seeds 100-115, three what-ifs on every
# seed and the others on four; my chip runs, PR 34; PERF.md section 4 has every
# number): bf16 weights and activations against the float32 reference, 224
# served tokens a seed (seven prompts x 32).
# - The mean, over every served token, of how far its reference logit falls short
#   of the reference's largest (as a share of it) is the sharp check of the
#   logits: as shipped 5.9e-5 to 3.2e-4; against fp8 weights 2.1e-2 to 2.8e-2; the
#   weakest structural what-if (beta not doubled) 5.8e-2 to 7.4e-2. The limit
#   2.7e-3 is their geometric middle: 8 times of room on either side.
# - The share of tokens that are the reference's exact argmax: as shipped 0.924 to
#   0.991, fp8 0.478 to 0.607, no structural what-if over 0.43. The floor lies
#   between, 0.12 under the worst shipped seed and 0.19 over the best fp8 one.
# - The worst single shortfall is a worst-of-224 with a heavy tail (as shipped up
#   to 0.016, fp8 from 0.149): no limit of this system's own; `judge_serve`'s 0.12
#   stays the outer one.
# - The rule's state is float32 by the configuration, and the logits cannot tell:
#   against a reference that rounds its state to bf16 after every token they read
#   the shipped range (7.6e-5 to 3.4e-4). So the states the probe left in its
#   slots are read back and held against the reference's own (`state_distances`)
#   in the first linear layer, whose inputs are the embedding's rows; by the
#   twelfth the bf16 activations' rounding reads 2.9e-2 to 3.2e-2 and drowns a
#   state's (3.0e-2 to 3.5e-2 against the bf16-state reference). After the prompt
#   (the chunkwise form) 2.9e-4 to 4.3e-4 as shipped, 5.0e-3 to 8.9e-3 against the
#   bf16-state reference, and 3.0e-3 to 3.3e-3 with `gdn_chunk`'s products at the
#   MXU's default precision (one bf16 pass over the state): the limit 1.5e-3, the
#   geometric middle, leaves 3.4 times of room on either side and refuses that
#   kernel too. After the probe's decode steps (the recurrent form; the last
#   tokens' convolution inputs come back from the cache's bf16 tail) 2.1e-3 to
#   2.3e-3 as shipped, 5.6e-3 to 9.5e-3 against the bf16-state reference: the
#   limit 3.6e-3, the geometric middle, 1.6 times of room on either side of a
#   shipped reading that spreads by 4% over the seeds.
TOLERANCES = {
    "serve_mean_short_max": 2.7e-3, "serve_exact_argmax_min": 0.80,
    "state_rel_after_prefill_max": 1.5e-3, "state_rel_after_decode_max": 3.6e-3,
}
# At tiny widths on a CPU rounding is a larger share of everything.
REHEARSAL_TOLERANCES = {
    "serve_mean_short_max": 0.2, "serve_exact_argmax_min": 0.3,
    "state_rel_after_prefill_max": 0.05, "state_rel_after_decode_max": 0.05,
}
# The reference's rows are right-padded to a multiple of this: the probe's
# seven prompts make three shapes, each a program for each kind of layer.
PAD_TO = 512


def model_config(config: dict, max_len: int):
    """The published keys as the program's `OlmoHybridConfig`, through the
    mapping every caller uses (`models.hf.from_hf_config`)."""
    from accelerate_tpu.models.hf import from_hf_config

    family, mcfg = from_hf_config({**config, "model_type": config["program"]["model_type"]})
    if family != "olmo_hybrid":
        raise ValueError(f"this system runs the olmo_hybrid family, not {family!r}")
    return dataclasses.replace(mcfg, max_seq_len=max_len)


# The family's init leaves mechanisms without weight in the logits, and a
# comparison cannot refuse what it cannot see:
# - norm weights start at 1 (a checkpoint's differ): all six kinds are drawn,
#   uniform on 1 +- NORM_SPREAD;
# - with unit-variance q and k (the QK-norm makes them so) the softmax over a
#   thousand rows is nearly flat, every row sees the mean of the values, and
#   neither a dropped QK-norm nor an added rotary term shows: the q norm's
#   weight is drawn Q_SHARPNESS times larger, so that attention picks rows;
# - the init's decays sit near 1 (alpha the same for every token), and at the
#   init's width `a = W_a x` and `b = W_b x` have the residual stream's spread,
#   4 to 7 here: beta is 0 or 2 and alpha 0 or 1. `A_log` is drawn log-uniform
#   on DECAY_RANGE, `dt_bias` normal, and `W_a` / `W_b` AB_SCALE times as wide
#   (a spread of 1.2 to 2), so that alpha spreads over (0, 1) and beta over
#   (0, 2) token by token;
# - the embedding is drawn EMBED_SCALE times larger, so that the residual stream
#   carries the token (as `engine_smallthinker.py` found for its family).
NORM_SPREAD = 0.75
Q_SHARPNESS = 3.0
DECAY_RANGE = (0.02, 1.0)
AB_SCALE = 0.3
EMBED_SCALE = 4.0


def init_params(seed: int, mcfg, device):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import olmo_hybrid

    def build(key):
        k_init, k_norms, k_decay = jax.random.split(key, 3)
        params = olmo_hybrid.init(k_init, mcfg, jnp.bfloat16)
        lin, full = params["linear"], params["full"]
        keys = iter(jax.random.split(k_norms, 12))
        draw = lambda like, scale=1.0: (
            scale * (1.0 + jax.random.uniform(next(keys), like.shape, minval=-NORM_SPREAD, maxval=NORM_SPREAD))
            - 1.0
        ).astype(like.dtype)  # stored as g - 1
        for blocks in (lin, full):
            blocks["mixer_norm"] = draw(blocks["mixer_norm"])
            blocks["mlp_norm"] = draw(blocks["mlp_norm"])
        lin["out_norm"] = draw(lin["out_norm"])
        full["q_norm"] = draw(full["q_norm"], Q_SHARPNESS)
        full["k_norm"] = draw(full["k_norm"])
        params["final_norm"] = draw(params["final_norm"])
        k_a, k_dt = jax.random.split(k_decay)
        lo, hi = DECAY_RANGE
        lin["A_log"] = jax.random.uniform(k_a, lin["A_log"].shape, minval=math.log(lo), maxval=math.log(hi))
        lin["dt_bias"] = jax.random.normal(k_dt, lin["dt_bias"].shape)
        lin["w_ab"] = (lin["w_ab"] * AB_SCALE).astype(jnp.bfloat16)
        params["embed"] = (params["embed"] * EMBED_SCALE).astype(jnp.bfloat16)
        return params

    with jax.default_device(device):
        return jax.jit(build)(jax.random.PRNGKey(program.jax_seed(seed)))


def build_engine(config: dict, cell: dict, seed: int, device):
    """`serving.Engine` as `atx serve` builds it, on bf16 weights made on
    ``device`` from the seed."""
    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import olmo_hybrid

    deploy = cell["engine"]
    if deploy["weights"] != "bf16":
        raise ValueError(f"unknown weight format {deploy['weights']!r}")
    mcfg = model_config(config, deploy["max_len"])
    params = init_params(seed, mcfg, device)
    engine = serving.Engine(
        lambda p, t, c: olmo_hybrid.forward_with_cache(p, t, c, mcfg),
        lambda batch, max_len: olmo_hybrid.init_cache(mcfg, batch, max_len),
        params,
        GenerationConfig(),  # greedy, no EOS: a request runs to its budget
        slots=deploy["slots"],
        buckets=tuple(deploy["buckets"]),
        max_len=deploy["max_len"],
        prefill_interleave=deploy["prefill_interleave"],
        decode_block=deploy["decode_block"],
        prefix_cache=deploy["prefix_cache"],
    )
    return engine, params, mcfg


def reference_weights(params, mcfg):
    """`(get_layer, top)` for `reference.olmo_hybrid.Decoder` from the
    program's parameter tree: the fused projections split, heads flattened
    into the output axis, the convolution turned ``(channels, 4)``, norm
    scales stored as ``g - 1`` turned back into ``g``, everything in
    float32. One layer is taken at a time; layer ``i`` is the ``i``-th of
    its kind's stack in layer order."""
    import jax
    import jax.numpy as jnp

    D = mcfg.d_model
    H, dk, dv = mcfg.linear_heads, mcfg.linear_key_dim, mcfg.linear_value_dim
    f32 = lambda a: a.astype(jnp.float32)
    cuts = [H * dk, 2 * H * dk]

    def shared(b):
        return {
            "mixer_norm": 1.0 + f32(b["mixer_norm"]),
            "mlp_norm": 1.0 + f32(b["mlp_norm"]),
            "gate_proj": f32(b["mlp"]["w_gate"]),
            "up_proj": f32(b["mlp"]["w_up"]),
            "down_proj": f32(b["mlp"]["w_down"]),
        }

    def linear(blocks, i):
        b = jax.tree.map(lambda a: a[i], blocks)
        q, k, v = jnp.split(f32(b["w_qkv"]), cuts, axis=1)
        cq, ck, cv = jnp.split(f32(b["conv"]).T, cuts, axis=0)
        return {
            "q_proj": q, "k_proj": k, "v_proj": v,
            "q_conv": cq, "k_conv": ck, "v_conv": cv,
            "g_proj": f32(b["w_gate"]),
            "a_proj": f32(b["w_ab"])[:, :H], "b_proj": f32(b["w_ab"])[:, H:],
            "A_log": f32(b["A_log"]), "dt_bias": f32(b["dt_bias"]),
            "o_norm": 1.0 + f32(b["out_norm"]),
            "o_proj": f32(b["w_out"]),
            **shared(b),
        }

    def full(blocks, i):
        b = jax.tree.map(lambda a: a[i], blocks)
        return {
            "q_proj": f32(b["attn"]["wq"]).reshape(D, -1),
            "k_proj": f32(b["attn"]["wk"]).reshape(D, -1),
            "v_proj": f32(b["attn"]["wv"]).reshape(D, -1),
            "o_proj": f32(b["attn"]["wo"]).reshape(-1, D),
            "q_norm": 1.0 + f32(b["q_norm"]),
            "k_norm": 1.0 + f32(b["k_norm"]),
            **shared(b),
        }

    fns = {"linear_attention": jax.jit(linear), "full_attention": jax.jit(full)}
    stacks = {"linear_attention": params["linear"], "full_attention": params["full"]}
    kinds = mcfg.kinds
    index = [sum(k == kinds[i] for k in kinds[:i]) for i in range(len(kinds))]
    top = {
        "embed_tokens": params["embed"],
        "lm_head": params["lm_head"],
        "norm": 1.0 + f32(params["final_norm"]),
    }
    return (lambda i: fns[kinds[i]](stacks[kinds[i]], index[i])), top


def state_distances(served: list[np.ndarray], reference: list[np.ndarray]) -> dict[str, Any]:
    """How far the states the engine left in the probe's slots lie from the
    reference's, layer by layer: the norm of the difference over the norm of
    the reference's, pooled over the prompts. ``served[r]`` and
    ``reference[r]`` are ``(linear layers, 2, H, d_k, d_v)``: after the
    prompt, and after the probe's last decode step. A reference with fewer
    layers (a what-if) is held against the layers it has."""
    layers = min(len(served[0]), len(reference[0]))
    diff, norm = np.zeros((layers, 2)), np.zeros((layers, 2))
    for mine, ref in zip(served, reference):
        mine, ref = (a[:layers].astype(np.float64).reshape(layers, 2, -1) for a in (mine, ref))
        diff += np.square(mine - ref).sum(-1)
        norm += np.square(ref).sum(-1)
    rel = np.sqrt(diff / norm)
    return {
        "state_rel_after_prefill": rel[:, 0].tolist(),
        "state_rel_after_decode": rel[:, 1].tolist(),
    }


def within(d: dict[str, Any], tol: dict[str, float]) -> bool:
    """The logits by `engine_smallthinker.within`'s rule, and the first linear
    layer's state within its limit after the prompt and after the decode
    steps (a state that is not finite is not within it)."""
    return bool(
        logits_within(d, tol)
        and d["state_rel_after_prefill"][0] <= tol["state_rel_after_prefill_max"]
        and d["state_rel_after_decode"][0] <= tol["state_rel_after_decode_max"]
    )


# ---------------------------------------------------------------- what-ifs
# Each takes (arch, get_layer) and returns them altered in one thing.
def _replace(**changes):
    return lambda arch, get_layer: (dataclasses.replace(arch, **changes), get_layer)


def _skip_layer(arch, get_layer):
    """Without the middle layer (the layout loses its entry too)."""
    gone = arch.num_hidden_layers // 2
    fewer = dataclasses.replace(
        arch, num_hidden_layers=arch.num_hidden_layers - 1,
        layer_types=arch.layer_types[:gone] + arch.layer_types[gone + 1 :],
    )
    return fewer, (lambda i: get_layer(i if i < gone else i + 1))


def _fp8_weights(arch, get_layer):
    """Every matrix rounded to float8_e4m3 first (the nearest precision
    below the bf16 the configuration states)."""
    import jax
    import jax.numpy as jnp

    def rounded(i):
        return jax.tree.map(
            lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype) if w.ndim >= 2 else w,
            get_layer(i),
        )

    return arch, rounded


WHAT_IFS = {
    "beta_not_doubled": _replace(linear_allow_neg_eigval=False),
    "no_decay": _replace(decay=False),
    "no_conv": _replace(conv=False),
    "qk_not_normalised": _replace(qk_l2norm=False),
    "bf16_state": _replace(state_dtype="bfloat16"),
    "no_qk_norm": _replace(qk_norm=False),
    "rope_on_full_layers": _replace(rope_full_layers=True),
    "pre_norm_block": _replace(block_norm="pre"),
    "skip_layer": _skip_layer,
    "fp8_weights": _fp8_weights,
}


class OlmoHybridCell(EngineCell):
    # ---------------------------------------------------------------- set-up
    def build(self) -> None:
        # First, and before any allocation: a program without the family
        # (the parent of the PR that added it) fails here, in seconds.
        from accelerate_tpu.models import olmo_hybrid  # noqa: F401

        import jax

        ctx = self.ctx
        self.engine, self.params, self.mcfg = build_engine(
            ctx.config, ctx.cell, ctx.seed, ctx.devices[0]
        )
        jax.block_until_ready(self.params)
        self.vocab = ctx.config["vocab_size"]
        self.probe_seed = ctx.seed
        self.tolerances = (
            TOLERANCES if ctx.devices[0].platform == "tpu" else REHEARSAL_TOLERANCES
        )
        self.probe_ok = False

    def reseed(self, seed: int) -> None:
        self.engine.params = self.params = None
        self.params = init_params(seed, self.mcfg, self.ctx.devices[0])
        self.engine.params = self.params
        self.engine.prefill_signatures.clear()
        self.probe_seed = seed

    def serve_probe(self):
        """`EngineCell.serve_probe`, and the rule's states the probe left in
        its slots, read back: after the whole probe (the prompt in chunks and
        then all but the last served token a decode step each), and from a
        second pass of the same prompts with one new token each, after the
        prompt alone (the chunkwise form, the state handed from chunk to
        chunk)."""
        prompts, served = super().serve_probe()
        engine = self.engine
        after_decode = [engine.slot_state(c.slot)["state_gdn"] for c in served]
        for prompt in prompts:
            engine.submit(prompt, max_new_tokens=1)
        again = sorted(engine.run_until_idle(), key=lambda c: c.rid)
        after_prefill = [engine.slot_state(c.slot)["state_gdn"] for c in again]
        # (linear layers, 2, H, d_k, d_v) a prompt, as the reference hands them back
        self._probe_states = [np.stack(pair, axis=1) for pair in zip(after_prefill, after_decode)]
        return prompts, served

    def probe(self, what_if=None) -> dict[str, Any]:
        """Serve the probe and judge it by this family's reference: a full
        forward over prompt + served tokens, one row at a time, on the
        logits and on the rule's states. With ``what_if`` the tokens already
        served and the states already read are judged again, by an altered
        reference."""
        from ..reference.olmo_hybrid import Arch, Decoder

        t0 = time.perf_counter()
        if what_if is None:
            self._served_probe = self.serve_probe()
        prompts, served = self._served_probe
        t1 = time.perf_counter()
        n_new = self.ctx.cell["probe"]["new_tokens"]
        bad = [c.rid for c in served if c.n_new != n_new or c.finish_reason != "length"]
        arch = Arch.from_config(self.ctx.config)
        get_layer, top = reference_weights(self.params, self.mcfg)
        if what_if is not None:
            arch, get_layer = what_if(arch, get_layer)
        by_width: dict[int, list[int]] = {}
        for r, prompt in enumerate(prompts):
            by_width.setdefault(-(-(len(prompt) + n_new) // PAD_TO) * PAD_TO, []).append(r)
        decoder = Decoder.of(arch)
        logits: list = [None] * len(prompts)
        states: list = [None] * len(prompts)
        for width, members in by_width.items():
            rows = np.zeros((len(members), width), np.int32)
            positions, states_after = [], []
            for k, r in enumerate(members):
                n = len(prompts[r])
                rows[k, :n] = prompts[r]
                rows[k, n : n + n_new] = served[r].tokens[:n_new]
                positions.append(slice(n - 1, n + n_new - 1))  # logits after served[:i] predict served[i]
                states_after.append((n, n + n_new - 1))  # the last served token never went in
            out = decoder.forward(get_layer, top, rows, positions, states_after)
            for r, l, s in zip(members, *out):
                logits[r], states[r] = l, s
        gaps = [correctness.short_of_top(l, np.asarray(c.tokens[:n_new])) for l, c in zip(logits, served)]
        distances = correctness.serve_distances(gaps)
        # A mean over every position separates more sharply than the worst of them.
        distances["mean_short_of_top"] = float(np.mean(np.concatenate(gaps)))
        distances.update(state_distances(self._probe_states, states))
        distances["wrong_length"] = bad
        distances["probe_serve_s"] = t1 - t0
        distances["reference_s"] = time.perf_counter() - t1
        if what_if is None:
            self.probe_ok = within(distances, self.tolerances)
            distances["within_bf16_tolerance"] = self.probe_ok
            distances["bf16_tolerances"] = dict(self.tolerances)
        return distances

    # ---------------------------------------------------------------- window
    def window(self, seconds: float, tracer) -> dict[str, Any]:
        outcome = super().window(seconds, tracer)
        outcome["invariants"]["probe_within_bf16_tolerance"] = self.probe_ok
        # A gauge, not a count: which attention the decode program compiled to.
        outcome["counters"]["decode_in_place"] = self.engine.stats["decode_in_place"]
        return outcome


CELL = OlmoHybridCell


# ------------------------------------------------------------ sweep, rehearsal
TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4, "num_key_value_heads": 4,
    "vocab_size": 512, "num_hidden_layers": 4,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
}


def shrink(cell: dict, config: dict) -> None:
    """The cell at a size a CPU runs: one period, prompts that take several
    chunks (the state handed over), every ratio that steers control flow kept."""
    config.update(TINY_CONFIG)
    cell["engine"].update(slots=4, max_len=256, buckets=[64, 128])
    cell["probe"] = {"prompt_tokens": [12, 65, 150], "new_tokens": 8}
    cell["traffic"].update(
        rate=4.0, prompt_tokens={"dist": "uniform", "min": 8, "max": 150},
        new_tokens={"dist": "uniform", "min": 4, "max": 12},
        warm_seconds=0.5, cool_seconds=1.0, drain_seconds=60.0,
    )
    cell["trace"]["seconds"] = 0.5


def sweep(name: str, seeds: list[int], what_if: list[int], only: list[str] | None = None) -> int:
    from .. import harness

    ctx = harness.prepare(name, seeds[0])
    cell = harness.build_cell(ctx)
    cell.build()
    keep = ("worst_short_of_top", "exact_argmax_share", "mean_short_of_top", "per_prompt_worst",
            "state_rel_after_prefill", "state_rel_after_decode")
    all_ok, worst, let_through = True, {}, []
    for n, seed in enumerate(seeds):
        if n:
            cell.reseed(seed)
        d = cell.probe()
        ok = cell.probe_ok and correctness.judge(ctx.traffic_module.SYSTEM, d)
        all_ok &= ok
        harness.say("seed", seed=seed, correct=ok, **d)
        worst["worst_short_of_top"] = max(worst.get("worst_short_of_top", 0.0), d["worst_short_of_top"])
        worst["exact_argmax_share"] = min(worst.get("exact_argmax_share", 1.0), d["exact_argmax_share"])
        worst["mean_short_of_top"] = max(worst.get("mean_short_of_top", 0.0), d["mean_short_of_top"])
        if seed in what_if:
            for label, alter in WHAT_IFS.items():
                if only and label not in only:
                    continue
                d = cell.probe(alter)
                refused = not within(d, cell.tolerances)
                if not refused:
                    let_through.append((seed, label))
                harness.say("what_if", seed=seed, what=label, refused=refused,
                            reference_s=d["reference_s"], **{k: d[k] for k in keep})
    harness.say("sweep", workload=name, seeds=len(seeds), all_correct=all_ok, worst=worst,
                what_ifs_let_through=let_through, tolerances=cell.tolerances,
                device_kind=ctx.devices[0].device_kind, platform=ctx.devices[0].platform)
    return 0 if all_ok and not let_through else 1


def main(argv: list[str] | None = None) -> int:
    import argparse
    import json

    from .. import harness
    from ..check_correct import REHEARSAL_TOLERANCES as OUTER_REHEARSAL, parse_seeds

    t_start = time.perf_counter()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="olmohybrid-serve-chat")
    parser.add_argument("--seeds", default="100-115")
    parser.add_argument("--what-if", default="", metavar="SEEDS")
    parser.add_argument("--only", default="", metavar="WHAT_IFS", help="comma-separated names; all when empty")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if args.rehearse:
        line = harness.run_cell(
            args.workload, parse_seeds(args.seeds)[0], args.seconds, bool(args.trace), t_start,
            shrink=shrink, tolerances=OUTER_REHEARSAL,
        )
        print("REHEARSAL " + json.dumps(line), flush=True)
        return 0
    return sweep(
        args.workload, parse_seeds(args.seeds), parse_seeds(args.what_if) if args.what_if else [],
        [w for w in args.only.split(",") if w],
    )


if __name__ == "__main__":
    import sys

    sys.exit(main())
