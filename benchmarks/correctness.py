"""How a run decides `correct`.

Only two things decide it, and neither can differ between two runs of the
same code and seed:

1. a comparison, made in set-up on inputs drawn from ``--seed``, of what the
   program computes with what `benchmarks/reference` computes from the same
   weights and the same tokens (this file);
2. exact invariants of the measured window — counts and finiteness — which
   the systems report (`systems/trainer.py`: every loss finite, one batch
   shape, no compilation; `systems/engine.py`: every completion has exactly
   its `max_new_tokens` and ended by length, no request refused, no
   compilation). A request that had not finished when the drain limit came
   counts in `failed`, not here: that is the clock's doing.

Nothing about the direction or size of the loss over the window, about
sampled tokens, about how many steps or requests the window reached, or
about the clock is looked at. (PR 24 was refused because a run of unchanged
code printed ``"correct": false``: a condition of that kind was in it.)

The tolerances are set from the distances seen on the chip over the seeds
of `check_correct.py` (PERF.md, "Cells", has the sweeps), with the margin
stated beside each. No data file can move them: only the CPU rehearsal,
which runs tiny widths and shows no number, hands `judge` its own.
"""

from __future__ import annotations

import math
from typing import Any

import numpy as np

# --- train cells -----------------------------------------------------------
# On the chip, `mistral7b-train-1chip`, 33 seeds (`check_correct.py`, seeds
# 0-3 and 100-115, and every run of the cell made by PR 25): the step's loss
# (read in float32: `metrics["loss"]` is not rounded to bf16 on this path) was
# within 2.9e-4 of the reference's, and its gradient norm within 2.9e-4 of it
# relatively. Both tolerances are ten times that: the distances follow the
# seed, and a driver's seeds are not the builder's.
# What the check reads on a fault (seeds 100-105, the reference given the
# altered weights): a skipped layer moves the gradient norm by 3.7e-2 to
# 4.6e-2 (over ten times the tolerance) and the loss by 7.5e-5 to 5.1e-3 (at
# random weights the loss is ln(vocab) + 0.4 whatever the layers do: the norm
# is the check of structure, the loss guards the head and the labels); weights
# rounded to fp8 (e4m3) move the norm by 6.0e-4 to 2.8e-3, inside the
# tolerance: these two scalars do not see a loss of precision. The check
# below does.
TRAIN_LOSS_ATOL = 3e-3
TRAIN_GRAD_NORM_RTOL = 3e-3
# A norm adds noise in quadrature, so the two scalars above see a loss of
# precision only at second order (weights perturbed by 3.6% rms, as fp8
# rounding does, move a norm by 0.036^2 / 2). The sharp check is element by
# element, on the one thing the real step lets out of its gradient: the
# direction of its first update. The norm weights are one-dimensional and
# start at exactly 1 (stored as 0), and adafactor's first update of such a
# weight is -lr * 1e-3 * sign(gradient) whatever the gradient's size
# (decay 0 at step 1, so g / sqrt(g^2); clipping, whether by global or by
# block norm, only scales). So the share of the (2 * layers + 1) * hidden
# norm weights that did NOT move against the reference's gradient is the
# share of gradient elements whose sign the program got wrong: about
# atan(e) / pi for a relative error e in the elements, first order in e.
# Judged where the cell's file sets ``probe.update_signs`` (it needs this
# recipe: adafactor, norm weights at their initial 1). On the chip,
# `mistral7b-train-1chip`, seeds 100-115 (PR 25): 0.38% to 0.45% of the
# 102,400 norm weights as shipped; against fp8 weights 6.37% to 6.48% and
# against a skipped layer 24.9% to 25.4% (seeds 100-105 each). The tolerance
# is 3.3 times the worst seen as shipped; fp8 weights read 4.2 times it.
TRAIN_UPDATE_SIGN_FLIP_MAX = 0.015

# --- serve cells -----------------------------------------------------------
# How far the served token's reference logit may fall short of the
# reference's largest, as a share of the largest. The int8 path quantizes
# activations per row as well as weights (part of the configuration, so the
# distance carries it), and the top two of 32768 random logits are often a
# few per cent apart: 88-95% of the probe's tokens are the reference's exact
# argmax, the rest near-ties. Worst over the 16-seed sweeps of both serve
# cells and every run of them by PR 25: 0.051 (`mistral7b-serve-long`; chat
# 0.040). The tolerance is 2.35 times that. On a fault (seeds 100-115, chat /
# long): the reference without its middle layer reads 0.23-0.37 / 0.23-0.45,
# with weights rounded to fp8 0.12-0.19 / 0.12-0.23: one fp8 seed of each cell
# read under 0.12, so the shortfall alone lets such a fault through now and
# then; the floor on the exact share below does not.
SERVE_LOGIT_SHORT_RTOL = 0.12
# The worst shortfall is a worst-of-N, whose usual value already ranges up to
# 0.05; the share of the probe's tokens that are the reference's exact argmax
# is a mean over all of them and separates more cleanly. On the chip, seeds
# 100-115 with every what-if on every seed (PR 25), chat (256 tokens) / long
# (128 tokens): as shipped 0.879-0.930 / 0.828-0.953; fp8 weights 0.523-0.719
# / 0.469-0.734; a skipped layer 0.270-0.496 / 0.250-0.484. The floor lies
# between the long cell's two ranges, some four standard deviations of a
# 128-token share from the middle of each.
SERVE_EXACT_ARGMAX_MIN = 0.78


TOLERANCES = {
    "train_loss_atol": TRAIN_LOSS_ATOL,
    "train_grad_norm_rtol": TRAIN_GRAD_NORM_RTOL,
    "train_update_sign_flip_max": TRAIN_UPDATE_SIGN_FLIP_MAX,
    "serve_logit_short_rtol": SERVE_LOGIT_SHORT_RTOL,
    "serve_exact_argmax_min": SERVE_EXACT_ARGMAX_MIN,
}


def update_sign_flip_share(before: dict, after: dict, reference_grads: dict) -> float:
    """Share of the norm weights whose first update (``after - before``, as
    `program.norm_scales` reads them around the step) did not go against
    the reference's gradient. A weight that did not move counts as wrong."""
    wrong = total = 0
    for name, grad in reference_grads.items():
        moved = np.sign(after[name] - before[name])
        wrong += int(np.sum(moved != -np.sign(grad)))
        total += grad.size
    return wrong / total


def train_distances(step: dict[str, Any], reference: dict[str, Any]) -> dict[str, float]:
    """``step``: the real step's ``loss`` and ``grad_norm`` and, where the
    cell asks for it, its norm weights ``before`` and ``after``."""
    out = {
        "loss": step["loss"],
        "reference_loss": reference["loss"],
        "loss_abs_diff": abs(step["loss"] - reference["loss"]),
        "grad_norm": step["grad_norm"],
        "reference_grad_norm": reference["grad_norm"],
        "grad_norm_rel_diff": abs(step["grad_norm"] / reference["grad_norm"] - 1.0),
    }
    if "before" in step:
        out["update_sign_flip_share"] = update_sign_flip_share(
            step["before"], step["after"], reference["norm_grads"]
        )
    return out


def judge_train(d: dict[str, float], tol: dict[str, float]) -> bool:
    return bool(
        all(math.isfinite(d[k]) for k in ("loss", "grad_norm", "reference_loss", "reference_grad_norm"))
        and d["loss_abs_diff"] <= tol["train_loss_atol"]
        and d["grad_norm_rel_diff"] <= tol["train_grad_norm_rtol"]
        and d.get("update_sign_flip_share", 0.0) <= tol["train_update_sign_flip_max"]
    )


def short_of_top(reference_logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """For each generated position, how far the served token's reference
    logit falls short of the reference's largest, as a share of it.
    ``reference_logits`` is ``(n_new, vocab)``: row i is the reference's
    prediction after prompt + served[:i]."""
    top = reference_logits.max(axis=-1)
    chosen = np.take_along_axis(reference_logits, served[:, None].astype(np.int64), axis=-1)[:, 0]
    return (top - chosen) / np.abs(top)


def serve_distances(per_prompt: list[np.ndarray]) -> dict[str, Any]:
    worst = [float(g.max()) for g in per_prompt]
    return {
        "worst_short_of_top": max(worst),
        "per_prompt_worst": worst,
        "exact_argmax_share": float(np.mean(np.concatenate(per_prompt) == 0.0)),
    }


def judge_serve(d: dict[str, Any], tol: dict[str, float]) -> bool:
    return bool(
        math.isfinite(d["worst_short_of_top"])
        and d["worst_short_of_top"] <= tol["serve_logit_short_rtol"]
        and d["exact_argmax_share"] >= tol["serve_exact_argmax_min"]
        and not d["wrong_length"]
    )


def judge(system: str, distances: dict[str, Any], tolerances: dict[str, float] = TOLERANCES) -> bool:
    """The set-up comparison's verdict for a cell of ``system``
    (``"trainer"`` or ``"engine"``)."""
    check = judge_train if system == "trainer" else judge_serve
    return check(distances, tolerances)
