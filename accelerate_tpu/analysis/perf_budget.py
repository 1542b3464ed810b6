"""Ratcheting perf budgets over the ATX601/ATX701/ATX706 static series.

`perf/budgets.json` commits statically-derived numbers per lint scenario —
the MFU ceiling, the exposed-collective bytes, and the tile-padding waste
fraction from the ATX601 roofline, the peak-HBM figure from the ATX701
memory timeline, and the serving planner's static max-slots from ATX706 —
and `atx lint perf|memory --budgets perf/budgets.json` (the `make
lint-perf` / `make lint-memory` lanes) fails when any of them regresses
past tolerance. A PR that
improves a series re-baselines it with `--write-budgets`, so the budget
only moves in the good direction deliberately — a ratchet.

Tolerances are small-but-nonzero because the series, while deterministic
for a given jax/XLA version, shift when the compiler changes fusion or
partitioning decisions; the ratchet should catch model/config mistakes,
not XLA point releases.
"""

from __future__ import annotations

import json
import os
from typing import Any

#: The budgeted series: the first three from every ATX601 `Finding.data`,
#: `peak_hbm_mib` from ATX701, `serve_static_max_slots` from ATX706.
SERIES = (
    "static_mfu_bound",
    "exposed_comms_bytes",
    "padding_waste_fraction",
    "peak_hbm_mib",
    "serve_static_max_slots",
)

# static_mfu_bound may drop (worsen) by at most this relative fraction.
MFU_REL_TOL = 0.02
# exposed_comms_bytes may grow by at most this relative fraction + floor
# (the floor keeps a 0 -> 4-byte wobble from failing the lane).
BYTES_REL_TOL = 0.02
BYTES_ABS_TOL = 1024
# padding_waste_fraction may grow by at most this absolute amount.
FRAC_ABS_TOL = 0.01
# peak_hbm_mib may grow by at most this relative fraction + 1 MiB.
HBM_REL_TOL = 0.02
HBM_ABS_TOL_MIB = 1.0
# serve_static_max_slots may shrink by at most max(1, 2% of the budget).
SLOTS_REL_TOL = 0.02

#: Which rule's Finding.data carries each series.
_SERIES_RULES = {
    "static_mfu_bound": "ATX601",
    "exposed_comms_bytes": "ATX601",
    "padding_waste_fraction": "ATX601",
    "peak_hbm_mib": "ATX701",
    "serve_static_max_slots": "ATX706",
}


def extract_series(report: Any) -> dict[str, float] | None:
    """The budget series from a Report's ATX601/ATX701/ATX706 findings, or
    None when the scenario produced no roofline AND no memory timeline
    (build failed, or no compiled step)."""
    out: dict[str, float] = {}
    for f in getattr(report, "findings", []):
        if f.rule_id not in ("ATX601", "ATX701", "ATX706") or not f.data:
            continue
        for key, rule_id in _SERIES_RULES.items():
            if f.rule_id == rule_id and key in f.data and key not in out:
                out[key] = float(f.data[key])
    return out or None


def load_budgets(path: str) -> dict[str, dict[str, float]]:
    with open(path) as fh:
        doc = json.load(fh)
    return doc.get("scenarios", doc)


def write_budgets(path: str, scenarios: dict[str, dict[str, float]]) -> None:
    doc = {
        "_comment": (
            "Static perf/memory budgets ratcheted by `make lint-perf` and "
            "`make lint-memory` (atx lint perf|memory --budgets "
            "perf/budgets.json). Regenerate with --write-budgets only when "
            "a regression is understood and accepted, or to bank an "
            "improvement. docs/performance.md, docs/static_analysis.md."
        ),
        "scenarios": {
            name: {k: scenarios[name][k] for k in SERIES if k in scenarios[name]}
            for name in sorted(scenarios)
        },
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)


def check_budgets(
    budgets: dict[str, dict[str, float]],
    measured: dict[str, dict[str, float] | None],
) -> list[str]:
    """Violation messages (empty = ratchet holds). A budgeted scenario
    that RAN but produced no series is a violation (its step stopped
    compiling); one that wasn't part of this run is skipped, and
    unbudgeted scenarios/series pass (they get banked by the next
    --write-budgets)."""
    problems: list[str] = []
    for name, budget in sorted(budgets.items()):
        if name not in measured:
            continue
        series = measured[name]
        if series is None:
            problems.append(
                f"{name}: budgeted scenario produced no ATX601/ATX701 "
                "series (step failed to compile, or the rules were "
                "filtered)"
            )
            continue
        old = budget.get("static_mfu_bound")
        new = series.get("static_mfu_bound")
        if old is not None and new is not None and new < old * (1 - MFU_REL_TOL):
            problems.append(
                f"{name}: static_mfu_bound regressed {old:.4f} -> {new:.4f} "
                f"(tolerance -{100 * MFU_REL_TOL:.0f}%)"
            )
        old = budget.get("exposed_comms_bytes")
        new = series.get("exposed_comms_bytes")
        if old is not None and new is not None and new > old * (1 + BYTES_REL_TOL) + BYTES_ABS_TOL:
            problems.append(
                f"{name}: exposed_comms_bytes regressed {int(old)} -> "
                f"{int(new)} (tolerance +{100 * BYTES_REL_TOL:.0f}% + "
                f"{BYTES_ABS_TOL} B)"
            )
        old = budget.get("padding_waste_fraction")
        new = series.get("padding_waste_fraction")
        if old is not None and new is not None and new > old + FRAC_ABS_TOL:
            problems.append(
                f"{name}: padding_waste_fraction regressed {old:.4f} -> "
                f"{new:.4f} (tolerance +{FRAC_ABS_TOL})"
            )
        old = budget.get("peak_hbm_mib")
        new = series.get("peak_hbm_mib")
        if (
            old is not None and new is not None
            and new > old * (1 + HBM_REL_TOL) + HBM_ABS_TOL_MIB
        ):
            problems.append(
                f"{name}: peak_hbm_mib regressed {old:.1f} -> {new:.1f} "
                f"(tolerance +{100 * HBM_REL_TOL:.0f}% + "
                f"{HBM_ABS_TOL_MIB:.0f} MiB)"
            )
        old = budget.get("serve_static_max_slots")
        new = series.get("serve_static_max_slots")
        if old is not None and new is not None:
            floor = old - max(1.0, old * SLOTS_REL_TOL)
            if new < floor:
                problems.append(
                    f"{name}: serve_static_max_slots regressed {int(old)} "
                    f"-> {int(new)} (tolerance -max(1, "
                    f"{100 * SLOTS_REL_TOL:.0f}%))"
                )
    return problems
