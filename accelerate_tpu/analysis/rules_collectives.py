"""ATX4xx — host-sync and collective-traffic rules.

Two failure classes the jaxpr and the compiled HLO expose statically:

- host round-trips inside the hot step (`pure_callback`/`io_callback`/
  `jax.debug.print`): each one fences the device stream and syncs
  device->host every step;
- collective traffic GSPMD inserted: the optimized HLO names every
  all-gather/all-reduce with its result shape, so the bytes each step
  moves over ICI are countable ahead of time — and a single all-gather
  whose output approaches the full parameter byte count is the signature
  of an accidental replication (a spec typo turned FSDP into "gather
  everything, everywhere, every step").
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Any, Iterator

import jax
import numpy as np

from .engine import LintContext, _leaf_bytes, rule
from .findings import Finding, Severity
from .hbm import human_bytes

_CALLBACK_PRIMS = {"pure_callback", "io_callback"}
# jax.debug.print traces to `debug_print`, jax.debug.callback / breakpoint to
# `debug_callback`.
_DEBUG_PRIMS = {"debug_callback", "debug_print"}

_HLO_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# `%name = f32[16,512]{1,0} all-reduce(...)` — or a tuple result
# `(f32[8,4]{1,0}, f32[8,4]{1,0}) all-reduce(...)`; async variants lower
# to `-start`/`-done` pairs (byte totals count the start, skip the done;
# ATX602 matches the pairs up by position to judge overlap).
_COLLECTIVE_RE = re.compile(
    r"%(?P<name>[\w.\-]+)\s*"
    r"=\s+(?P<shape>\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<variant>-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    """Bytes of one HLO result shape (sums tuple elements)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        nbytes = _HLO_DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        count = 1
        for d in dims.split(","):
            if d:
                count *= int(d)
        total += count * nbytes
    return total


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One collective instruction located in the HLO text. ``variant`` is
    "sync", "start", or "done"; ``line`` is the 0-based text line, so the
    ATX602 overlap rule can measure what sits between a start/done pair."""

    op: str
    variant: str
    name: str
    bytes: int
    line: int


def parse_collectives_detailed(hlo_text: str) -> list[CollectiveSite]:
    """Every collective site in optimized HLO text, in program order, with
    async `-start`/`-done` variants distinguished and positioned."""
    sites = []
    for line_no, line in enumerate(hlo_text.splitlines()):
        for m in _COLLECTIVE_RE.finditer(line):
            variant = (m.group("variant") or "-sync").lstrip("-")
            sites.append(
                CollectiveSite(
                    op=m.group("op"),
                    variant=variant,
                    name=m.group("name"),
                    bytes=_shape_bytes(m.group("shape")),
                    line=line_no,
                )
            )
    return sites


def parse_collectives(hlo_text: str) -> list[tuple[str, int]]:
    """(op, result_bytes) per collective in optimized HLO text. Result
    shapes are per-device (post-partitioning), i.e. what each chip
    materializes for the op. `-done` halves of async pairs are skipped —
    the `-start` already carried the bytes."""
    return [
        (s.op, s.bytes)
        for s in parse_collectives_detailed(hlo_text)
        if s.variant != "done"
    ]


def _iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """All eqns in a jaxpr, recursing into sub-jaxprs (pjit bodies, scan,
    cond branches, custom_* calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from _iter_eqns(sub)


def _sub_jaxprs(value: Any) -> Iterator[Any]:
    if hasattr(value, "jaxpr"):  # ClosedJaxpr
        yield value.jaxpr
    elif hasattr(value, "eqns"):  # raw Jaxpr
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


@rule(
    "ATX401",
    Severity.WARNING,
    "host-sync",
    "host callback inside the hot jaxpr (device->host sync every step)",
    "move the host work outside the compiled step, or batch it behind an "
    "explicit metrics fetch every N steps",
    needs={"fn"},
)
def atx401_callbacks(ctx: LintContext) -> Iterator[Finding]:
    closed = ctx.jaxpr()
    if closed is None:
        return
    counts: dict[str, int] = defaultdict(int)
    for eqn in _iter_eqns(closed.jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMS:
            counts[eqn.primitive.name] += 1
    for name, n in sorted(counts.items()):
        yield Finding(
            "ATX401",
            Severity.WARNING,
            name,
            f"{n} `{name}` call(s) traced into the step — each one fences "
            "the device stream and round-trips device->host every step, "
            "serializing dispatch on TPU",
            "hoist the host work out of the jitted step (act on the "
            "returned metrics instead), or amortize it every N steps",
        )


@rule(
    "ATX402",
    Severity.WARNING,
    "host-sync",
    "jax.debug.print / debug callback left in the hot jaxpr",
    "remove it or gate it behind a debug flag; it syncs device->host on "
    "every step",
    needs={"fn"},
)
def atx402_debug_print(ctx: LintContext) -> Iterator[Finding]:
    closed = ctx.jaxpr()
    if closed is None:
        return
    n = sum(
        1 for eqn in _iter_eqns(closed.jaxpr) if eqn.primitive.name in _DEBUG_PRIMS
    )
    if n:
        yield Finding(
            "ATX402",
            Severity.WARNING,
            "debug_callback",
            f"{n} jax.debug.print/breakpoint call(s) traced into the step — "
            "fine for debugging, a per-step host sync in production",
            "delete it, or gate it behind a flag that is False when "
            "compiling the production step",
        )


def _total_param_bytes(ctx: LintContext) -> int:
    if ctx.params_shapes is None:
        return 0
    return sum(
        _leaf_bytes(l)
        for l in jax.tree.leaves(ctx.params_shapes)
        if hasattr(l, "shape") and hasattr(l, "dtype")
    )


@rule(
    "ATX403",
    Severity.WARNING,
    "collectives",
    "single all-gather moves a full-parameter-scale buffer every step",
    "a gather this size usually means a spec typo replicated something "
    "that was meant to stay sharded — check the output constraints and "
    "the param specs feeding this step",
    needs={"fn"},
)
def atx403_giant_gather(ctx: LintContext) -> Iterator[Finding]:
    hlo = ctx.compiled_text()
    if hlo is None:
        return
    param_total = _total_param_bytes(ctx)
    abs_threshold = ctx.opt("gather_bytes_threshold")
    frac = ctx.opt("gather_param_fraction")
    min_bytes = ctx.opt("gather_min_bytes")
    for op, nbytes in parse_collectives(hlo):
        if op != "all-gather":
            continue
        relative_hit = (
            param_total > 0 and nbytes >= frac * param_total and nbytes >= min_bytes
        )
        if nbytes >= abs_threshold or relative_hit:
            detail = (
                f" ({100 * nbytes / param_total:.0f}% of the "
                f"{human_bytes(param_total)} total param bytes)"
                if param_total
                else ""
            )
            yield Finding(
                "ATX403",
                Severity.WARNING,
                "all-gather",
                f"a single all-gather materializes {human_bytes(nbytes)} "
                f"per device per step{detail} — the accidental-replication "
                "signature (a wrong spec makes XLA gather instead of "
                "erroring, 5-50x slower)",
                "find the op's source in the compiled HLO metadata; the "
                "usual causes are an output sharding constraint of P() on "
                "sharded state, or a spec axis dropped by ATX101/ATX102",
            )


@rule(
    "ATX404",
    Severity.INFO,
    "collectives",
    "per-step collective traffic summary mined from the compiled HLO",
    "",
    needs={"fn"},
)
def atx404_traffic_summary(ctx: LintContext) -> Iterator[Finding]:
    hlo = ctx.compiled_text()
    if hlo is None:
        return
    totals: dict[str, tuple[int, int]] = {}
    for op, nbytes in parse_collectives(hlo):
        count, acc = totals.get(op, (0, 0))
        totals[op] = (count + 1, acc + nbytes)
    if not totals:
        return
    parts = [
        f"{op} x{count} ({human_bytes(nbytes)})"
        for op, (count, nbytes) in sorted(totals.items())
    ]
    yield Finding(
        "ATX404",
        Severity.INFO,
        "",
        "collective traffic per step (per-device result bytes): "
        + ", ".join(parts),
        "",
        data={
            "collectives": [
                {"op": op, "count": count, "bytes": nbytes}
                for op, (count, nbytes) in sorted(totals.items())
            ]
        },
    )
