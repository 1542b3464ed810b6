"""Static roofline model over compiled (post-GSPMD) HLO — no steps run.

The optimized HLO `LintContext.compiled_text()` already produces names every
op with its result shape, operand shapes, contracting dims, and loop
structure, and a chip-generation spec table supplies the peaks — so a
classical roofline bound (Williams et al., CACM 2009) is computable ahead
of time, on the CPU container, with zero weights materialized:

- every instruction is parsed (shapes, dtypes, operands, the call graph of
  fusions / while bodies / called computations, with while trip counts
  recovered from the loop-condition `compare(iv, constant)` pattern);
- each op is bucketed **MXU** (dot/convolution FLOPs at the dtype's peak —
  looking *through* upcast converts so a bf16 model compiled by the CPU
  backend still rates at bf16 peak), **vector** (elementwise FLOPs at VPU
  peak), **HBM** (bytes moved at HBM bandwidth — fusions count their
  materialized operands/outputs once, their internal elementwise traffic
  stays on-chip), or **collective** (per-device result bytes at ICI
  bandwidth);
- the static step-time lower bound is the max over per-resource busy times
  (each resource is serial with itself; perfect overlap is assumed across
  resources — hence a true lower bound), and the **static MFU upper
  bound** is MXU busy time over that bound: the utilization ceiling no
  amount of scheduling can beat for this program on this chip.

Also computed here, for the ATX6xx rules that share the parse: per-dot
tile-padding waste against the native (sublane x 128) tile, dots fed by
precision-fallback upcasts, and kLoop-fusion chains materializing large
intermediates to HBM. Chip peaks are approximate public numbers — they set
the *ratios* the bound needs, not benchmarked truth.
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Any, Iterator

# --------------------------------------------------------------- chip specs

#: HLO dtype -> (itemsize, peak-table class). Classes: mxu-rated dtypes map
#: to a peak_flops key; everything else rates at the widest ("f32") peak.
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
}

_PEAK_CLASS = {
    "bf16": "bf16", "f16": "bf16",
    "s8": "int8", "u8": "int8", "s4": "int8", "u4": "int8",
    "f8e4m3fn": "f8", "f8e5m2": "f8", "f8e4m3": "f8", "f8e5m2fnuz": "f8",
}


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-generation peaks the roofline rates against. ``peak_flops`` keys
    are peak classes ("bf16", "f32", "int8", "f8"); ``sublane`` is the f32
    sublane count — narrower dtypes pack ``sublane * (4 // itemsize)``."""

    name: str
    peak_flops: dict[str, float]
    hbm_bytes_per_sec: float
    ici_bytes_per_sec: float
    vmem_bytes: int
    vector_flops_per_sec: float
    hbm_bytes: int = 16 << 30   # per-chip HBM capacity (ATX7xx memory lint)
    sublane: int = 8
    lane: int = 128

    def peak_for(self, dtype: str) -> float:
        cls = _PEAK_CLASS.get(dtype, "f32")
        return self.peak_flops.get(cls) or self.peak_flops["f32"]

    def native_sublane(self, dtype: str) -> int:
        itemsize = _DTYPE_BYTES.get(dtype, 4)
        return self.sublane * max(4 // max(itemsize, 1), 1)


# Approximate public per-chip numbers (dense matmul peaks, HBM/ICI
# bandwidth per chip, VMEM). The `cpu` entry is a stand-in so the analysis
# runs end-to-end on the CPU container — its *ratios* (compute:HBM ~2.5
# FLOP/byte) are chosen TPU-shaped so category attribution stays sane.
CHIP_SPECS: dict[str, ChipSpec] = {
    "v4": ChipSpec(
        "v4",
        {"bf16": 275e12, "f32": 68.75e12, "int8": 275e12, "f8": 275e12},
        1228e9, 300e9, 128 << 20, 4.3e12, hbm_bytes=32 << 30,
    ),
    "v5e": ChipSpec(
        "v5e",
        {"bf16": 197e12, "f32": 49.25e12, "int8": 394e12, "f8": 394e12},
        819e9, 200e9, 128 << 20, 3.1e12, hbm_bytes=16 << 30,
    ),
    "v5p": ChipSpec(
        "v5p",
        {"bf16": 459e12, "f32": 114.75e12, "int8": 918e12, "f8": 918e12},
        2765e9, 600e9, 128 << 20, 7.2e12, hbm_bytes=95 << 30,
    ),
    "v6e": ChipSpec(
        "v6e",
        {"bf16": 918e12, "f32": 229.5e12, "int8": 1836e12, "f8": 1836e12},
        1640e9, 448e9, 128 << 20, 14.3e12, hbm_bytes=32 << 30,
    ),
    "cpu": ChipSpec(
        "cpu",
        {"bf16": 50e9, "f32": 50e9, "int8": 100e9, "f8": 100e9},
        # Host-RAM stand-in sized like a v5e so capacity findings stay
        # TPU-shaped on the CPU container.
        20e9, 10e9, 32 << 20, 5e9, hbm_bytes=16 << 30,
    ),
}

_DEVICE_KIND_PREFIXES = (
    ("TPU v6", "v6e"), ("TPU v5p", "v5p"), ("TPU v5 lite", "v5e"),
    ("TPU v5e", "v5e"), ("TPU v5", "v5p"), ("TPU v4", "v4"),
)


def chip_spec_for(chip: "str | Any | None" = None) -> ChipSpec:
    """Resolve a ChipSpec from a spec-table name, a device-kind string, a
    jax Device, or None (the local device). The `cpu` stand-in is returned
    only for a CPU device or an explicit ``"cpu"``: an accelerator whose kind
    is not in the table raises — its numbers must never be read off the
    stand-in's."""
    if isinstance(chip, str):
        if chip in CHIP_SPECS:
            return CHIP_SPECS[chip]
        kind, platform = chip, None
    else:
        if chip is None or not hasattr(chip, "device_kind"):
            import jax

            chip = jax.devices()[0]
        kind, platform = chip.device_kind, getattr(chip, "platform", None)
    for prefix, name in _DEVICE_KIND_PREFIXES:
        if kind.startswith(prefix):
            return CHIP_SPECS[name]
    if platform == "cpu":
        return CHIP_SPECS["cpu"]
    raise ValueError(
        f"no ChipSpec for device kind {kind!r}: add it to CHIP_SPECS / "
        f"_DEVICE_KIND_PREFIXES (known: {sorted(CHIP_SPECS)})"
    )


# --------------------------------------------------------------- HLO parse

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
    r"(?P<type>\([^=]*?\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>[\w\-]+)\("
)
_OPERAND_RE = re.compile(
    r"(?:([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?\s+)?%([\w.\-]+)"
)
_CALLED_RE = re.compile(
    r"(?P<kind>calls|to_apply|body|condition|true_computation|"
    r"false_computation|branch_computations)=\{?%?([^,\s){]+)"
)
_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_BATCH_DIMS_RE = re.compile(r"lhs_batch_dims=\{([0-9,]*)\}")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CONST_VAL_RE = re.compile(r"constant\((-?[0-9]+)\)")
_TRIP_COUNT_RE = re.compile(r'"known_trip_count":\{"n":"([0-9]+)"\}')

# Zero-cost bookkeeping ops: no bytes move (bitcast is a layout pun; tuples
# and parameters alias existing buffers).
_FREE_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "rng-bit-generator",
    "opt-barrier", "add-dependency", "domain",
})
# Control-flow ops whose cost lives in their called computations.
_CONTROL_OPS = frozenset({"while", "conditional", "call", "fusion"})

_COLLECTIVE_BASE = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def collective_base(op: str) -> str | None:
    """`all-gather-start` / `all-gather` -> `all-gather`; None otherwise."""
    for base in _COLLECTIVE_BASE:
        if op == base or op == base + "-start" or op == base + "-done":
            return base
    return None


@dataclasses.dataclass
class HloInstr:
    """One parsed HLO instruction."""

    name: str
    op: str
    dtype: str          # result dtype ("tuple" for tuple-typed results)
    shape: tuple[int, ...]
    out_bytes: int
    operands: list[tuple[str, tuple[int, ...], str]]  # (dtype, shape, name)
    attrs: str
    comp: str
    index: int          # position within its computation
    op_name: str = ""

    @property
    def operand_bytes(self) -> int:
        return sum(
            _elems(s) * _DTYPE_BYTES.get(d, 4) for d, s, _ in self.operands
        )


@dataclasses.dataclass
class HloComputation:
    name: str
    instrs: list[HloInstr]
    by_name: dict[str, HloInstr]


def _elems(shape: tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _parse_type(text: str) -> tuple[str, tuple[int, ...], int]:
    """(dtype, shape, total_bytes) for a result type; tuple types sum their
    element bytes and report dtype "tuple" with the first element's shape."""
    matches = _SHAPE_RE.findall(text)
    if not matches:
        return "tuple", (), 0
    total = sum(
        _elems(tuple(int(d) for d in dims.split(",") if d))
        * _DTYPE_BYTES.get(dt, 4)
        for dt, dims in matches
    )
    first_dt, first_dims = matches[0]
    shape = tuple(int(d) for d in first_dims.split(",") if d)
    dtype = first_dt if len(matches) == 1 else "tuple"
    return dtype, shape, total


def _split_operands(line: str, op: str) -> tuple[str, str]:
    """(operand_text, attrs_text) — balanced-paren split at the opcode."""
    start = line.index(op + "(") + len(op)
    depth, i = 0, start
    while i < len(line):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                break
        i += 1
    return line[start + 1 : i], line[i + 1 :]


def parse_hlo_module(text: str) -> dict[str, HloComputation]:
    """Parse optimized HLO text into computations of instructions."""
    comps: dict[str, HloComputation] = {}
    current: HloComputation | None = None
    entry_marker: str | None = None
    for raw in text.splitlines():
        # `/*index=5*/` comments inside wide tuple types would defeat the
        # type regex (they contain `=` and `/`); they carry no information.
        if "/*" in raw:
            raw = re.sub(r"/\*.*?\*/", "", raw)
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and ("{" in line) and "->" in line:
            m = _COMP_HEAD_RE.match(line.strip())
            if m:
                current = HloComputation(m.group(1), [], {})
                comps[current.name] = current
                if line.lstrip().startswith("ENTRY"):
                    entry_marker = current.name
                continue
        if current is None:
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        op = m.group("op")
        dtype, shape, out_bytes = _parse_type(m.group("type"))
        try:
            operand_text, attrs = _split_operands(line, op)
        except ValueError:
            operand_text, attrs = "", ""
        if op == "constant" and operand_text:
            # The literal lives in the operand slot; keep scalar values
            # reachable (while_trip_count reads them through attrs).
            attrs = f"constant({operand_text})" + attrs
        operands = [
            (
                od if od else "",
                tuple(int(d) for d in dims.split(",") if d) if od else (),
                name,
            )
            for od, dims, name in _OPERAND_RE.findall(operand_text)
        ]
        op_name_m = _OP_NAME_RE.search(attrs)
        instr = HloInstr(
            name=m.group(1),
            op=op,
            dtype=dtype,
            shape=shape,
            out_bytes=out_bytes,
            operands=operands,
            attrs=attrs,
            comp=current.name,
            index=len(current.instrs),
            op_name=op_name_m.group(1) if op_name_m else "",
        )
        current.instrs.append(instr)
        current.by_name[instr.name] = instr
    if entry_marker is not None:
        for comp in comps.values():
            comp.entry = comp.name == entry_marker  # type: ignore[attr-defined]
    return comps


def entry_computation(comps: dict[str, HloComputation]) -> HloComputation | None:
    for comp in comps.values():
        if getattr(comp, "entry", False):
            return comp
    return None


def _resolve_operand(
    instr: HloInstr, i: int, comp: HloComputation
) -> tuple[str, tuple[int, ...], str]:
    """Operand i with dtype/shape filled from the defining instruction when
    the text carried only a bare %name."""
    dtype, shape, name = instr.operands[i]
    if not dtype:
        definition = comp.by_name.get(name)
        if definition is not None:
            return definition.dtype, definition.shape, name
    return dtype, shape, name


def while_trip_count(
    comps: dict[str, HloComputation], cond_name: str
) -> int:
    """Trip count recovered from the `compare(iv, constant), direction=LT`
    pattern lax.scan/fori lower to; 1 when the pattern is absent (a bound
    the analysis can still work with — it only *under*counts loop work)."""
    comp = comps.get(cond_name)
    if comp is None:
        return 1
    for instr in comp.instrs:
        if instr.op != "compare" or "direction=LT" not in instr.attrs:
            continue
        for _, _, opname in instr.operands:
            definition = comp.by_name.get(opname)
            if definition is not None and definition.op == "constant":
                m = _CONST_VAL_RE.search(
                    definition.attrs
                ) or _CONST_VAL_RE.search(opname)
                if m:
                    return max(int(m.group(1)), 1)
        # constant folded inline into the compare line
        m = _CONST_VAL_RE.search(instr.attrs)
        if m:
            return max(int(m.group(1)), 1)
    return 1


def iter_costed_instrs(
    comps: dict[str, HloComputation],
) -> Iterator[tuple[HloInstr, int, str]]:
    """Yield (instr, multiplier, mode) over every instruction reachable from
    the entry computation. ``multiplier`` is the product of enclosing while
    trip counts; ``mode`` is "full" (count FLOPs and bytes) or "flops"
    (fusion bodies: internal traffic stays on-chip, only MXU work counts).
    Scalar reduction regions and loop conditions are skipped."""
    entry = entry_computation(comps)
    if entry is None:
        return
    # (comp name, multiplier, mode); visited keyed the same way so shared
    # computations called from two sites are costed once per site.
    stack: list[tuple[str, int, str]] = [(entry.name, 1, "full")]
    seen: set[tuple[str, int, str]] = set()
    while stack:
        comp_name, mult, mode = stack.pop()
        key = (comp_name, mult, mode)
        if key in seen:
            continue
        seen.add(key)
        comp = comps.get(comp_name)
        if comp is None:
            continue
        for instr in comp.instrs:
            yield instr, mult, mode
            for m in _CALLED_RE.finditer(instr.attrs):
                kind, target = m.group("kind"), m.group(2).strip("%{} ")
                if kind == "condition":
                    continue
                if kind == "body":
                    # XLA annotates statically-known loops directly; fall
                    # back to the condition's `compare(iv, K), LT` pattern.
                    known = _TRIP_COUNT_RE.search(instr.attrs)
                    if known:
                        trips = max(int(known.group(1)), 1)
                    else:
                        trips = 1
                        for mm in _CALLED_RE.finditer(instr.attrs):
                            if mm.group("kind") == "condition":
                                trips = while_trip_count(
                                    comps, mm.group(2).strip("%{} ")
                                )
                    stack.append((target, mult * trips, mode))
                elif kind == "calls" and instr.op == "fusion":
                    stack.append((target, mult, "flops"))
                elif kind == "to_apply" and instr.op in (
                    "reduce", "reduce-window", "scatter", "all-reduce",
                    "reduce-scatter", "sort", "select-and-scatter",
                ) or collective_base(instr.op):
                    continue  # scalar regions: negligible
                else:
                    stack.append((target, mult, mode))


# --------------------------------------------------------------- cost model

# Elementwise/vector-ish ops: FLOPs ~ output elements (transcendentals
# weighted heavier).
_VECTOR_OPS = {
    "add": 1, "subtract": 1, "multiply": 1, "divide": 4, "maximum": 1,
    "minimum": 1, "compare": 1, "select": 1, "negate": 1, "abs": 1,
    "exponential": 8, "log": 8, "tanh": 10, "logistic": 10, "rsqrt": 4,
    "sqrt": 4, "power": 10, "cosine": 8, "sine": 8, "erf": 10,
    "exponential-minus-one": 8, "log-plus-one": 8, "convert": 1,
    "reduce": 1, "reduce-window": 1, "clamp": 2, "round-nearest-even": 1,
    "floor": 1, "ceil": 1, "sign": 1, "and": 1, "or": 1, "xor": 1, "not": 1,
}


@dataclasses.dataclass
class DotInfo:
    """One dot/convolution with its roofline-relevant numbers."""

    name: str
    op_name: str
    dtype: str               # rated dtype (looked through upcast converts)
    result_dtype: str
    flops: float
    bytes: int
    mult: int
    m: int
    n: int
    k: int
    batch: int
    upcast_from: str = ""    # source dtype when an operand was upcast

    @property
    def intensity(self) -> float:
        return self.flops / max(self.bytes, 1)


def _dot_dims(instr: HloInstr, comp: HloComputation) -> tuple[int, int, int, int]:
    """(batch, M, N, K) for a dot from its operand shapes + contracting and
    batch dims."""
    lhs_d, lhs_shape, _ = _resolve_operand(instr, 0, comp)
    contracting = [
        int(d)
        for d in (_DIMS_RE.search(instr.attrs).group(1).split(",")
                  if _DIMS_RE.search(instr.attrs) else ["-1"])
        if d not in ("", "-1")
    ]
    batch_dims = [
        int(d)
        for d in (_BATCH_DIMS_RE.search(instr.attrs).group(1).split(",")
                  if _BATCH_DIMS_RE.search(instr.attrs) else [])
        if d != ""
    ]
    if not lhs_shape:
        # No shape info: fall back to output-only accounting.
        return 1, _elems(instr.shape), 1, 1
    k = 1
    for d in contracting:
        if 0 <= d < len(lhs_shape):
            k *= lhs_shape[d]
    batch = 1
    for d in batch_dims:
        if 0 <= d < len(lhs_shape):
            batch *= lhs_shape[d]
    m = 1
    for d, size in enumerate(lhs_shape):
        if d not in contracting and d not in batch_dims:
            m *= size
    out = _elems(instr.shape)
    n = max(out // max(batch * m, 1), 1)
    return batch, m, n, k


def _conv_flops(instr: HloInstr, comp: HloComputation) -> float:
    """2 * out_elems * (kernel spatial x in-channels), in-channels inferred
    from the rhs shape and the dim_labels output-feature position."""
    _, rhs_shape, _ = _resolve_operand(instr, 1, comp)
    out = _elems(instr.shape)
    if not rhs_shape:
        return 2.0 * out
    m = re.search(r"dim_labels=\w*_(\w+)->", instr.attrs)
    co = 1
    if m and "o" in m.group(1) and len(m.group(1)) == len(rhs_shape):
        co = rhs_shape[m.group(1).index("o")]
    else:
        co = rhs_shape[-1]
    return 2.0 * out * (_elems(rhs_shape) / max(co, 1))


def _is_convert(definition: HloInstr, comps: dict[str, HloComputation]) -> bool:
    """A bare `convert`, or the single-op kLoop fusion XLA wraps one in
    (``%wrapped_convert = fusion(...), calls=%wrapped_convert_computation``)."""
    if definition.op == "convert":
        return True
    if definition.op != "fusion":
        return False
    m = _CALLS_RE.search(definition.attrs)
    fused = comps.get(m.group(1)) if m else None
    if fused is None:
        return False
    body = [fi.op for fi in fused.instrs if fi.op != "parameter"]
    return body == ["convert"]


def _rated_dtype(
    instr: HloInstr, comp: HloComputation, comps: dict[str, HloComputation]
) -> tuple[str, str]:
    """(rated dtype, upcast source) for a dot: when an operand is a convert
    from a narrower float/int (bf16->f32, s8->bf16...), rate the dot at the
    SOURCE dtype — that is what the program meant, and what a TPU MXU would
    run — and report the upcast for ATX604."""
    rated = instr.dtype
    upcast_from = ""
    best_bytes = _DTYPE_BYTES.get(rated, 4)
    for i in range(min(len(instr.operands), 2)):
        od, _, oname = _resolve_operand(instr, i, comp)
        src = od
        definition = comp.by_name.get(oname)
        converted = (
            definition is not None
            and bool(definition.operands)
            and _is_convert(definition, comps)
        )
        if converted:
            src_d, _, _ = _resolve_operand(definition, 0, comp)
            if src_d:
                src = src_d
        nbytes = _DTYPE_BYTES.get(src, 4)
        if src in _PEAK_CLASS and nbytes < best_bytes:
            rated, best_bytes = src, nbytes
            if converted:
                upcast_from = src
    return rated, upcast_from


_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")


def _fusion_hbm_bytes(instr: HloInstr, comps: dict[str, HloComputation]) -> int:
    """HBM bytes a fusion actually moves. The naive operands+output total
    wildly overcounts fusions that slice into big buffers: a fused
    dynamic-slice reads only the slice, and a fused dynamic-update-slice
    writes only the update into an aliased buffer (the scan-carry pattern —
    charging the full stacked array once per trip would dominate every
    loop)."""
    default = instr.operand_bytes + instr.out_bytes
    m = _CALLS_RE.search(instr.attrs)
    fused = comps.get(m.group(1)) if m else None
    if fused is None:
        return default
    savings = 0
    for fi in fused.instrs:
        if fi.op == "dynamic-slice" and fi.operands:
            od, osh, _ = _resolve_operand(fi, 0, fused)
            savings += max(
                _elems(osh) * _DTYPE_BYTES.get(od, 4) - fi.out_bytes, 0
            )
        elif fi.op == "dynamic-update-slice" and len(fi.operands) >= 2:
            od, osh, _ = _resolve_operand(fi, 0, fused)
            ud, ush, _ = _resolve_operand(fi, 1, fused)
            big = _elems(osh) * _DTYPE_BYTES.get(od, 4)
            upd = _elems(ush) * _DTYPE_BYTES.get(ud, 4)
            savings += 2 * max(big - upd, 0)
    return max(default - savings, 0)


@dataclasses.dataclass
class RooflineResult:
    """Per-category busy times + the derived step-time / MFU bounds."""

    chip: ChipSpec
    mxu_flops: float = 0.0
    mxu_time_s: float = 0.0
    vector_flops: float = 0.0
    vector_time_s: float = 0.0
    hbm_bytes: float = 0.0
    hbm_time_s: float = 0.0
    ici_bytes: float = 0.0
    ici_time_s: float = 0.0
    dots: list[DotInfo] = dataclasses.field(default_factory=list)
    padded_mxu_flops: float = 0.0

    @property
    def step_time_lower_bound_s(self) -> float:
        return max(
            self.mxu_time_s, self.vector_time_s, self.hbm_time_s,
            self.ici_time_s, 1e-12,
        )

    @property
    def static_mfu_bound(self) -> float:
        """Ceiling on achievable MFU: MXU busy time over the bound (1.0
        when the program is purely compute-bound)."""
        if self.mxu_time_s <= 0:
            return 0.0
        return min(self.mxu_time_s / self.step_time_lower_bound_s, 1.0)

    @property
    def bound_category(self) -> str:
        times = {
            "mxu": self.mxu_time_s, "vector": self.vector_time_s,
            "hbm": self.hbm_time_s, "collective": self.ici_time_s,
        }
        return max(times, key=lambda k: times[k])

    @property
    def padding_waste_fraction(self) -> float:
        """Fraction of MXU FLOPs spent on tile padding (dims > one native
        tile that are not tile multiples; sub-tile dims are model-scale
        choices, not tiling bugs, and don't count)."""
        if self.padded_mxu_flops <= 0:
            return 0.0
        return max(1.0 - self.mxu_flops / self.padded_mxu_flops, 0.0)

    def top_dots(self, k: int = 8) -> list[DotInfo]:
        return sorted(self.dots, key=lambda d: -d.flops)[:k]

    def category_table(self) -> list[dict]:
        return [
            {"category": "mxu", "flops": self.mxu_flops,
             "time_ms": self.mxu_time_s * 1e3},
            {"category": "vector", "flops": self.vector_flops,
             "time_ms": self.vector_time_s * 1e3},
            {"category": "hbm", "bytes": int(self.hbm_bytes),
             "time_ms": self.hbm_time_s * 1e3},
            {"category": "collective", "bytes": int(self.ici_bytes),
             "time_ms": self.ici_time_s * 1e3},
        ]


def padded_dot_flops(d: DotInfo, chip: ChipSpec) -> float:
    """FLOPs after rounding each dim up to its native tile — only dims
    LARGER than one tile pad (a 64-wide model on a 128-lane MXU is a model
    choice; a 513-wide dim is a tiling bug)."""
    sub = chip.native_sublane(d.dtype)

    def pad(dim: int, tile: int) -> int:
        if dim <= tile:
            return dim
        return math.ceil(dim / tile) * tile

    return 2.0 * d.batch * pad(d.m, sub) * pad(d.n, chip.lane) * pad(d.k, chip.lane) * d.mult


def analyze_hlo(text: str, chip: ChipSpec) -> RooflineResult:
    """Run the roofline over one optimized-HLO module."""
    comps = parse_hlo_module(text)
    result = RooflineResult(chip=chip)
    for instr, mult, mode in iter_costed_instrs(comps):
        comp = comps[instr.comp]
        if instr.op in ("dot", "convolution"):
            if instr.op == "dot":
                batch, m, n, k = _dot_dims(instr, comp)
                flops = 2.0 * batch * m * n * k
            else:
                flops = _conv_flops(instr, comp)
                batch, m, n, k = 1, _elems(instr.shape), 1, 1
            rated, upcast = _rated_dtype(instr, comp, comps)
            nbytes = (instr.operand_bytes + instr.out_bytes) * mult
            info = DotInfo(
                name=instr.name,
                op_name=instr.op_name,
                dtype=rated,
                result_dtype=instr.dtype,
                flops=flops * mult,
                bytes=nbytes,
                mult=mult,
                m=m, n=n, k=k, batch=batch,
                upcast_from=upcast,
            )
            result.dots.append(info)
            result.mxu_flops += info.flops
            result.mxu_time_s += info.flops / chip.peak_for(rated)
            result.padded_mxu_flops += padded_dot_flops(info, chip)
            if mode == "full":
                result.hbm_bytes += nbytes
                result.hbm_time_s += nbytes / chip.hbm_bytes_per_sec
            continue
        if mode != "full":
            continue  # fusion internals: on-chip traffic
        base = collective_base(instr.op)
        if base is not None:
            if instr.op.endswith("-done"):
                continue  # the matching -start carried the bytes
            nbytes = instr.out_bytes * mult
            result.ici_bytes += nbytes
            result.ici_time_s += nbytes / chip.ici_bytes_per_sec
            continue
        if instr.op in _FREE_OPS or instr.op in ("while", "conditional", "call"):
            continue
        if instr.op in ("dynamic-slice", "slice", "gather"):
            # Reads only the sliced region, not the (possibly huge,
            # loop-stacked) operand: one slice-sized read + one write.
            nbytes = 2 * instr.out_bytes * mult
        elif instr.op in ("dynamic-update-slice", "scatter") and len(instr.operands) >= 2:
            # Reads + writes an update-sized region of an aliased buffer.
            ud, us, _ = _resolve_operand(instr, 1, comps[instr.comp])
            nbytes = 2 * _elems(us) * _DTYPE_BYTES.get(ud, 4) * mult
        elif instr.op == "fusion":
            nbytes = _fusion_hbm_bytes(instr, comps) * mult
        else:
            nbytes = (instr.operand_bytes + instr.out_bytes) * mult
        result.hbm_bytes += nbytes
        result.hbm_time_s += nbytes / chip.hbm_bytes_per_sec
        weight = _VECTOR_OPS.get(instr.op)
        if weight:
            flops = float(weight) * _elems(instr.shape) * mult
            result.vector_flops += flops
            result.vector_time_s += flops / chip.vector_flops_per_sec
    return result


# ------------------------------------------------- exposed-collective scan

@dataclasses.dataclass
class ExposedCollective:
    """An async `-start`/`-done` pair with too little compute between them
    to hide the wire time: the collective sits on the critical path."""

    op: str
    start_name: str
    bytes: int
    collective_time_s: float
    overlap_compute_s: float
    comp: str

    @property
    def exposed_s(self) -> float:
        return max(self.collective_time_s - self.overlap_compute_s, 0.0)


def find_exposed_collectives(
    text: str,
    chip: ChipSpec,
    *,
    min_bytes: int = 1 << 20,
    overlap_fraction: float = 0.5,
) -> list[ExposedCollective]:
    """Scan every computation for async collective start/done pairs and
    rate the compute scheduled between them (dot FLOP time + fusion HBM
    time) against the collective's wire time; pairs covering less than
    ``overlap_fraction`` of it are exposed. Synchronous (non `-start`)
    collectives are not judged — backends without async lowering (the CPU
    container) would flag everything."""
    comps = parse_hlo_module(text)
    out: list[ExposedCollective] = []
    for comp in comps.values():
        starts: dict[str, HloInstr] = {
            i.name: i for i in comp.instrs if i.op.endswith("-start")
            and collective_base(i.op)
        }
        if not starts:
            continue
        for done in comp.instrs:
            if not done.op.endswith("-done") or not collective_base(done.op):
                continue
            start = next(
                (starts[name] for _, _, name in done.operands if name in starts),
                None,
            )
            if start is None:
                continue
            nbytes = start.out_bytes
            if nbytes < min_bytes:
                continue
            wire_s = nbytes / chip.ici_bytes_per_sec
            overlap_s = 0.0
            for between in comp.instrs[start.index + 1 : done.index]:
                if between.op in ("dot", "convolution"):
                    batch, m, n, k = _dot_dims(between, comp)
                    overlap_s += (2.0 * batch * m * n * k) / chip.peak_for(
                        between.dtype
                    )
                elif between.op == "fusion":
                    overlap_s += (
                        between.operand_bytes + between.out_bytes
                    ) / chip.hbm_bytes_per_sec
            if overlap_s < overlap_fraction * wire_s:
                out.append(
                    ExposedCollective(
                        op=collective_base(start.op) or start.op,
                        start_name=start.name,
                        bytes=nbytes,
                        collective_time_s=wire_s,
                        overlap_compute_s=overlap_s,
                        comp=comp.name,
                    )
                )
    return out


# ------------------------------------------------------ fusion-break scan

@dataclasses.dataclass
class FusionBreak:
    """A kLoop fusion whose whole output round-trips HBM just to feed one
    other kLoop fusion — an elementwise chain XLA materialized mid-way."""

    producer: str
    consumer: str
    buffer_bytes: int
    comp: str

    @property
    def extra_hbm_bytes(self) -> int:
        return 2 * self.buffer_bytes  # one write + one read back


def find_fusion_breaks(text: str, *, min_bytes: int = 32 << 20) -> list[FusionBreak]:
    """Pairs of kLoop fusions where the producer's only consumer is the
    other fusion and the materialized intermediate is >= ``min_bytes``."""
    comps = parse_hlo_module(text)
    out: list[FusionBreak] = []
    for comp in comps.values():
        loop_fusions = {
            i.name: i
            for i in comp.instrs
            if i.op == "fusion" and "kind=kLoop" in i.attrs
        }
        if not loop_fusions:
            continue
        uses: dict[str, list[HloInstr]] = defaultdict(list)
        for instr in comp.instrs:
            for _, _, name in instr.operands:
                uses[name].append(instr)
        for name, producer in loop_fusions.items():
            if producer.out_bytes < min_bytes:
                continue
            consumers = uses.get(name, [])
            if len(consumers) == 1 and consumers[0].name in loop_fusions:
                out.append(
                    FusionBreak(
                        producer=name,
                        consumer=consumers[0].name,
                        buffer_bytes=producer.out_bytes,
                        comp=comp.name,
                    )
                )
    return out
