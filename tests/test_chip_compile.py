"""What can be known about the chip without one.

- The TPU compiler is installed here: `jax.experimental.topologies` describes
  a v5e that is not attached, and `jit(...).lower(shapes).compile()` raises
  what the chip's compiler would raise. The main-path Pallas kernels are
  compiled at `chip_smoke.py`'s widths (Llama-3-8B: 32/8 heads of 128,
  d_model 4096, d_ff 14336) — interpret mode accepts block shapes and VMEM
  footprints that Mosaic refuses, so the interpret tests in test_kernels
  cannot stand in for these.
- `chip_smoke.py`'s phase functions are rehearsed at tiny sizes on the CPU
  mesh (interpret kernels), and the script itself must refuse a host with no
  TPU.

A compile that passes is not a chip run: nothing here produces a time.
"""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from accelerate_tpu.models import llama, olmo_hybrid, smallthinker  # noqa: E402
from accelerate_tpu.native.pallas import (  # noqa: E402
    decode_attention, fused_adamw, gated_delta, moe_experts, prefill_attention, quant_matmul,
)
from accelerate_tpu.ops import gated_delta as gated_delta_ops  # noqa: E402
from accelerate_tpu.native.pallas.dispatch import force_kernels  # noqa: E402
from accelerate_tpu.ops.flash_attention import flash_attention  # noqa: E402

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
# chip_smoke widths: train at (B, S) = (1, 4096); serve with 4 slots of 256.
H, K, HD, D, FF = 32, 8, 128, 4096, 14336
SLOTS, SLOT_LEN = chip_smoke.SERVE_ENGINE["slots"], chip_smoke.SERVE_ENGINE["max_len"]
SEQ = chip_smoke.TRAIN_CUTS["seq_len"]


@pytest.fixture(scope="module")
def v5e():
    """The four chips of a described (not attached) v5e 2x2. The persistent
    compile cache stays off around these compiles: it would write entries
    that no process without a chip can read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"TPU topology cannot be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _flash(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def _flash_grad(q, k, v):
    return jax.grad(lambda *a: _flash(*a).astype(F32).sum(), argnums=(0, 1, 2))(q, k, v)


def _decode(q, k, v, lengths, layer):
    return decode_attention.flash_decode(q, k, v, lengths, layer, interpret=False)


def _decode_int8(q, k, v, lengths, layer, ks, vs):
    return decode_attention.flash_decode(
        q, k, v, lengths, layer, k_scale=ks, v_scale=vs, interpret=False
    )


def _decode_case(slots, slot_len, heads=H, kv_heads=K, int8=False):
    """A KERNELS entry: `flash_decode` over a two-layer cache of ``slots`` x
    ``slot_len`` rows of ``kv_heads`` heads of 128."""
    stack = (2, slots, slot_len, kv_heads * 128)
    kv = (stack, I8 if int8 else BF16)
    operands = [((slots, 1, heads, 128), BF16), kv, kv, ((slots,), I32), ((), I32)]
    if int8:
        operands += [(stack[:3] + (kv_heads,), BF16)] * 2
    return (_decode_int8 if int8 else _decode, operands, ["flash_decode"])


def _prefill(q, k, v, start, layer):
    return prefill_attention.flash_prefill(q, k, v, start, layer, interpret=False)


def _prefill_case(rows, slot_len, heads=H, kv_heads=K, batch=None):
    """A KERNELS entry: `flash_prefill` of a ``rows``-row chunk over the
    engine's batch-1 row view of a two-layer cache of ``slot_len`` rows of
    ``kv_heads`` heads of 128; with ``batch``, over that many rows of the
    cache, each at a cursor of its own (a `Generator`'s prefill, speculative
    decoding's verification)."""
    kv = ((2, batch or 1, slot_len, kv_heads * 128), BF16)
    q = ((batch or 1, rows, heads, 128), BF16)
    assert prefill_attention.supported(
        jax.ShapeDtypeStruct(*q), jax.ShapeDtypeStruct(*kv), compiled=True
    )
    start = ((), I32) if batch is None else ((batch,), I32)
    return (_prefill, [q, kv, kv, start, ((), I32)], ["flash_prefill"])


def _int8_matmul(x, w, s):
    return quant_matmul.int8_matmul_fused("mc,cn->mn", x, w, s, interpret=False)


def _int8_matmul_stack(x, w, s, layer):
    return quant_matmul.int8_matmul_fused("mc,cn->mn", x, w, s, layer, interpret=False)


def _fp8_matmul(a, b, s):
    return quant_matmul.scaled_matmul("mc,cn->mn", a, b, s, BF16, interpret=False)


def _moe_experts(tile_rows):
    def call(x, w_gate, w_up, w_down, tile_expert, n_tiles, layer):
        return moe_experts.moe_experts(
            x, w_gate, w_up, w_down, tile_expert, n_tiles, layer, tile_rows=tile_rows, interpret=False
        )

    return call


def _gdn_decode(q, k, v, alpha, beta, state, layer, decoding):
    return gated_delta.gdn_decode(q, k, v, alpha, beta, state, layer, decoding, interpret=False)


def _gdn_chunk(q, k, v, g, beta, state):
    return gated_delta.gdn_chunk(gated_delta_ops.chunk_prepare(q, k, v, g, beta), state, interpret=False)


def _adamw(g, mu, nu, p, count, lr):
    return fused_adamw.fused_adamw_update(
        g, mu, nu, p, count, lr, 0.9, 0.999, 1e-8, 0.01, interpret=False
    )


_QKV = [((1, SEQ, H, HD), BF16), ((1, SEQ, K, HD), BF16), ((1, SEQ, K, HD), BF16)]
_LEAF = ((D, FF), F32)
_QKV_SHORT = [((1, 2048, H, HD), BF16), ((1, 2048, K, HD), BF16), ((1, 2048, K, HD), BF16)]
F8 = jnp.float8_e4m3fn
# SmallThinker-21BA3B: 64 experts of 2560 x 768, a two-layer stack; a decode
# step's 96 assignments in tiles of 16 rows, a 1024-row chunk's 6144 in 128s.
ST_E, ST_D, ST_F = 64, 2560, 768
_EXPERT_STACKS = [((2, ST_E, ST_D, ST_F), BF16)] * 2 + [((2, ST_E, ST_F, ST_D), BF16)]
_FLASH_BWD = ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
# name -> (function, [(shape, dtype)...], the kernels expected, by the name each carries)
KERNELS = {
    "flash_fwd": (_flash, _QKV, ["flash_fwd"]),
    "flash_fwd_bwd": (_flash_grad, _QKV, _FLASH_BWD),
    # Under 4096 tokens the whole K and V of a head stay in VMEM.
    "flash_resident_fwd_bwd": (_flash_grad, _QKV_SHORT, [k + "_resident" for k in _FLASH_BWD]),
    "flash_decode_bf16": _decode_case(SLOTS, SLOT_LEN),
    "flash_decode_int8_kv": _decode_case(SLOTS, SLOT_LEN, int8=True),
    # The serve cells' caches (two layers of each): the chat cell's 32 slots of
    # 1024, the long cell's 4 of 8192 (also as int8 KV), and both kinds of the
    # mixed cell's leaves (16 slots, 4 kv heads, 7 query heads a kv head).
    "flash_decode_chat": _decode_case(32, 1024),
    "flash_decode_long": _decode_case(4, 8192),
    "flash_decode_long_int8_kv": _decode_case(4, 8192, int8=True),
    "flash_decode_mixed_full": _decode_case(16, 16384, heads=28, kv_heads=4),
    "flash_decode_mixed_ring": _decode_case(16, 4096, heads=28, kv_heads=4),
    # The down projection of a 2048-token prefill: the whole contraction
    # (14336) staged per block was 43 MB of VMEM against a 16 MB limit.
    "int8_matmul_prefill": (_int8_matmul, [((2048, FF), BF16), ((FF, D), I8), ((1, D), F32)], ["int8_matmul"]),
    "int8_matmul_decode": (_int8_matmul, [((SLOTS, D), BF16), ((D, FF), I8), ((1, FF), F32)], ["int8_matmul"]),
    # A layer stack read in place at a traced layer: the chat cell's 32 decode
    # rows (one row tile: 3.7 MB weight tiles) and a 1024-row chunk.
    "int8_matmul_stack_decode": (
        _int8_matmul_stack, [((32, D), BF16), ((32, D, FF), I8), ((1, FF), F32), ((), I32)], ["int8_matmul"],
    ),
    "int8_matmul_stack_prefill": (
        _int8_matmul_stack, [((1024, D), BF16), ((32, D, FF), I8), ((1, FF), F32), ((), I32)], ["int8_matmul"],
    ),
    "fp8_scaled_matmul": (_fp8_matmul, [((2048, D), F8), ((D, FF), F8), ((), F32)], ["scaled_matmul"]),
    "fused_adamw_leaf": (_adamw, [_LEAF, _LEAF, _LEAF, _LEAF, ((), I32), ((), F32)], ["fused_adamw"]),
    # Three 3.9 MB weight blocks an expert, double-buffered: past Mosaic's
    # default scoped VMEM, so the call raises the limit.
    "moe_experts_decode": (
        _moe_experts(16),
        [((66 * 16, ST_D), BF16), *_EXPERT_STACKS, ((66,), I32), ((), I32), ((), I32)],
        ["moe_experts"],
    ),
    # Olmo-Hybrid-7B: the delta rule's state stack of two layers x 32 slots x
    # 30 heads of 96 x 192 float32, one token a slot in place; a 256-row
    # chunk's state pass; and `flash_decode` at 30 kv heads of one query head.
    "gdn_decode": (
        _gdn_decode,
        [((32, 30, 96), F32)] * 2 + [((32, 30, 192), F32)] + [((32, 30), F32)] * 2
        + [((2, 32, 30, 96, 192), F32), ((), I32), ((32,), jnp.bool_)],
        ["gdn_decode"],
    ),
    "gdn_chunk_256": (
        _gdn_chunk,
        [((1, 256, 30, 96), F32)] * 2 + [((1, 256, 30, 192), F32)] + [((1, 256, 30), F32)] * 2
        + [((1, 30, 96, 192), F32)],
        ["gdn_chunk"],
    ),
    "flash_decode_hybrid_mha": _decode_case(32, 2048, heads=30, kv_heads=30),
    "moe_experts_prefill": (
        _moe_experts(128),
        [((111 * 128, ST_D), BF16), *_EXPERT_STACKS, ((111,), I32), ((), I32), ((), I32)],
        ["moe_experts"],
    ),
    # A prefill chunk against the serve cells' slots, every bucket of each:
    # long (32/8 heads, 8192 rows), chat (1024 rows), mixed (28/4, 16,384
    # rows: seven query heads folded into a tile), hybrid (30/30, 2048 rows).
    **{f"flash_prefill_long_{s}": _prefill_case(s, 8192) for s in (256, 1024)},
    **{f"flash_prefill_chat_{s}": _prefill_case(s, 1024) for s in (32, 64, 128, 256)},
    **{f"flash_prefill_mixed_{s}": _prefill_case(s, 16384, heads=28, kv_heads=4) for s in (256, 1024)},
    **{f"flash_prefill_hybrid_{s}": _prefill_case(s, 2048, heads=30, kv_heads=30) for s in (64, 128, 256)},
    # The widest tile the module picks: eight query heads a kv head, 2048 rows a product.
    "flash_prefill_group_8": _prefill_case(1024, 8192, heads=64, kv_heads=8),
    # Callers other than the engine, at cursors a row: a `Generator` prefills
    # four 128-token prompts into 256 rows; a verification chunk of 16 rows.
    "flash_prefill_generator_4x128": _prefill_case(128, 256, batch=4),
    "flash_prefill_verify_3x16": _prefill_case(16, 1024, batch=3),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(v5e, name):
    """The chip's compiler takes the kernel, and the compiled text carries
    the kernel's name where a v5e trace shows it: an operation is named by
    its HLO text there, and the name rides in the custom call's
    ``frontend_attributes={kernel_metadata={...}}``."""
    fn, operands, kernels = KERNELS[name]
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in operands]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == len(kernels)
    # (a kernel's get-tuple-elements repeat its attributes: a set, not a count)
    named = re.findall(r'kernel_metadata=\{\s*"kernel":"(\w+)"', text)
    assert set(named) == set(kernels)


# The chat cell's cache: 32 slots x 1024 rows, 8 kv heads x 128, two layers.
_CHAT_SLOTS, _CHAT_LEN = 32, 1024
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "reshape", "transpose", "slice")


def _cache_sized_moves(text, layer_elements, dims=None):
    """Instructions of a compiled module (fused computations included) that
    copy, slice, reshape or update-slice an array of one layer's cache or
    more: (name, opcode) pairs, named as a v5e trace would show them.
    ``dims`` (a regular expression on an array's ``a,b,c`` dimensions) keeps
    only arrays of such a shape."""
    found = []
    dims_wanted = re.compile(dims) if dims else None
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\(", line)
        if m is None:
            continue
        name, result, opcode = m.groups()
        sizes = [
            int(np.prod([int(d) for d in dims.split(",")]))
            for dims in re.findall(r"[a-z]\w*\[([0-9,]+)\]", result)
            if dims_wanted is None or dims_wanted.search(dims)
        ]
        moved = opcode.removesuffix("-start").removesuffix("-done") in _MOVES or any(
            word in name for word in _MOVES
        )
        if moved and max(sizes, default=0) >= layer_elements:
            found.append((name, opcode))
    return found


@pytest.mark.parametrize("kernels, in_place", [("on", 1), ("off", 0)])
def test_engine_decode_touches_the_cache_in_place_on_v5e(v5e, monkeypatch, kernels, in_place):
    """The engine's decode program at the chat cell's cache widths, compiled
    for the described chip: between the donated, loop-carried cache and the
    kernel stands no copy, slice, reshape or update of a layer or more (the
    row write is a scatter, aliased onto the carry), and the program's
    temporaries are far under one cache buffer. With the kernel off the same
    check finds the sliced lowering's whole-layer reads: it has teeth."""
    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.native.pallas import dispatch

    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)  # jax.default_backend() is the CPU
    cfg = llama.LlamaConfig(
        vocab_size=512, d_model=512, n_layers=2, num_heads=H, num_kv_heads=K,
        head_dim=HD, d_ff=1024, max_seq_len=_CHAT_LEN,
    )
    params = jax.tree.map(lambda x: x.astype(BF16), llama.init(jax.random.PRNGKey(0), cfg))
    engine = serving.Engine(
        lambda p, t, c: llama.forward_with_cache(p, t, c, cfg),
        lambda b, m: llama.init_cache(cfg, b, m),
        params, GenerationConfig(), slots=_CHAT_SLOTS, max_len=_CHAT_LEN, prefix_cache=False,
    )
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        engine.abstract_decode_args(),
    )
    with force_kernels(kernels):
        compiled = jax.jit(engine._decode_fn, donate_argnums=(3,)).lower(*shapes).compile()
    assert engine.stats["decode_in_place"] == in_place
    layer_elements = _CHAT_SLOTS * _CHAT_LEN * K * HD
    buffer_bytes = cfg.n_layers * layer_elements * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * buffer_bytes  # k and v stay where they were donated
    moves = _cache_sized_moves(compiled.as_text(), layer_elements)
    if in_place:
        assert moves == []
        assert "tpu_custom_call" in compiled.as_text()
        assert memory.temp_size_in_bytes < buffer_bytes // 8
    else:
        assert any("slice" in name or "slice" in opcode for name, opcode in moves), moves


def test_smallthinker_decode_reads_cache_and_experts_in_place_on_v5e(v5e, monkeypatch):
    """The engine's decode program of one period (a full layer and three
    windowed ones) at the published widths, 16 slots of 16,384, compiled for
    the described chip: both kinds of cache are read by `flash_decode` where
    they lie, the expert stacks by `moe_experts` where they lie, and no copy,
    slice, reshape or update of a layer of either kind of cache (a ring layer
    of 16 slots is 33.5 M elements) or of a layer's experts (126 M a matrix)
    stands between. Arrays of other shapes are not looked at: XLA prefetches
    the period's attention weights, 36.7 M elements of `wq`, by a slice."""
    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.native.pallas import dispatch

    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    monkeypatch.setenv("ATX_SERVE_CAPACITY_CHECK", "off")
    monkeypatch.setattr(serving.engine.jax, "device_put", lambda x, device=None: x)  # shapes only
    cfg = smallthinker.SmallThinkerConfig(
        vocab_size=1024, n_layers=4, window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1)
    )
    slots, max_len = 16, 16384
    engine = serving.Engine(
        lambda p, t, c: smallthinker.forward_with_cache(p, t, c, cfg),
        lambda b, m: jax.eval_shape(lambda: smallthinker.init_cache(cfg, b, m)),
        jax.eval_shape(lambda: smallthinker.init(jax.random.PRNGKey(0), cfg, BF16)),
        GenerationConfig(), slots=slots, buckets=(256, 1024), max_len=max_len,
    )
    assert engine.prefix_cache is None and engine.stats["prefix_cache_off_for_ring"] == 1
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        engine.abstract_decode_args(),
    )
    with force_kernels("on"):
        compiled = jax.jit(engine._decode_fn, donate_argnums=(3,)).lower(*shapes).compile()
    assert engine.stats["decode_in_place"] == 1
    text = compiled.as_text()
    named = set(re.findall(r'kernel_metadata=\{\s*"kernel":"(\w+)"', text))
    assert named == {"flash_decode", "moe_experts"}
    lanes = cfg.num_kv_heads * cfg.head_dim
    ring_layer = slots * cfg.sliding_window * lanes
    cache_or_experts = rf",{lanes}$|{ST_D},{ST_F}$|{ST_F},{ST_D}$"
    assert _cache_sized_moves(text, ring_layer, cache_or_experts) == []
    assert _cache_sized_moves(text, ring_layer) != []  # the filter is what lets the prefetch by
    memory = compiled.memory_analysis()
    cache_bytes = 2 * 2 * slots * lanes * (max_len + 3 * cfg.sliding_window)
    assert memory.alias_size_in_bytes >= cache_bytes  # every leaf stays where it was donated
    assert memory.temp_size_in_bytes < ST_E * ST_D * ST_F * 2  # under one matrix of a layer's experts


@pytest.mark.parametrize("program", ["decode", 256])
def test_olmo_hybrid_engine_programs_at_the_published_widths_on_v5e(v5e, monkeypatch, program):
    """The engine's decode step and 256-row prefill chunk of one period (three
    linear-attention layers and a full one) at the published widths, 32 slots
    of 2048, compiled for the described chip: the decode step runs
    `gdn_decode` on the state stack and `flash_decode` on the rows in place
    (every leaf stays where it was donated), the chunk runs `gdn_chunk`, and
    neither copies a layer's weights before it reads them: handed a period's
    slice of the stacks, XLA did (1.5 GB of temporaries a decode step at 16
    layers)."""
    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.native.pallas import dispatch

    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    monkeypatch.setenv("ATX_SERVE_CAPACITY_CHECK", "off")
    monkeypatch.setattr(serving.engine.jax, "device_put", lambda x, device=None: x)  # shapes only
    cfg = olmo_hybrid.OlmoHybridConfig(vocab_size=1024, n_layers=4, max_seq_len=2048)
    slots, max_len = 32, 2048
    engine = serving.Engine(
        lambda p, t, c: olmo_hybrid.forward_with_cache(p, t, c, cfg),
        lambda b, m: jax.eval_shape(lambda: olmo_hybrid.init_cache(cfg, b, m)),
        jax.eval_shape(lambda: olmo_hybrid.init(jax.random.PRNGKey(0), cfg, BF16)),
        GenerationConfig(), slots=slots, buckets=(64, 128, 256), max_len=max_len,
    )
    assert engine.prefix_cache is None and engine.stats["prefix_cache_off_for_state"] == 1
    assert engine._ring_len == 0 and engine._state_names == ("state_conv", "state_gdn")
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree
    )
    args = engine.abstract_decode_args()
    with force_kernels("on"):
        if program == "decode":
            compiled = jax.jit(engine._decode_fn, donate_argnums=(3,)).lower(*on_chip(args)).compile()
        else:
            scalar = lambda dt: jax.ShapeDtypeStruct((), dt)
            chunk = (args[0], jax.ShapeDtypeStruct((1, program), np.int32), args[3],
                     scalar(np.int32), scalar(np.int32), scalar(np.int32), scalar(np.uint32))
            compiled = jax.jit(engine._prefill_fn, donate_argnums=(2,)).lower(*on_chip(chunk)).compile()
    named = set(re.findall(r'kernel_metadata=\{\s*"kernel":"(\w+)"', compiled.as_text()))
    memory = compiled.memory_analysis()
    cache_bytes = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(args[3])
    )
    assert memory.alias_size_in_bytes >= cache_bytes  # every leaf stays where it was donated
    one_matrix = cfg.d_model * cfg.d_ff * 2  # a layer's smallest feed-forward matrix, bf16
    if program == "decode":
        assert named == {"gdn_decode", "flash_decode"} and engine.stats["decode_in_place"] == 1
        assert memory.temp_size_in_bytes < one_matrix
    else:
        # The full layers' chunk attends through `flash_prefill`: every one in place.
        assert named == {"gdn_chunk", "flash_prefill"}
        assert engine._attention_paths[program] == (cfg.n_layers - cfg.n_linear_layers, 0)
        # no scores of 30 heads x 256 queries x 2048 rows (63 MB in float32), no weights copied
        assert memory.temp_size_in_bytes < 6 * one_matrix


def _s8_results(text, dims):
    """(shape, opcode) of every instruction of a compiled module whose result
    is an int8 array of ``dims`` (a regular expression on ``a,b,c``), other
    than the ones that move nothing: parameters, tuple elements, bitcasts."""
    found = re.findall(r"= s8\[([0-9,]+)\]\S* ([a-z][a-z\-]*)\(", text)
    still = ("parameter", "get-tuple-element", "bitcast")
    return [(shape, op) for shape, op in found if re.search(dims, shape) and op not in still]


@pytest.mark.parametrize("program", ["decode", 256])
def test_engine_reads_int8_weight_stacks_in_place_on_v5e(v5e, monkeypatch, program):
    """The int8 engine's decode step and 256-row prefill chunk at the Mistral
    widths (eight layers: at four XLA moves the whole `wo` stack, 67 MB, into its
    fast memory before the loop), compiled for the described chip: `int8_matmul` reads
    gate, up, down and `wo` out of their layer stacks where they lie, so no
    instruction's result is one layer's matrix of those (58.7 MB each, a copy
    a layer a step) nor a whole stack; `wq`, `wk`, `wv` are sliced, their
    (d, h * k) view being a relayout. Handed what the scan slices, the same
    check finds the copies: it has teeth."""
    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.native.pallas import dispatch
    from accelerate_tpu.ops.int8 import with_int8_compute
    from accelerate_tpu.utils.quantization import quantize_pytree

    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    monkeypatch.setenv("ATX_SERVE_CAPACITY_CHECK", "off")
    monkeypatch.setattr(serving.engine.jax, "device_put", lambda x, device=None: x)  # shapes only
    cfg = llama.LlamaConfig(
        vocab_size=512, d_model=D, n_layers=8, num_heads=H, num_kv_heads=K, head_dim=HD,
        d_ff=FF, max_seq_len=_CHAT_LEN,
    )
    params = jax.eval_shape(
        lambda: quantize_pytree(llama.init(jax.random.PRNGKey(0), cfg, BF16))
    )
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree
    )

    def compile_program():
        engine = serving.Engine(
            with_int8_compute(lambda p, t, c: llama.forward_with_cache(p, t, c, cfg)),
            lambda b, m: jax.eval_shape(lambda: llama.init_cache(cfg, b, m)),
            params, GenerationConfig(), slots=_CHAT_SLOTS, buckets=(256,), max_len=_CHAT_LEN,
            prefix_cache=False,
        )
        decode_args = on_chip(engine.abstract_decode_args())
        with force_kernels("on"):
            if program == "decode":
                fn, args, donate = engine._decode_fn, decode_args, 3
            else:
                scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one_chip)
                chunk = jax.ShapeDtypeStruct((1, program), I32, sharding=one_chip)
                args = (decode_args[0], chunk, decode_args[3], *map(scalar, (I32, I32, I32, jnp.uint32)))
                fn, donate = engine._prefill_fn, 2
            text = jax.jit(fn, donate_argnums=(donate,)).lower(*args).compile().as_text()
        return text, engine._weight_paths[program]

    one_matrix = rf"^(1,)?({D},{FF}|{FF},{D}|{H},{HD},{D})$"
    text, paths = compile_program()
    assert paths == (4, 3)  # in place, sliced
    assert len(set(re.findall(r"%(int8_matmul[.\d]*) = ", text))) == 7
    assert _s8_results(text, one_matrix) == []
    assert _s8_results(text, "^8,") == []  # no stack is copied into the loop
    assert len(_s8_results(text, rf"^{D},{D}$")) == 1  # wq: sliced and laid out anew
    monkeypatch.setattr(llama, "hoist_layer_stacks", lambda blocks: (blocks, {}))
    text, paths = compile_program()
    assert paths == (0, 7)
    assert len(_s8_results(text, one_matrix)) >= 4


@pytest.mark.parametrize("kernels, in_place", [("on", 1), ("off", 0)])
def test_long_cell_prefill_chunk_holds_no_scores_of_the_whole_slot_on_v5e(v5e, monkeypatch, kernels, in_place):
    """The long cell's 1024-row prefill program (int8 weights at the Mistral
    widths, two layers, slots of 8192 rows), compiled for the described chip:
    with `flash_prefill` no instruction's result is as large as the scores of
    1024 queries of every kv head against the slot's 8192 rows (XLA's
    lowering wrote ``bf16[8,8192,1024,4]`` and read it back), and the engine
    counts every layer in place. With the kernels off the same check finds
    the scores: it has teeth."""
    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.native.pallas import dispatch
    from accelerate_tpu.ops.int8 import with_int8_compute
    from accelerate_tpu.utils.quantization import quantize_pytree

    rows, slot_len, layers = 1024, 8192, 2
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    monkeypatch.setenv("ATX_SERVE_CAPACITY_CHECK", "off")
    monkeypatch.setattr(serving.engine.jax, "device_put", lambda x, device=None: x)  # shapes only
    cfg = llama.LlamaConfig(
        vocab_size=512, d_model=D, n_layers=layers, num_heads=H, num_kv_heads=K, head_dim=HD,
        d_ff=FF, max_seq_len=slot_len,
    )
    params = jax.eval_shape(lambda: quantize_pytree(llama.init(jax.random.PRNGKey(0), cfg, BF16)))
    one_chip = jax.sharding.SingleDeviceSharding(v5e[0])
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree
    )
    engine = serving.Engine(
        with_int8_compute(lambda p, t, c: llama.forward_with_cache(p, t, c, cfg)),
        lambda b, m: jax.eval_shape(lambda: llama.init_cache(cfg, b, m)),
        params, GenerationConfig(), slots=2, buckets=(256, rows), max_len=slot_len, prefix_cache=False,
    )
    decode_args = on_chip(engine.abstract_decode_args())
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one_chip)
    chunk = jax.ShapeDtypeStruct((1, rows), I32, sharding=one_chip)
    args = (decode_args[0], chunk, decode_args[3], *map(scalar, (I32, I32, I32, jnp.uint32)))
    with force_kernels(kernels):
        text = jax.jit(engine._prefill_fn, donate_argnums=(2,)).lower(*args).compile().as_text()
    assert engine._attention_paths[rows] == ((layers, 0) if in_place else (0, layers))
    assert len(set(re.findall(r"%(flash_prefill[.\d]*) = ", text))) == in_place
    scores = rows * slot_len * K  # one query head of each kv head against every row
    results = re.findall(r"= (?:bf16|f32|pred)\[([0-9,]+)\]", text)
    as_large = [dims for dims in results if np.prod([int(d) for d in dims.split(",")]) >= scores]
    assert bool(as_large) != bool(in_place), as_large[:5]


def test_every_pallas_call_in_the_package_is_named():
    """Each `pl.pallas_call(` site takes its keywords from
    `tuned_call_kwargs`, which always gives it a name and the metadata that
    reaches the trace: a kernel added without one fails here, not in a
    trace somebody reads months later."""
    import ast

    from accelerate_tpu.ops.flash_attention import tuned_call_kwargs

    kwargs = tuned_call_kwargs("some_kernel", False, ("parallel",))
    assert kwargs["name"] == "some_kernel" and kwargs["metadata"] == {"kernel": "some_kernel"}
    assert tuned_call_kwargs("k", True)["interpret"] is True
    sites, helpers = [], ("tuned_call_kwargs", "_call_kwargs")
    package = os.path.join(REPO, "accelerate_tpu")
    for base, _, files in os.walk(package):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(base, f)
            for node in ast.walk(ast.parse(open(path).read())):
                if isinstance(node, ast.Call) and getattr(node.func, "attr", None) == "pallas_call":
                    spread = [k.value for k in node.keywords if k.arg is None]
                    ok = any(
                        isinstance(v, ast.Call) and getattr(v.func, "id", None) in helpers
                        for v in spread
                    )
                    sites.append((os.path.relpath(path, REPO), node.lineno, ok))
    assert len(sites) == 13, sites
    assert [s for s in sites if not s[2]] == []


def test_flash_partitions_over_a_described_mesh(v5e):
    """Flash attention (fwd + bwd) under fsdp=2 x tensor=2 on four described
    chips: batch and heads are sharded by `shard_map`, each chip runs the
    kernels on its shard. (`custom_partitioning` got as far as the TPU
    backend and no further: "Custom emitter for CustomSPMDPartitioning not
    found", on described and on real chips alike.)"""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(v5e).reshape(2, 2), ("fsdp", "tensor"))
    sharding = NamedSharding(mesh, PartitionSpec("fsdp", None, "tensor", None))
    shapes = [
        jax.ShapeDtypeStruct((2, 2048, heads, HD), BF16, sharding=sharding)
        for heads in (H, K, K)
    ]
    with jax.sharding.set_mesh(mesh):
        text = jax.jit(_flash_grad).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    # Each chip's kernel sees its own shard: one batch row, half the heads.
    assert f"bf16[1,{H // 2},2048,{HD}]" in text


def test_plan_refuses_what_cannot_be_staged():
    # A contraction with no 128-multiple divisor must be staged whole; at
    # this width that is past the VMEM budget, so the plan says no (the
    # caller falls back) instead of lowering a kernel the compiler refuses.
    x = jax.ShapeDtypeStruct((2048, 14337), BF16)
    w = jax.ShapeDtypeStruct((14337, 4096), I8)
    assert quant_matmul._plan("mc,cn->mn", x, w, BF16) is None
    assert fused_adamw._plan(8 * 129) is None  # no lane-aligned view


# ------------------------------------------------- chip_smoke, rehearsed on CPU
_TINY_TRAIN = dict(
    head_dim=16, max_seq_len=64, remat=True, remat_policy="attn_and_outputs",
    attention_impl="flash", loss_chunk_size=32,
)


def _rehearse_kernel_parity():
    errors = chip_smoke.kernel_parity_phase(seq_len=64, cache_len=64, head_dim=16, seed=0)
    chip_smoke.check_parity(errors)
    assert set(errors) == {
        "flash_fwd_bwd", "flash_decode", "flash_decode_int8_kv", "flash_prefill", "int8_matmul", "gdn_chunk",
        "gdn_decode",
    }


def _rehearse_serve():
    requests = ((16, 16), (5, 6), (23, 8))
    out = chip_smoke.serve_phase(
        llama.LlamaConfig.tiny(max_seq_len=32),
        requests=requests,
        engine_kwargs={"slots": 2, "buckets": (8, 16), "max_len": 32},
        seed=0,
    )
    assert out["generated_tokens"] == 30 and out["equal_to_generator"] == "16 of 16"
    assert out["reference_argmax_or_tie"] == "16 of 16"
    assert out["decode_compiles"] == 1 and out["prefill_compiles"] == 2


def _rehearse_sharded():
    # Runs `train_phase` too: it is the one-device side of the comparison.
    out = chip_smoke.sharded_phase(
        llama.LlamaConfig.tiny(**_TINY_TRAIN), batch_size=2, seq_len=64, steps=2,
        seed=0, devices=jax.devices()[:4],
    )
    assert len(out["w_gate_shard_bytes"]) == 4 and "all-reduce" in out["collectives"]
    assert out["one_device_losses"][-1] < out["one_device_losses"][0]


@pytest.mark.parametrize(
    "rehearse", [_rehearse_kernel_parity, _rehearse_serve, _rehearse_sharded]
)
def test_chip_smoke_phase_on_cpu(rehearse):
    with force_kernels("interpret"):
        rehearse()


def test_chip_smoke_refuses_a_host_with_no_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == "" and "no TPU" in proc.stderr


# ------------------------------------------------------------ no hidden device
def _mfu_peak_of_an_unknown_chip():
    from accelerate_tpu.telemetry import peak_device_flops

    assert peak_device_flops() is None  # a CPU device has no MFU
    tpu = type("D", (), {"device_kind": "TPU v9 ultra", "platform": "tpu"})()
    with pytest.raises(ValueError, match="TPU v9 ultra"):
        peak_device_flops(tpu)


def _dryrun_on_more_devices_than_exist():
    from __graft_entry__ import dryrun_multichip

    with pytest.raises(RuntimeError, match="needs 64 devices, found 8"):
        dryrun_multichip(64)


@pytest.mark.parametrize(
    "case", [_mfu_peak_of_an_unknown_chip, _dryrun_on_more_devices_than_exist]
)
def test_no_path_stands_in_for_the_device(case):
    case()
