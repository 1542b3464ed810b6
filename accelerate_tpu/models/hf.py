"""Hugging Face checkpoint interop: zero-key-map ingestion of HF repos.

Reference parity: `load_checkpoint_in_model` (`utils/modeling.py:1787`) and
`load_checkpoint_and_dispatch` (`big_modeling.py:511`) let a user point at an
HF repo directory and get a dispatched model with no manual tensor-name
mapping — the reference's core migration value prop. This module gives the
model zoo the same ergonomics, TPU-style:

    family, config, params, plan = hf.load_pretrained("/path/to/Llama-3-8B",
                                                      mesh=mesh)

`load_pretrained` reads ``config.json``, builds the matching family config
(`from_hf_config`), plans shardings against an optional HBM budget
(`infer_sharding_plan`), and streams the HF-named safetensors tensors into
the family's scan-over-layers pytree. Because this framework stacks all L
transformer blocks along a leading layer axis (one leaf per weight *kind*,
not per layer), the translation is not a plain rename: each stacked leaf
gathers L per-layer HF tensors, transposed from torch Linear's ``(out, in)``
to the einsum-native ``(in, out)`` and reshaped to split fused head dims.
Every transform is *slice-mapped* — a device asking for its planned shard of
a leaf reads only the matching byte ranges of the source tensors, so a 70B
repo never materializes a full tensor on any host (the streaming contract of
`load_checkpoint_and_dispatch`).

Supported ``model_type``s: smallthinker and olmo_hybrid (their configs only:
`from_hf_config`), llama, mistral, mixtral, qwen2 (the llama
family — mixtral routes through the MoE blocks, qwen2 adds q/k/v biases),
gpt2, gpt_neox, gptj, opt (the gpt family — variant knobs select rotary
style, parallel residual, activation, and bias layout; these are the
reference's published big-model-inference models,
`benchmarks/big_model_inference/README.md:27-37`), bert, vit, t5 (v1.1
gated layout). Norm weights are rebased for this framework's
``(1 + scale)`` RMSNorm parameterization where applicable.
`save_pretrained` writes the repo back out in HF layout (every family and
layout above) so `transformers` loads the export unchanged — round-trip
logit parity is tested for every family.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import numpy as np
Params = Any

# A fetcher maps (read, out_idx, out_shape) -> np array for ONE layer (or the
# whole leaf when not per-layer). `read(idx)` returns the source tensor's
# slice `idx`; `out_idx` is the requested slice of the TARGET leaf (without
# the stacked layer axis); `out_shape` the target leaf shape (ditto).
Fetcher = Callable[[Callable, tuple, tuple], np.ndarray]


def _norm_idx(idx: tuple, shape: tuple) -> tuple[slice, ...]:
    return tuple(slice(*s.indices(d)) for s, d in zip(idx, shape))


def _ident(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
    return read(idx)


def _minus1(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
    # HF norm weight w -> this framework's rms_norm computes x * (1 + scale),
    # so scale = w - 1 (layers.py:69).
    arr = read(idx)
    return arr - np.asarray(1, dtype=arr.dtype)


def _t2(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
    # torch Linear (out, in) -> (in, out).
    i0, i1 = idx
    return read((i1, i0)).T


def _full(s: slice, dim: int) -> bool:
    return s.start == 0 and s.stop == dim


def _qkv(head_dim: int) -> Fetcher:
    """HF ``{q,k,v}_proj.weight`` (n_heads*h, d) -> (d, n_heads, h)."""

    def fetch(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
        ds, hs, hd = idx
        if not _full(hd, shape[2]):
            raise NotImplementedError(
                "HF streaming does not support sharding the head_dim axis "
                f"(requested {hd} of {shape[2]}); shard heads instead."
            )
        h = head_dim
        rows = slice(hs.start * h, hs.stop * h)
        arr = read((rows, ds))  # ((hs)*h, d_sub)
        return arr.T.reshape(ds.stop - ds.start, hs.stop - hs.start, h)

    return fetch


def _oproj(head_dim: int) -> Fetcher:
    """HF ``o_proj.weight`` (d, n_heads*h) -> (n_heads, h, d)."""

    def fetch(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
        hs, hd, ds = idx
        if not _full(hd, shape[1]):
            raise NotImplementedError(
                "HF streaming does not support sharding the head_dim axis "
                f"(requested {hd} of {shape[1]}); shard heads instead."
            )
        h = head_dim
        cols = slice(hs.start * h, hs.stop * h)
        arr = read((ds, cols))  # (d_sub, (hs)*h)
        return arr.T.reshape(hs.stop - hs.start, h, ds.stop - ds.start)

    return fetch


def _conv1d_qkv(d_model: int, head_dim: int, part: int) -> Fetcher:
    """GPT-2 fused ``c_attn.weight`` (d, 3d), already (in, out): block
    ``part`` (0=q, 1=k, 2=v) -> (d, n_heads, h)."""

    def fetch(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
        ds, hs, hd = idx
        if not _full(hd, shape[2]):
            raise NotImplementedError("head_dim axis must not be sharded")
        h = head_dim
        cols = slice(part * d_model + hs.start * h, part * d_model + hs.stop * h)
        arr = read((ds, cols))
        return arr.reshape(ds.stop - ds.start, hs.stop - hs.start, h)

    return fetch


def _conv1d_qkv_bias(d_model: int, head_dim: int, part: int) -> Fetcher:
    """GPT-2 fused ``c_attn.bias`` (3d,): block ``part`` -> (n_heads, h)."""

    def fetch(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
        hs, hd = idx
        if not _full(hd, shape[1]):
            raise NotImplementedError("head_dim axis must not be sharded")
        h = head_dim
        rows = slice(part * d_model + hs.start * h, part * d_model + hs.stop * h)
        return read((rows,)).reshape(hs.stop - hs.start, h)

    return fetch


def _neox_qkv(head_dim: int, part: int) -> Fetcher:
    """GPT-NeoX fused ``query_key_value.weight`` (3d, d): rows for head i
    are ``[i*3h, (i+1)*3h)`` laid out ``[q|k|v]`` PER HEAD (transformers
    views to ``(..., num_heads, 3*head_size)`` then chunks) — unlike
    GPT-2's ``[all-q|all-k|all-v]`` Conv1D blocks. -> (d, n_heads, h)."""

    def fetch(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
        ds, hs, hd = idx
        if not _full(hd, shape[2]):
            raise NotImplementedError("head_dim axis must not be sharded")
        h = head_dim
        rows = slice(hs.start * 3 * h, hs.stop * 3 * h)
        arr = read((rows, ds))  # (3h * heads, d_sub)
        arr = arr.reshape(hs.stop - hs.start, 3, h, ds.stop - ds.start)
        return np.ascontiguousarray(arr[:, part].transpose(2, 0, 1))

    return fetch


def _neox_qkv_bias(head_dim: int, part: int) -> Fetcher:
    """GPT-NeoX fused ``query_key_value.bias`` (3d,) -> (n_heads, h)."""

    def fetch(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
        hs, hd = idx
        if not _full(hd, shape[1]):
            raise NotImplementedError("head_dim axis must not be sharded")
        h = head_dim
        arr = read((slice(hs.start * 3 * h, hs.stop * 3 * h),))
        return np.ascontiguousarray(arr.reshape(-1, 3, h)[:, part])

    return fetch


def _vec_heads(head_dim: int) -> Fetcher:
    """HF flat per-head bias (n_heads*h,) -> (n_heads, h)."""

    def fetch(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
        hs, hd = idx
        if not _full(hd, shape[1]):
            raise NotImplementedError("head_dim axis must not be sharded")
        h = head_dim
        return read((slice(hs.start * h, hs.stop * h),)).reshape(
            hs.stop - hs.start, h
        )

    return fetch


@dataclass(frozen=True)
class _Src:
    """Where one target leaf comes from in the HF checkpoint.

    ``invert`` (when set) maps ONE per-layer slice of this framework's leaf
    back to the HF tensor layout — the export direction
    (`save_pretrained`)."""

    key: str  # tensor name; ``{i}`` substituted per layer when per_layer
    fetch: Fetcher = _ident
    per_layer: bool = False
    invert: Callable[[np.ndarray], np.ndarray] | None = None
    # Leaf carries a second stacked axis of per-expert HF tensors (``{e}``
    # in the template) — the Mixtral block_sparse_moe layout.
    per_expert: bool = False


# Inverse layouts for the export direction.
def _inv_ident(arr: np.ndarray) -> np.ndarray:
    return arr


def _inv_vec_heads(arr: np.ndarray) -> np.ndarray:
    # (n_heads, h) -> (n_heads*h,)
    return np.ascontiguousarray(arr.reshape(-1))


def _inv_plus1(arr: np.ndarray) -> np.ndarray:
    return arr + np.asarray(1, dtype=arr.dtype)


def _inv_t2(arr: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(arr.T)


def _inv_qkv(arr: np.ndarray) -> np.ndarray:
    # (d, n_heads, h) -> (n_heads*h, d)
    d = arr.shape[0]
    return np.ascontiguousarray(arr.reshape(d, -1).T)


def _inv_oproj(arr: np.ndarray) -> np.ndarray:
    # (n_heads, h, d) -> (d, n_heads*h)
    d = arr.shape[-1]
    return np.ascontiguousarray(arr.reshape(-1, d).T)


# --------------------------------------------------------------- family maps
def _llama_specs(config) -> dict[str, _Src]:
    h = config.resolved_head_dim
    L = "model.layers.{i}."
    m = {
        "embed": _Src("model.embed_tokens.weight", invert=_inv_ident),
        "final_norm": _Src("model.norm.weight", _minus1, invert=_inv_plus1),
        "blocks.attn_norm": _Src(
            L + "input_layernorm.weight", _minus1, True, invert=_inv_plus1
        ),
        "blocks.mlp_norm": _Src(
            L + "post_attention_layernorm.weight", _minus1, True, invert=_inv_plus1
        ),
        "blocks.attn.wq": _Src(
            L + "self_attn.q_proj.weight", _qkv(h), True, invert=_inv_qkv
        ),
        "blocks.attn.wk": _Src(
            L + "self_attn.k_proj.weight", _qkv(h), True, invert=_inv_qkv
        ),
        "blocks.attn.wv": _Src(
            L + "self_attn.v_proj.weight", _qkv(h), True, invert=_inv_qkv
        ),
        "blocks.attn.wo": _Src(
            L + "self_attn.o_proj.weight", _oproj(h), True, invert=_inv_oproj
        ),
        "blocks.mlp.w_gate": _Src(
            L + "mlp.gate_proj.weight", _t2, True, invert=_inv_t2
        ),
        "blocks.mlp.w_up": _Src(L + "mlp.up_proj.weight", _t2, True, invert=_inv_t2),
        "blocks.mlp.w_down": _Src(
            L + "mlp.down_proj.weight", _t2, True, invert=_inv_t2
        ),
    }
    if config.attn_bias:
        # Qwen2 layout: q/k/v projections carry biases (o_proj does not).
        m["blocks.attn.bq"] = _Src(
            L + "self_attn.q_proj.bias", _vec_heads(h), True, invert=_inv_vec_heads
        )
        m["blocks.attn.bk"] = _Src(
            L + "self_attn.k_proj.bias", _vec_heads(h), True, invert=_inv_vec_heads
        )
        m["blocks.attn.bv"] = _Src(
            L + "self_attn.v_proj.bias", _vec_heads(h), True, invert=_inv_vec_heads
        )
    if config.n_experts:
        # Mixtral block_sparse_moe layout: w1=gate, w3=up, w2=down, all
        # torch (out, in); router `gate.weight` is (E, d).
        E = L + "block_sparse_moe.experts.{e}."
        for leaf in ("blocks.mlp.w_gate", "blocks.mlp.w_up", "blocks.mlp.w_down"):
            del m[leaf]
        m["blocks.moe.router"] = _Src(
            L + "block_sparse_moe.gate.weight", _t2, True, invert=_inv_t2
        )
        m["blocks.moe.w_gate"] = _Src(
            E + "w1.weight", _t2, True, invert=_inv_t2, per_expert=True
        )
        m["blocks.moe.w_up"] = _Src(
            E + "w3.weight", _t2, True, invert=_inv_t2, per_expert=True
        )
        m["blocks.moe.w_down"] = _Src(
            E + "w2.weight", _t2, True, invert=_inv_t2, per_expert=True
        )
    if not config.tie_embeddings:
        m["lm_head"] = _Src("lm_head.weight", _t2, invert=_inv_t2)
    return m


def _gpt2_specs(config) -> dict[str, _Src]:
    h = config.attention_spec.head_dim
    d = config.d_model
    L = "h.{i}."
    m = {
        "wte": _Src("wte.weight"),
        "wpe": _Src("wpe.weight"),
        "lnf_scale": _Src("ln_f.weight"),
        "lnf_bias": _Src("ln_f.bias"),
        "blocks.ln1_scale": _Src(L + "ln_1.weight", _ident, True),
        "blocks.ln1_bias": _Src(L + "ln_1.bias", _ident, True),
        "blocks.ln2_scale": _Src(L + "ln_2.weight", _ident, True),
        "blocks.ln2_bias": _Src(L + "ln_2.bias", _ident, True),
        "blocks.attn.wq": _Src(L + "attn.c_attn.weight", _conv1d_qkv(d, h, 0), True),
        "blocks.attn.wk": _Src(L + "attn.c_attn.weight", _conv1d_qkv(d, h, 1), True),
        "blocks.attn.wv": _Src(L + "attn.c_attn.weight", _conv1d_qkv(d, h, 2), True),
        "blocks.attn.bq": _Src(L + "attn.c_attn.bias", _conv1d_qkv_bias(d, h, 0), True),
        "blocks.attn.bk": _Src(L + "attn.c_attn.bias", _conv1d_qkv_bias(d, h, 1), True),
        "blocks.attn.bv": _Src(L + "attn.c_attn.bias", _conv1d_qkv_bias(d, h, 2), True),
        # c_proj is Conv1D too: (in = H*h, out = d) — no transpose, reshape only.
        "blocks.attn.wo": _Src(L + "attn.c_proj.weight", _gpt2_oproj(h), True),
        "blocks.attn.bo": _Src(L + "attn.c_proj.bias", _ident, True),
        "blocks.mlp.w_in": _Src(L + "mlp.c_fc.weight", _ident, True),
        "blocks.mlp.b_in": _Src(L + "mlp.c_fc.bias", _ident, True),
        "blocks.mlp.w_out": _Src(L + "mlp.c_proj.weight", _ident, True),
        "blocks.mlp.b_out": _Src(L + "mlp.c_proj.bias", _ident, True),
    }
    if not config.tie_embeddings:
        # Untied head (this framework's own exports write one): HF (V, d)
        # -> (d, V).
        m["lm_head"] = _Src("lm_head.weight", _t2)
    return m


def _gpt2_oproj(head_dim: int) -> Fetcher:
    """GPT-2 ``c_proj.weight`` (n_heads*h, d) already (in, out) ->
    (n_heads, h, d): reshape only."""

    def fetch(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
        hs, hd, ds = idx
        if not _full(hd, shape[1]):
            raise NotImplementedError("head_dim axis must not be sharded")
        h = head_dim
        rows = slice(hs.start * h, hs.stop * h)
        arr = read((rows, ds))
        return arr.reshape(hs.stop - hs.start, h, ds.stop - ds.start)

    return fetch


def _neox_specs(config) -> dict[str, _Src]:
    """GPT-NeoX layout (``gpt_neox.layers.{i}.*`` + ``embed_in``/
    ``embed_out``); canonical names are unprefixed, the loader's suffix
    match absorbs the ``gpt_neox.`` root."""
    h = config.head_dim
    L = "layers.{i}."
    m = {
        "wte": _Src("embed_in.weight", invert=_inv_ident),
        "lnf_scale": _Src("final_layer_norm.weight", invert=_inv_ident),
        "lnf_bias": _Src("final_layer_norm.bias", invert=_inv_ident),
        "blocks.ln1_scale": _Src(L + "input_layernorm.weight", _ident, True, _inv_ident),
        "blocks.ln1_bias": _Src(L + "input_layernorm.bias", _ident, True, _inv_ident),
        "blocks.ln2_scale": _Src(L + "post_attention_layernorm.weight", _ident, True, _inv_ident),
        "blocks.ln2_bias": _Src(L + "post_attention_layernorm.bias", _ident, True, _inv_ident),
        "blocks.attn.wq": _Src(L + "attention.query_key_value.weight", _neox_qkv(h, 0), True),
        "blocks.attn.wk": _Src(L + "attention.query_key_value.weight", _neox_qkv(h, 1), True),
        "blocks.attn.wv": _Src(L + "attention.query_key_value.weight", _neox_qkv(h, 2), True),
        "blocks.attn.wo": _Src(L + "attention.dense.weight", _oproj(h), True, _inv_oproj),
        "blocks.mlp.w_in": _Src(L + "mlp.dense_h_to_4h.weight", _t2, True, _inv_t2),
        "blocks.mlp.b_in": _Src(L + "mlp.dense_h_to_4h.bias", _ident, True, _inv_ident),
        "blocks.mlp.w_out": _Src(L + "mlp.dense_4h_to_h.weight", _t2, True, _inv_t2),
        "blocks.mlp.b_out": _Src(L + "mlp.dense_4h_to_h.bias", _ident, True, _inv_ident),
    }
    if config.attn_bias:
        m["blocks.attn.bq"] = _Src(L + "attention.query_key_value.bias", _neox_qkv_bias(h, 0), True)
        m["blocks.attn.bk"] = _Src(L + "attention.query_key_value.bias", _neox_qkv_bias(h, 1), True)
        m["blocks.attn.bv"] = _Src(L + "attention.query_key_value.bias", _neox_qkv_bias(h, 2), True)
        m["blocks.attn.bo"] = _Src(L + "attention.dense.bias", _ident, True, _inv_ident)
    if not config.tie_embeddings:
        m["lm_head"] = _Src("embed_out.weight", _t2, invert=_inv_t2)
    return m


def _gptj_specs(config) -> dict[str, _Src]:
    """GPT-J layout (``transformer.h.{i}.*``): separate bias-free q/k/v/out
    projections, biased MLP, single shared ``ln_1``, untied biased head."""
    h = config.head_dim
    L = "h.{i}."
    m = {
        "wte": _Src("wte.weight", invert=_inv_ident),
        "lnf_scale": _Src("ln_f.weight", invert=_inv_ident),
        "lnf_bias": _Src("ln_f.bias", invert=_inv_ident),
        "blocks.ln1_scale": _Src(L + "ln_1.weight", _ident, True, _inv_ident),
        "blocks.ln1_bias": _Src(L + "ln_1.bias", _ident, True, _inv_ident),
        "blocks.attn.wq": _Src(L + "attn.q_proj.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.wk": _Src(L + "attn.k_proj.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.wv": _Src(L + "attn.v_proj.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.wo": _Src(L + "attn.out_proj.weight", _oproj(h), True, _inv_oproj),
        "blocks.mlp.w_in": _Src(L + "mlp.fc_in.weight", _t2, True, _inv_t2),
        "blocks.mlp.b_in": _Src(L + "mlp.fc_in.bias", _ident, True, _inv_ident),
        "blocks.mlp.w_out": _Src(L + "mlp.fc_out.weight", _t2, True, _inv_t2),
        "blocks.mlp.b_out": _Src(L + "mlp.fc_out.bias", _ident, True, _inv_ident),
    }
    if not config.tie_embeddings:
        m["lm_head"] = _Src("lm_head.weight", _t2, invert=_inv_t2)
        if config.head_bias:
            m["lm_head_bias"] = _Src("lm_head.bias", invert=_inv_ident)
    return m


def _inv_opt_pos(arr: np.ndarray) -> np.ndarray:
    # Re-prepend OPTLearnedPositionalEmbedding's 2 offset rows (never read
    # at inference — position lookups add offset 2).
    return np.concatenate([np.zeros((2, arr.shape[1]), arr.dtype), arr])


def _opt_specs(config) -> dict[str, _Src]:
    """OPT layout (``model.decoder.layers.{i}.*``). ``embed_positions`` has
    a 2-row lookup offset (transformers ``OPTLearnedPositionalEmbedding``);
    the fetch slices it off so forward uses plain 0-based positions. The
    per-layer ``final_layer_norm`` is the MLP's pre-norm (ln2) — only the
    top-level ``decoder.final_layer_norm`` is the real final norm, and the
    canonical names keep the ``decoder.`` segment so the suffix match can't
    confuse the two."""
    h = config.head_dim

    def pos_fetch(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
        i0, i1 = _norm_idx(idx, shape)
        return read((slice(i0.start + 2, i0.stop + 2), i1))

    L = "decoder.layers.{i}."
    m = {
        "wte": _Src("decoder.embed_tokens.weight", invert=_inv_ident),
        "wpe": _Src("decoder.embed_positions.weight", pos_fetch, invert=_inv_opt_pos),
        "lnf_scale": _Src("decoder.final_layer_norm.weight", invert=_inv_ident),
        "lnf_bias": _Src("decoder.final_layer_norm.bias", invert=_inv_ident),
        "blocks.ln1_scale": _Src(L + "self_attn_layer_norm.weight", _ident, True, _inv_ident),
        "blocks.ln1_bias": _Src(L + "self_attn_layer_norm.bias", _ident, True, _inv_ident),
        "blocks.ln2_scale": _Src(L + "final_layer_norm.weight", _ident, True, _inv_ident),
        "blocks.ln2_bias": _Src(L + "final_layer_norm.bias", _ident, True, _inv_ident),
        "blocks.attn.wq": _Src(L + "self_attn.q_proj.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.wk": _Src(L + "self_attn.k_proj.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.wv": _Src(L + "self_attn.v_proj.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.bq": _Src(L + "self_attn.q_proj.bias", _vec_heads(h), True, _inv_vec_heads),
        "blocks.attn.bk": _Src(L + "self_attn.k_proj.bias", _vec_heads(h), True, _inv_vec_heads),
        "blocks.attn.bv": _Src(L + "self_attn.v_proj.bias", _vec_heads(h), True, _inv_vec_heads),
        "blocks.attn.wo": _Src(L + "self_attn.out_proj.weight", _oproj(h), True, _inv_oproj),
        "blocks.attn.bo": _Src(L + "self_attn.out_proj.bias", _ident, True, _inv_ident),
        "blocks.mlp.w_in": _Src(L + "fc1.weight", _t2, True, _inv_t2),
        "blocks.mlp.b_in": _Src(L + "fc1.bias", _ident, True, _inv_ident),
        "blocks.mlp.w_out": _Src(L + "fc2.weight", _t2, True, _inv_t2),
        "blocks.mlp.b_out": _Src(L + "fc2.bias", _ident, True, _inv_ident),
    }
    if not config.tie_embeddings:
        # Every released OPT ties, but an untied config must still map its
        # head — otherwise export silently drops the trained weight.
        m["lm_head"] = _Src("lm_head.weight", _t2, invert=_inv_t2)
    return m


def _gpt_specs(config) -> dict[str, _Src]:
    layout = getattr(config, "hf_layout", "gpt2")
    builder = {
        "gpt2": _gpt2_specs,
        "gpt_neox": _neox_specs,
        "gptj": _gptj_specs,
        "opt": _opt_specs,
    }.get(layout)
    if builder is None:
        raise ValueError(
            f"GPTConfig.hf_layout={layout!r} has no HF map; known: gpt2, "
            "gpt_neox, gptj, opt."
        )
    return builder(config)


def _bert_specs(config) -> dict[str, _Src]:
    h = config.attention_spec.head_dim
    E = "embeddings."
    L = "encoder.layer.{i}."
    return {
        "tok_embed": _Src(E + "word_embeddings.weight", invert=_inv_ident),
        "pos_embed": _Src(E + "position_embeddings.weight", invert=_inv_ident),
        "type_embed": _Src(E + "token_type_embeddings.weight", invert=_inv_ident),
        "embed_norm_scale": _Src(E + "LayerNorm.weight", invert=_inv_ident),
        "embed_norm_bias": _Src(E + "LayerNorm.bias", invert=_inv_ident),
        "blocks.attn.wq": _Src(L + "attention.self.query.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.wk": _Src(L + "attention.self.key.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.wv": _Src(L + "attention.self.value.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.bq": _Src(L + "attention.self.query.bias", _vec_heads(h), True, _inv_vec_heads),
        "blocks.attn.bk": _Src(L + "attention.self.key.bias", _vec_heads(h), True, _inv_vec_heads),
        "blocks.attn.bv": _Src(L + "attention.self.value.bias", _vec_heads(h), True, _inv_vec_heads),
        "blocks.attn.wo": _Src(L + "attention.output.dense.weight", _oproj(h), True, _inv_oproj),
        "blocks.attn.bo": _Src(L + "attention.output.dense.bias", _ident, True, _inv_ident),
        "blocks.attn_norm_scale": _Src(L + "attention.output.LayerNorm.weight", _ident, True, _inv_ident),
        "blocks.attn_norm_bias": _Src(L + "attention.output.LayerNorm.bias", _ident, True, _inv_ident),
        "blocks.mlp.w_in": _Src(L + "intermediate.dense.weight", _t2, True, _inv_t2),
        "blocks.mlp.b_in": _Src(L + "intermediate.dense.bias", _ident, True, _inv_ident),
        "blocks.mlp.w_out": _Src(L + "output.dense.weight", _t2, True, _inv_t2),
        "blocks.mlp.b_out": _Src(L + "output.dense.bias", _ident, True, _inv_ident),
        "blocks.mlp_norm_scale": _Src(L + "output.LayerNorm.weight", _ident, True, _inv_ident),
        "blocks.mlp_norm_bias": _Src(L + "output.LayerNorm.bias", _ident, True, _inv_ident),
        "pooler.w": _Src("pooler.dense.weight", _t2, invert=_inv_t2),
        "pooler.b": _Src("pooler.dense.bias", invert=_inv_ident),
        "classifier.w": _Src("classifier.weight", _t2, invert=_inv_t2),
        "classifier.b": _Src("classifier.bias", invert=_inv_ident),
    }


def _vit_specs(config) -> dict[str, _Src]:
    h = config.attention_spec.head_dim
    E = "embeddings."
    L = "encoder.layer.{i}."

    def patch_fetch(read: Callable, idx: tuple, shape: tuple) -> np.ndarray:
        # HF conv kernel (d, C, p, p) -> patchify matmul weight (p*p*C, d).
        # Patch rows are ordered (p, p, C) here (image unfolded HWC); torch
        # conv weight is (d, C, p, p) -> permute to (p, p, C, d) then flatten.
        i0, i1 = idx
        arr = read((i1, slice(None), slice(None), slice(None)))
        arr = np.transpose(arr, (2, 3, 1, 0)).reshape(-1, i1.stop - i1.start)
        return arr[i0]

    def patch_invert(arr: np.ndarray) -> np.ndarray:
        # (p*p*C, d) -> conv kernel (d, C, p, p)
        p_sz, C = config.patch_size, config.channels
        d = arr.shape[-1]
        return np.ascontiguousarray(
            arr.reshape(p_sz, p_sz, C, d).transpose(3, 2, 0, 1)
        )

    return {
        "patch_proj.w": _Src(E + "patch_embeddings.projection.weight", patch_fetch, invert=patch_invert),
        "patch_proj.b": _Src(E + "patch_embeddings.projection.bias", invert=_inv_ident),
        "cls_token": _Src(E + "cls_token", lambda r, i, s: r((slice(0, 1), slice(0, 1), i[0]))[0, 0],
                          invert=lambda a: a[None, None, :]),
        "pos_embed": _Src(E + "position_embeddings", lambda r, i, s: r((slice(0, 1), i[0], i[1]))[0],
                          invert=lambda a: a[None]),
        "lnf_scale": _Src("layernorm.weight", invert=_inv_ident),
        "lnf_bias": _Src("layernorm.bias", invert=_inv_ident),
        "blocks.ln1_scale": _Src(L + "layernorm_before.weight", _ident, True, _inv_ident),
        "blocks.ln1_bias": _Src(L + "layernorm_before.bias", _ident, True, _inv_ident),
        "blocks.ln2_scale": _Src(L + "layernorm_after.weight", _ident, True, _inv_ident),
        "blocks.ln2_bias": _Src(L + "layernorm_after.bias", _ident, True, _inv_ident),
        "blocks.attn.wq": _Src(L + "attention.attention.query.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.wk": _Src(L + "attention.attention.key.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.wv": _Src(L + "attention.attention.value.weight", _qkv(h), True, _inv_qkv),
        "blocks.attn.bq": _Src(L + "attention.attention.query.bias", _vec_heads(h), True, _inv_vec_heads),
        "blocks.attn.bk": _Src(L + "attention.attention.key.bias", _vec_heads(h), True, _inv_vec_heads),
        "blocks.attn.bv": _Src(L + "attention.attention.value.bias", _vec_heads(h), True, _inv_vec_heads),
        "blocks.attn.wo": _Src(L + "attention.output.dense.weight", _oproj(h), True, _inv_oproj),
        "blocks.attn.bo": _Src(L + "attention.output.dense.bias", _ident, True, _inv_ident),
        "blocks.mlp.w_in": _Src(L + "intermediate.dense.weight", _t2, True, _inv_t2),
        "blocks.mlp.b_in": _Src(L + "intermediate.dense.bias", _ident, True, _inv_ident),
        "blocks.mlp.w_out": _Src(L + "output.dense.weight", _t2, True, _inv_t2),
        "blocks.mlp.b_out": _Src(L + "output.dense.bias", _ident, True, _inv_ident),
        "head.w": _Src("classifier.weight", _t2, invert=_inv_t2),
        "head.b": _Src("classifier.bias", invert=_inv_ident),
    }


def _t5_specs(config) -> dict[str, _Src]:
    """T5 **v1.1** layout (gated-gelu `DenseGatedActDense`, untied head).
    The rel-bias tables live only on block 0 in HF; this framework keeps one
    shared table per stack, which is the same tensor."""
    h = config.head_dim
    E = "encoder.block.{i}.layer."
    D = "decoder.block.{i}.layer."
    m = {
        "embed": _Src("shared.weight", invert=_inv_ident),
        "enc_rel_bias": _Src(
            "encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight",
            invert=_inv_ident,
        ),
        "dec_rel_bias": _Src(
            "decoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight",
            invert=_inv_ident,
        ),
        "enc_final_norm": _Src("encoder.final_layer_norm.weight", _minus1, invert=_inv_plus1),
        "dec_final_norm": _Src("decoder.final_layer_norm.weight", _minus1, invert=_inv_plus1),
        "encoder.attn_norm": _Src(E + "0.layer_norm.weight", _minus1, True, _inv_plus1),
        "encoder.attn.wq": _Src(E + "0.SelfAttention.q.weight", _qkv(h), True, _inv_qkv),
        "encoder.attn.wk": _Src(E + "0.SelfAttention.k.weight", _qkv(h), True, _inv_qkv),
        "encoder.attn.wv": _Src(E + "0.SelfAttention.v.weight", _qkv(h), True, _inv_qkv),
        "encoder.attn.wo": _Src(E + "0.SelfAttention.o.weight", _oproj(h), True, _inv_oproj),
        "encoder.mlp_norm": _Src(E + "1.layer_norm.weight", _minus1, True, _inv_plus1),
        "encoder.mlp.w_gate": _Src(E + "1.DenseReluDense.wi_0.weight", _t2, True, _inv_t2),
        "encoder.mlp.w_up": _Src(E + "1.DenseReluDense.wi_1.weight", _t2, True, _inv_t2),
        "encoder.mlp.w_down": _Src(E + "1.DenseReluDense.wo.weight", _t2, True, _inv_t2),
        "decoder.self_norm": _Src(D + "0.layer_norm.weight", _minus1, True, _inv_plus1),
        "decoder.self_attn.wq": _Src(D + "0.SelfAttention.q.weight", _qkv(h), True, _inv_qkv),
        "decoder.self_attn.wk": _Src(D + "0.SelfAttention.k.weight", _qkv(h), True, _inv_qkv),
        "decoder.self_attn.wv": _Src(D + "0.SelfAttention.v.weight", _qkv(h), True, _inv_qkv),
        "decoder.self_attn.wo": _Src(D + "0.SelfAttention.o.weight", _oproj(h), True, _inv_oproj),
        "decoder.cross_norm": _Src(D + "1.layer_norm.weight", _minus1, True, _inv_plus1),
        "decoder.cross_attn.wq": _Src(D + "1.EncDecAttention.q.weight", _qkv(h), True, _inv_qkv),
        "decoder.cross_attn.wk": _Src(D + "1.EncDecAttention.k.weight", _qkv(h), True, _inv_qkv),
        "decoder.cross_attn.wv": _Src(D + "1.EncDecAttention.v.weight", _qkv(h), True, _inv_qkv),
        "decoder.cross_attn.wo": _Src(D + "1.EncDecAttention.o.weight", _oproj(h), True, _inv_oproj),
        "decoder.mlp_norm": _Src(D + "2.layer_norm.weight", _minus1, True, _inv_plus1),
        "decoder.mlp.w_gate": _Src(D + "2.DenseReluDense.wi_0.weight", _t2, True, _inv_t2),
        "decoder.mlp.w_up": _Src(D + "2.DenseReluDense.wi_1.weight", _t2, True, _inv_t2),
        "decoder.mlp.w_down": _Src(D + "2.DenseReluDense.wo.weight", _t2, True, _inv_t2),
    }
    if not config.tie_embeddings:
        m["lm_head"] = _Src("lm_head.weight", _t2, invert=_inv_t2)
    return m


_SPEC_BUILDERS: dict[str, Callable[[Any], dict[str, _Src]]] = {
    "llama": _llama_specs,
    "gpt": _gpt_specs,
    "bert": _bert_specs,
    "vit": _vit_specs,
    "t5": _t5_specs,
}


def hf_key_specs(family: str, config: Any) -> dict[str, _Src]:
    """The built-in leaf-path -> HF-tensor map for a model family."""
    try:
        return _SPEC_BUILDERS[family](config)
    except KeyError:
        raise ValueError(
            f"No built-in HF map for family {family!r}; known: "
            f"{sorted(_SPEC_BUILDERS)}. Use load_checkpoint_and_dispatch "
            "with an explicit key_map instead."
        ) from None


# ------------------------------------------------------------ config parsing
def _num_labels(config: dict, default: int = 2) -> int:
    """transformers serializes num_labels as the id2label map."""
    if "num_labels" in config:
        return config["num_labels"]
    if config.get("id2label"):
        return len(config["id2label"])
    return default


def resolve_repo(path_or_id: str) -> str:
    """Local directory/file passthrough, or Hugging Face Hub id resolution
    (reference `create_empty_model` accepts Hub names, `commands/estimate.py:64`).

    Hub ids resolve cache-first (`snapshot_download(local_files_only=True)`
    — works fully offline against a pre-populated HF_HUB_CACHE), then via
    the network; both failing raises with the pre-download remedy."""
    path = os.fspath(path_or_id)
    if os.path.exists(path):
        return path
    # Hub ids look like "org/name" (or bare "name"): no absolute/relative
    # filesystem syntax.
    if path.startswith((".", "/", "~")) or path.count("/") > 1:
        raise ValueError(f"checkpoint path {path!r} does not exist")
    try:
        from huggingface_hub import snapshot_download
    except ImportError as e:
        raise ValueError(
            f"{path!r} is not a local directory and huggingface_hub is not "
            "installed to resolve it as a Hub id."
        ) from e
    patterns = ["*.safetensors", "*.safetensors.index.json", "config.json"]
    # huggingface_hub latches HF_HUB_CACHE at import; read the env at call
    # time so per-process/per-test cache dirs work.
    cache_dir = os.environ.get("HF_HUB_CACHE") or None
    try:
        return snapshot_download(
            path, allow_patterns=patterns, local_files_only=True,
            cache_dir=cache_dir,
        )
    except Exception:
        pass
    try:
        return snapshot_download(path, allow_patterns=patterns, cache_dir=cache_dir)
    except Exception as e:
        raise ValueError(
            f"{path!r} is not a local directory, is not in the local Hub "
            f"cache, and could not be downloaded ({type(e).__name__}: {e}). "
            "In an air-gapped environment, pre-download with "
            f"`huggingface-cli download {path}` on a connected machine and "
            "point HF_HUB_CACHE at the result, or pass a local repo path."
        ) from e


def _parse_rope_scaling(rs: dict | None, RopeScaling: Any) -> Any:
    """HF ``rope_scaling`` dict -> layers.RopeScaling (or None).

    Implements the two schemes real llama-family checkpoints ship:
    ``llama3`` (every Llama-3.1/3.2 repo) and ``linear`` position
    interpolation; anything else (yarn, dynamic-NTK, longrope) still fails
    loudly — those change the frequency tables per sequence length and are
    not implemented."""
    if rs is None:
        return None
    rtype = rs.get("rope_type") or rs.get("type") or "default"
    if rtype == "default":
        return None
    if rtype == "llama3":
        return RopeScaling(
            rope_type="llama3",
            factor=float(rs["factor"]),
            low_freq_factor=float(rs.get("low_freq_factor", 1.0)),
            high_freq_factor=float(rs.get("high_freq_factor", 4.0)),
            original_max_position_embeddings=int(
                rs.get("original_max_position_embeddings", 8192)
            ),
        )
    if rtype == "linear":
        return RopeScaling(rope_type="linear", factor=float(rs["factor"]))
    raise ValueError(
        f"This checkpoint uses rope_scaling rope_type={rtype!r}; implemented "
        "types: 'llama3' (Llama-3.1+), 'linear'. Loading with plain RoPE "
        "would silently diverge from the original model."
    )


def from_hf_config(config: Any) -> tuple[str, Any]:
    """Translate an HF ``config.json`` (dict, file path, or repo dir) into
    ``(family, FamilyConfig)`` for this framework's model zoo."""
    if isinstance(config, (str, os.PathLike)):
        path = os.fspath(config)
        if path.endswith(".json"):
            if not os.path.exists(path):
                raise ValueError(f"checkpoint config {path!r} does not exist")
        else:
            path = resolve_repo(path)
        if os.path.isdir(path):
            path = os.path.join(path, "config.json")
        with open(path) as f:
            config = json.load(f)
    mt = config.get("model_type")
    if mt in ("llama", "mistral", "mixtral", "qwen2"):
        from .layers import RopeScaling
        from .llama import LlamaConfig

        # Refuse architecture-affecting knobs this family doesn't implement:
        # loading would succeed but every forward pass would silently diverge
        # from transformers' output — the opposite of the parity contract.
        # (hidden_act is validated for the same reason: a llama variant with
        # hidden_act="gelu" would load cleanly and silently diverge.)
        act = config.get("hidden_act", "silu")
        if act != "silu":
            raise ValueError(
                f"This llama-family checkpoint uses hidden_act={act!r}; the "
                "block here hardwires the standard silu/swiglu MLP — logits "
                "would silently diverge if the activation were substituted."
            )
        rope_scaling = _parse_rope_scaling(config.get("rope_scaling"), RopeScaling)
        # Community llama variants can carry q/k/v/o and MLP biases
        # (LlamaConfig.attention_bias / mlp_bias); the block here models
        # q/k/v biases only in the qwen2 layout — anything else would load
        # with silently dropped tensors.
        if mt != "qwen2" and config.get("attention_bias"):
            raise ValueError(
                "This llama-family checkpoint sets attention_bias=true "
                "(biases on q/k/v/o projections); only the qwen2 bias "
                "layout (q/k/v, no o_proj bias) is implemented — logits "
                "would silently diverge if the biases were dropped."
            )
        if config.get("mlp_bias"):
            raise ValueError(
                "This checkpoint sets mlp_bias=true; the llama family here "
                "has bias-free MLPs — loading would silently drop tensors."
            )
        sliding = config.get("sliding_window")
        if mt == "qwen2":
            # HF qwen2 applies the window only to layers i >= max_window_layers
            # (layer_types in Qwen2Config; default 28). Uniform SWA therefore
            # means max_window_layers == 0; max_window_layers >= num layers
            # means NO layer uses it (full attention everywhere).
            mwl = config.get("max_window_layers", 28)
            if not config.get("use_sliding_window", False):
                sliding = None  # qwen2 ships the field but disables the feature
            elif mwl >= config["num_hidden_layers"]:
                sliding = None  # window enabled but banded past the last layer
            elif mwl != 0:
                # A mixed schedule (full attention below mwl, SWA above) would
                # silently diverge on one band or the other; this family
                # applies one attention pattern uniformly.
                raise ValueError(
                    "This qwen2 checkpoint enables sliding-window attention "
                    f"on a subset of layers (max_window_layers={mwl} of "
                    f"{config['num_hidden_layers']}); only uniform windows "
                    "(max_window_layers=0) are implemented."
                )
        if mt == "mixtral" and sliding:
            # Mixtral-8x7B-v0.1 ships sliding_window=4096 in some revisions
            # but the released model was trained (and is served by
            # transformers) with full attention when the context fits; the
            # window composes with MoE untested here, so refuse loudly.
            raise ValueError(
                "sliding_window on a mixtral checkpoint is not supported "
                "(the MoE block + window composition is untested); edit the "
                "config to sliding_window=null if the model was trained "
                "with full attention."
            )

        return "llama", LlamaConfig(
            vocab_size=config["vocab_size"],
            d_model=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config.get(
                "num_key_value_heads", config["num_attention_heads"]
            ),
            d_ff=config["intermediate_size"],
            head_dim=config.get("head_dim"),
            max_seq_len=config.get("max_position_embeddings", 8192),
            rope_theta=config.get("rope_theta", 10000.0),
            rope_scaling=rope_scaling,
            sliding_window=sliding,
            norm_eps=config.get("rms_norm_eps", 1e-5),
            tie_embeddings=config.get("tie_word_embeddings", False),
            # Qwen2 = llama block + q/k/v biases.
            attn_bias=(mt == "qwen2"),
            # Mixtral: routed experts replace every block's FFN. A capacity
            # factor of E/k removes dropping entirely, matching HF's
            # capacity-free routing exactly (ops/moe.py renormalizes kept
            # gates the same way Mixtral softmaxes over the top-k).
            n_experts=config.get("num_local_experts", 0),
            moe_top_k=config.get("num_experts_per_tok", 2),
            moe_capacity_factor=(
                config["num_local_experts"] / config.get("num_experts_per_tok", 2)
                if config.get("num_local_experts")
                else 1.25
            ),
        )
    if mt == "gpt2":
        from .gpt import GPTConfig

        act = config.get("activation_function", "gelu_new")
        if act != "gelu_new":
            raise ValueError(
                f"This GPT-2 checkpoint uses activation_function={act!r}; "
                "the block here hardwires gelu_new (the tanh approximation) "
                "— logits would silently diverge otherwise."
            )
        d = config["n_embd"]
        return "gpt", GPTConfig(
            vocab_size=config["vocab_size"],
            d_model=d,
            n_layers=config["n_layer"],
            num_heads=config["n_head"],
            d_ff=config.get("n_inner") or 4 * d,
            max_seq_len=config.get("n_positions", 1024),
            norm_eps=config.get("layer_norm_epsilon", 1e-5),
            tie_embeddings=config.get("tie_word_embeddings", True),
        )
    if mt == "gpt_neox":
        from .gpt import GPTConfig

        act = {"gelu": "gelu", "gelu_new": "gelu_new", "gelu_fast": "gelu_new"}.get(
            config.get("hidden_act", "gelu")
        )
        if act is None:
            raise ValueError(
                f"This GPT-NeoX checkpoint uses hidden_act="
                f"{config.get('hidden_act')!r}; implemented: gelu, gelu_new, "
                "gelu_fast — logits would silently diverge otherwise."
            )
        rs = config.get("rope_scaling")
        if rs and (rs.get("rope_type") or rs.get("type") or "default") != "default":
            raise ValueError(
                "rope_scaling on a GPT-NeoX checkpoint is not implemented "
                "for this family (no released NeoX-lineage checkpoint ships "
                "one); loading with unscaled rotary would silently diverge."
            )
        d = config["hidden_size"]
        head_dim = d // config["num_attention_heads"]
        return "gpt", GPTConfig(
            vocab_size=config["vocab_size"],
            d_model=d,
            n_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            d_ff=config["intermediate_size"],
            max_seq_len=config.get("max_position_embeddings", 2048),
            norm_eps=config.get("layer_norm_eps", 1e-5),
            tie_embeddings=config.get("tie_word_embeddings", False),
            hf_layout="gpt_neox",
            positional="rotary",
            # 0.25 is GPTNeoXConfig's default — an omitted rotary_pct means
            # quarter-head rotary, not full-head.
            rotary_dim=int(head_dim * config.get("rotary_pct", 0.25)),
            rope_theta=float(
                config.get("rotary_emb_base", config.get("rope_theta", 10000.0))
            ),
            parallel_residual=config.get("use_parallel_residual", True),
            activation=act,
            attn_bias=config.get("attention_bias", True),
        )
    if mt == "gptj":
        from .gpt import GPTConfig

        act = config.get("activation_function", "gelu_new")
        if act not in ("gelu_new", "gelu_fast"):
            raise ValueError(
                f"This GPT-J checkpoint uses activation_function={act!r}; "
                "the family hardwires gelu_new — logits would silently "
                "diverge otherwise."
            )
        d = config["n_embd"]
        tie = config.get("tie_word_embeddings", False)
        # 64 is GPTJConfig's default when the key is omitted; an EXPLICIT
        # null selects a transformers code path whose table sizing is tied
        # to embed_dim (broken for multi-head) — refuse rather than guess.
        rotary_dim = config.get("rotary_dim", 64)
        if rotary_dim is None:
            raise ValueError(
                "This GPT-J checkpoint sets rotary_dim=null; the "
                "full-embedding rotary path is not implemented — set the "
                "trained rotary_dim explicitly."
            )
        return "gpt", GPTConfig(
            vocab_size=config["vocab_size"],
            d_model=d,
            n_layers=config["n_layer"],
            num_heads=config["n_head"],
            d_ff=config.get("n_inner") or 4 * d,
            max_seq_len=config.get("n_positions", 2048),
            norm_eps=config.get("layer_norm_epsilon", 1e-5),
            tie_embeddings=tie,
            hf_layout="gptj",
            positional="rotary",
            rotary_dim=rotary_dim,
            rotary_interleaved=True,
            parallel_residual=True,
            shared_parallel_norm=True,
            attn_bias=False,
            head_bias=not tie,
        )
    if mt == "opt":
        from .gpt import GPTConfig

        # The 350m checkpoint (post-LN + a d_model!=word_embed_proj_dim
        # projection) and the bias-free research variants change the block
        # structure itself; loading them into this layout would silently
        # diverge, so they fail loudly.
        if not config.get("do_layer_norm_before", True):
            raise ValueError(
                "This OPT checkpoint uses post-layernorm blocks "
                "(do_layer_norm_before=false, the 350m layout); only the "
                "pre-LN layout is implemented."
            )
        if config.get("word_embed_proj_dim", config["hidden_size"]) != config["hidden_size"]:
            raise ValueError(
                "This OPT checkpoint projects embeddings "
                f"(word_embed_proj_dim={config['word_embed_proj_dim']} != "
                f"hidden_size={config['hidden_size']}); the projection "
                "layers are not implemented."
            )
        if not config.get("enable_bias", True) or not config.get(
            "layer_norm_elementwise_affine", True
        ):
            raise ValueError(
                "This OPT checkpoint disables projection biases or affine "
                "layernorms; only the standard released layout is implemented."
            )
        if config.get("_remove_final_layer_norm"):
            raise ValueError(
                "This OPT checkpoint sets _remove_final_layer_norm (a "
                "pre-release conversion quirk); re-convert with a current "
                "transformers before loading."
            )
        act = config.get("activation_function", "relu")
        if act not in ("relu", "gelu", "gelu_new"):
            raise ValueError(
                f"This OPT checkpoint uses activation_function={act!r}; "
                "implemented: relu, gelu, gelu_new."
            )
        return "gpt", GPTConfig(
            vocab_size=config["vocab_size"],
            d_model=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            d_ff=config["ffn_dim"],
            max_seq_len=config.get("max_position_embeddings", 2048),
            # torch nn.LayerNorm default — OPT has no eps config field.
            norm_eps=1e-5,
            tie_embeddings=config.get("tie_word_embeddings", True),
            hf_layout="opt",
            activation=act,
        )
    if mt == "bert":
        from .bert import BertConfig

        act = config.get("hidden_act", "gelu")
        if act != "gelu":
            raise ValueError(
                f"This BERT checkpoint uses hidden_act={act!r}; the block "
                "here hardwires the exact-erf gelu — logits would silently "
                "diverge otherwise."
            )
        return "bert", BertConfig(
            vocab_size=config["vocab_size"],
            d_model=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            d_ff=config["intermediate_size"],
            max_seq_len=config.get("max_position_embeddings", 512),
            type_vocab_size=config.get("type_vocab_size", 2),
            norm_eps=config.get("layer_norm_eps", 1e-12),
            num_labels=_num_labels(config),
        )
    if mt == "vit":
        from .vit import ViTConfig

        act = config.get("hidden_act", "gelu")
        if act != "gelu":
            raise ValueError(
                f"This ViT checkpoint uses hidden_act={act!r}; the block "
                "here hardwires the exact-erf gelu — logits would silently "
                "diverge otherwise."
            )
        return "vit", ViTConfig(
            image_size=config.get("image_size", 224),
            patch_size=config.get("patch_size", 16),
            d_model=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            d_ff=config["intermediate_size"],
            norm_eps=config.get("layer_norm_eps", 1e-12),
            num_classes=_num_labels(config),
        )
    if mt == "t5":
        from .t5 import T5Config

        ff_proj = config.get("feed_forward_proj", "relu")
        if ff_proj != "gated-gelu":
            raise ValueError(
                f"This T5 checkpoint uses feed_forward_proj={ff_proj!r}; the "
                "t5 family here implements the v1.1 gated-gelu layout only "
                "(ungated relu and gated-silu would silently diverge) — use "
                "a google/t5-v1_1-* style checkpoint."
            )
        return "t5", T5Config(
            vocab_size=config["vocab_size"],
            d_model=config["d_model"],
            n_encoder_layers=config["num_layers"],
            n_decoder_layers=config.get("num_decoder_layers", config["num_layers"]),
            num_heads=config["num_heads"],
            head_dim=config["d_kv"],
            d_ff=config["d_ff"],
            rel_buckets=config.get("relative_attention_num_buckets", 32),
            rel_max_distance=config.get("relative_attention_max_distance", 128),
            norm_eps=config.get("layer_norm_epsilon", 1e-6),
            tie_embeddings=config.get("tie_word_embeddings", True),
        )
    if mt == "smallthinker":
        from .smallthinker import SmallThinkerConfig

        # The config maps; a checkpoint's tensor names are not known here,
        # so `load_pretrained` has no key specs for this family.
        if not config.get("moe_primary_router_apply_softmax", True):
            raise ValueError(
                "This smallthinker config routes without a softmax "
                "(moe_primary_router_apply_softmax=false); only the softmax "
                "router is implemented."
            )
        if config.get("rope_scaling"):
            raise ValueError("rope_scaling is not implemented for the smallthinker family")
        n_layers = config["num_hidden_layers"]

        def layout(key: str, default: int) -> tuple[int, ...]:
            # A depth-cut config keeps the published per-layer layout whole:
            # its first num_hidden_layers entries apply.
            entries = tuple(config.get(key) or (default,) * n_layers)
            if len(entries) < n_layers:
                raise ValueError(f"{key} has {len(entries)} entries for {n_layers} layers")
            return entries[:n_layers]

        return "smallthinker", SmallThinkerConfig(
            vocab_size=config["vocab_size"],
            d_model=config["hidden_size"],
            n_layers=n_layers,
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"],
            n_experts=config["moe_num_primary_experts"],
            moe_top_k=config["moe_num_active_primary_experts"],
            d_expert=config["moe_ffn_hidden_size"],
            norm_topk_prob=bool(config.get("norm_topk_prob", True)),
            sliding_window=config["sliding_window_size"],
            window_layout=layout("sliding_window_layout", 0),
            rope_layout=layout("rope_layout", 1),
            max_seq_len=config["max_position_embeddings"],
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
            tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        )
    if mt == "olmo_hybrid":
        from .olmo_hybrid import FULL, LINEAR, OlmoHybridConfig

        # The config maps; a checkpoint's tensor names are not known here,
        # so `load_pretrained` has no key specs for this family. What the
        # family does not implement is refused by the key that asks for it.
        if not config.get("linear_allow_neg_eigval", False):
            raise ValueError(
                "linear_allow_neg_eigval=false (beta in (0, 1)) is not implemented for the "
                "olmo_hybrid family: its write strength is beta = 2 sigmoid(b), in (0, 2)"
            )
        heads = config["linear_num_key_heads"]
        if config["linear_num_value_heads"] != heads:
            raise ValueError(
                f"linear_num_value_heads ({config['linear_num_value_heads']}) differs from "
                f"linear_num_key_heads ({heads}): grouped value heads are not implemented"
            )
        rope = config.get("rope_parameters") or {}
        if rope.get("rope_theta") is not None or config.get("rope_theta") is not None:
            raise ValueError(
                "a rotary setting (rope_parameters.rope_theta / rope_theta) is not implemented "
                "for the olmo_hybrid family: its full-attention layers carry no rotary term"
            )
        if config.get("attention_bias"):
            raise ValueError("attention_bias=true is not implemented for the olmo_hybrid family")
        if config.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act {config['hidden_act']!r} is not implemented for the olmo_hybrid family")
        n_layers = config["num_hidden_layers"]
        # A depth-cut config keeps the published per-layer layout whole: its
        # first num_hidden_layers entries apply.
        kinds = tuple(config["layer_types"])[:n_layers]
        if len(kinds) < n_layers or set(kinds) - {LINEAR, FULL}:
            raise ValueError(f"layer_types must name {n_layers} layers of {LINEAR!r} / {FULL!r}")
        return "olmo_hybrid", OlmoHybridConfig(
            vocab_size=config["vocab_size"],
            d_model=config["hidden_size"],
            d_ff=config["intermediate_size"],
            n_layers=n_layers,
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"],
            layer_types=kinds,
            linear_heads=heads,
            linear_key_dim=config["linear_key_head_dim"],
            linear_value_dim=config["linear_value_head_dim"],
            conv_kernel=config["linear_conv_kernel_dim"],
            max_seq_len=config["max_position_embeddings"],
            norm_eps=float(config["rms_norm_eps"]),
            tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        )
    raise ValueError(
        f"Unsupported HF model_type {mt!r}; supported: llama, mistral, "
        "mixtral, qwen2, smallthinker (config only), olmo_hybrid (config only), gpt2, gpt_neox, gptj, "
        "opt, bert, vit, t5 (v1.1 gated layout)."
    )


# --------------------------------------------------------------- entry point
class PretrainedModel(NamedTuple):
    family: str
    config: Any
    params: Params
    plan: Any


def load_pretrained(
    path: str,
    *,
    mesh=None,
    dtype: Any | None = None,
    hbm_budget: int | None = None,
    rules: Any = None,
    min_weight_size: int = 2**11,
    no_offload_patterns=(),
    quantize_bits: int | None = None,
    offload_dir: str | None = None,
) -> PretrainedModel:
    """One-call HF repo ingestion: ``config.json`` -> family config, plan
    shardings, stream weights (reference `load_checkpoint_and_dispatch`
    ergonomics, `big_modeling.py:511`, with the key map built in).

    ``path`` is a local HF repo directory (``config.json`` plus
    ``*.safetensors`` / ``*.safetensors.index.json``). ``dtype`` casts on
    the fly (e.g. ``jnp.bfloat16`` for inference deploys). ``rules``
    defaults to the family's registered TP plan (`parallel/tp.py`) so the
    params land sharded over whatever mesh axes exist — pass ``rules=()``
    explicitly to replicate instead. Leaves the plan offloads stay
    host-resident numpy, ready for `streamed_scan`.

    ``quantize_bits=8|4`` quantizes the big matmul weights ON THE WAY IN
    (the `load_and_quantize_model` analog, reference `utils/bnb.py`): each
    leaf is streamed to host, packed to int8/int4 with per-channel scales
    there, and only the packed values reach HBM — an 8B bf16 repo loads
    into ≈8/4 GiB of device memory without the full-precision weights ever
    being resident. Embeddings/norms/heads stay full precision
    (`utils/quantization.DEFAULT_SKIP_PATTERNS`); the model families
    dequantize per layer inside their scan.
    """
    from .. import models
    from ..big_modeling import infer_sharding_plan

    if mesh is None:
        from ..state import AcceleratorState

        mesh = AcceleratorState().mesh

    path = resolve_repo(path)
    family, config = from_hf_config(path)
    if rules is None:
        from ..parallel.tp import get_tp_plan

        rules = get_tp_plan(family)
    module = getattr(models, family)
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), config))
    plan = infer_sharding_plan(
        shapes,
        mesh,
        hbm_budget=hbm_budget,
        rules=rules,
        dtype=dtype,
        no_offload_patterns=no_offload_patterns,
        min_weight_size=min_weight_size,
    )
    params = load_hf_checkpoint(
        shapes, path, plan, family=family, config=config, dtype=dtype,
        quantize_bits=quantize_bits, offload_dir=offload_dir,
    )
    return PretrainedModel(family, config, params, plan)


def _make_quantize_override(plan, bits):
    """leaf_override for `dispatch_leaves`: pack eligible weights on the
    host, ship only int8/int4 + scales to device (specs sanitized to the
    packed shapes). Stacked leaves quantize ONE stack slice at a time —
    scales are per-slice, so the result is identical, but the transient
    host buffer is a single layer's worth instead of 3x the whole leaf.
    Leaves the plan offloads keep the normal host-resident bf16 path
    (`streamed_scan` owns their lifecycle)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.sharding import _path_str, _sanitize_spec
    from ..utils.quantization import leaf_quant_plan, quantize_array_host

    spec_by_key: dict[str, Any] = {}

    def spec_for(key):
        if not spec_by_key:
            leaves, _ = jax.tree_util.tree_flatten_with_path(
                plan.specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
            )
            for p, s in leaves:
                spec_by_key[_path_str(p)] = s
        return spec_by_key[key]

    def quantize_streaming(leaf, fetch, stack):
        shape = tuple(leaf.shape)
        if stack is None and leaf.ndim >= 3:
            stack = 1
        if not stack:
            full = fetch(tuple(slice(0, d) for d in shape))
            return quantize_array_host(np.asarray(full), stack_dims=0, bits=bits)
        out: dict[str, np.ndarray] = {}
        for i in range(shape[0]):
            idx = (slice(i, i + 1),) + tuple(slice(0, d) for d in shape[1:])
            part = quantize_array_host(
                np.asarray(fetch(idx)), stack_dims=stack, bits=bits
            )
            for name, arr in part.items():
                if name not in out:
                    out[name] = np.empty((shape[0],) + arr.shape[1:], arr.dtype)
                out[name][i] = arr[0]
        return out

    def override(plan_key, leaf, fetch):
        if plan_key in plan.offload:
            return None
        eligible, stack = leaf_quant_plan(plan_key, tuple(leaf.shape), leaf.dtype)
        if not eligible:
            return None

        # (host_fn, place_fn) pair: dispatch_leaves runs the read+pack on
        # its IO worker and the place stage on the transfer engine's
        # pool, overlapped with the previous leaf's device traffic.
        def host_fn():
            return quantize_streaming(leaf, fetch, stack)

        def place_fn(packed):
            spec = spec_for(plan_key)
            shardings = {
                name: NamedSharding(
                    plan.mesh, _sanitize_spec(spec, arr.shape, plan.mesh, path=plan_key)
                )
                for name, arr in packed.items()
            }
            # One pytree transfer per leaf: values + scales ride a single
            # device_put call instead of paying the link's per-call
            # overhead once per array (runs on a transfer-engine worker,
            # so packed leaves stream concurrently).
            return jax.device_put(packed, shardings)

        return host_fn, place_fn

    return override


def load_hf_checkpoint(
    shapes: Any,
    path: str,
    plan: Any,
    *,
    family: str,
    config: Any,
    dtype: Any | None = None,
    quantize_bits: int | None = None,
    offload_dir: str | None = None,
) -> Params:
    """Stream an HF-named checkpoint into sharded device buffers per
    ``plan`` using the built-in family map (the key-mapped sibling of
    `load_checkpoint_and_dispatch`; both ride
    `big_modeling.dispatch_leaves`)."""
    from ..big_modeling import _open_source, dispatch_leaves

    specs_map = hf_key_specs(family, config)
    source = _open_source(path)
    available = set(source.keys())
    _resolved: dict[str, str] = {}

    def resolve(name: str) -> str:
        """Map a canonical tensor name to the checkpoint's actual key. HF
        task wrappers prefix the backbone (``transformer.`` for
        GPT2LMHeadModel, ``bert.``/``vit.`` for classification heads); a
        unique suffix match absorbs the prefix without hardcoding it."""
        hit = _resolved.get(name)
        if hit is not None:
            return hit
        if name in available:
            _resolved[name] = name
            return name
        cands = [k for k in available if k.endswith("." + name)]
        if len(cands) == 1:
            _resolved[name] = cands[0]
            return cands[0]
        raise KeyError(
            f"Checkpoint at {path!r} has no tensor {name!r} "
            f"({'ambiguous: ' + str(cands) if cands else 'no suffix match'})."
        )

    def make_fetch(plan_key: str, leaf: Any):
        # Plan paths are '/'-joined; the maps here use '.' (HF style).
        key = plan_key.replace("/", ".")
        if key not in specs_map:
            raise KeyError(
                f"No HF mapping for model leaf {key!r} (family "
                f"{family!r}). Mapped leaves: {sorted(specs_map)}"
            )
        src = specs_map[key]
        # Resolve every needed tensor up front so a truncated repo (config
        # promising more layers than the weights hold) fails loudly before
        # any device allocation.
        if src.per_layer:
            for i in range(int(leaf.shape[0])):
                if src.per_expert:
                    for e in range(int(leaf.shape[1])):
                        resolve(src.key.format(i=i, e=e))
                else:
                    resolve(src.key.format(i=i))
        else:
            resolve(src.key)
        shape = tuple(leaf.shape)

        def read_for(name: str):
            return lambda s_idx, _k=resolve(name): np.asarray(
                source.read_slice(_k, tuple(s_idx))
            )

        def fetch_host(idx: tuple, _src=src, _shape=shape) -> np.ndarray:
            idx = _norm_idx(idx, _shape)
            if _src.per_layer:
                layers = idx[0]
                sub_idx, sub_shape = idx[1:], _shape[1:]
                planes = []
                for i in range(layers.start, layers.stop):
                    if _src.per_expert:
                        experts = sub_idx[0]
                        e_planes = [
                            _src.fetch(
                                read_for(_src.key.format(i=i, e=e)),
                                sub_idx[1:],
                                sub_shape[1:],
                            )
                            for e in range(experts.start, experts.stop)
                        ]
                        planes.append(np.stack(e_planes))
                    else:
                        planes.append(
                            _src.fetch(
                                read_for(_src.key.format(i=i)), sub_idx, sub_shape
                            )
                        )
                return np.stack(planes)
            return _src.fetch(read_for(_src.key), idx, _shape)

        return fetch_host

    try:
        return dispatch_leaves(
            shapes,
            plan,
            make_fetch,
            dtype=dtype,
            leaf_override=(
                _make_quantize_override(plan, quantize_bits)
                if quantize_bits
                else None
            ),
            offload_dir=offload_dir,
            source_id=(
                __import__("accelerate_tpu.big_modeling", fromlist=["source_fingerprint"]).source_fingerprint(path)
                if offload_dir
                else ""
            ),
        )
    finally:
        source.close()


# ----------------------------------------------------------------- export
def config_to_hf(family: str, config: Any, *, torch_dtype: str = "float32") -> dict:
    """Family config -> HF ``config.json`` payload (inverse of
    `from_hf_config`) for every exportable family."""
    if family == "llama":
        qwen = getattr(config, "attn_bias", False)
        sliding = getattr(config, "sliding_window", None)
        moe = getattr(config, "n_experts", 0)
        if moe:
            mt, arch = "mixtral", "MixtralForCausalLM"
        elif qwen:
            mt, arch = "qwen2", "Qwen2ForCausalLM"
        elif sliding is not None:
            # LlamaConfig (HF) has no sliding_window field; exporting a
            # windowed model as model_type=llama would silently drop the
            # window on reload. Mistral is the HF family with this layout.
            mt, arch = "mistral", "MistralForCausalLM"
        else:
            mt, arch = "llama", "LlamaForCausalLM"
        out = {
            "model_type": mt,
            "architectures": [arch],
            "vocab_size": config.vocab_size,
            "hidden_size": config.d_model,
            "intermediate_size": config.d_ff,
            "num_hidden_layers": config.n_layers,
            "num_attention_heads": config.num_heads,
            "num_key_value_heads": config.num_kv_heads,
            "head_dim": config.resolved_head_dim,
            "max_position_embeddings": config.max_seq_len,
            "rope_theta": config.rope_theta,
            "rms_norm_eps": config.norm_eps,
            "tie_word_embeddings": config.tie_embeddings,
            "hidden_act": "silu",
            "torch_dtype": torch_dtype,
        }
        if moe:
            out["num_local_experts"] = config.n_experts
            out["num_experts_per_tok"] = config.moe_top_k
        rs = getattr(config, "rope_scaling", None)
        if rs is not None:
            payload = {"rope_type": rs.rope_type, "factor": rs.factor}
            if rs.rope_type == "llama3":
                payload.update(
                    low_freq_factor=rs.low_freq_factor,
                    high_freq_factor=rs.high_freq_factor,
                    original_max_position_embeddings=rs.original_max_position_embeddings,
                )
            out["rope_scaling"] = payload
        if sliding is not None:
            out["sliding_window"] = sliding
            if qwen:
                out["use_sliding_window"] = True
                # 0 = every layer windowed (HF windows layers >= this index);
                # n_layers here would silently disable SWA on reload.
                out["max_window_layers"] = 0
        return out
    if family == "bert":
        return {
            "model_type": "bert",
            "architectures": ["BertForSequenceClassification"],
            "vocab_size": config.vocab_size,
            "hidden_size": config.d_model,
            "num_hidden_layers": config.n_layers,
            "num_attention_heads": config.num_heads,
            "intermediate_size": config.d_ff,
            "max_position_embeddings": config.max_seq_len,
            "type_vocab_size": config.type_vocab_size,
            "layer_norm_eps": config.norm_eps,
            "num_labels": config.num_labels,
            "id2label": {str(i): f"LABEL_{i}" for i in range(config.num_labels)},
            "torch_dtype": torch_dtype,
        }
    if family == "vit":
        return {
            "model_type": "vit",
            "architectures": ["ViTForImageClassification"],
            "image_size": config.image_size,
            "patch_size": config.patch_size,
            "hidden_size": config.d_model,
            "num_hidden_layers": config.n_layers,
            "num_attention_heads": config.num_heads,
            "intermediate_size": config.d_ff,
            "num_channels": config.channels,
            "layer_norm_eps": config.norm_eps,
            "num_labels": config.num_classes,
            "id2label": {str(i): f"LABEL_{i}" for i in range(config.num_classes)},
            "torch_dtype": torch_dtype,
        }
    if family == "t5":
        return {
            "model_type": "t5",
            "architectures": ["T5ForConditionalGeneration"],
            "vocab_size": config.vocab_size,
            "d_model": config.d_model,
            "d_kv": config.head_dim,
            "d_ff": config.d_ff,
            "num_layers": config.n_encoder_layers,
            "num_decoder_layers": config.n_decoder_layers,
            "num_heads": config.num_heads,
            "relative_attention_num_buckets": config.rel_buckets,
            "relative_attention_max_distance": config.rel_max_distance,
            "layer_norm_epsilon": config.norm_eps,
            "feed_forward_proj": "gated-gelu",
            "tie_word_embeddings": config.tie_embeddings,
            "is_encoder_decoder": True,
            "torch_dtype": torch_dtype,
        }
    if family == "gpt":
        layout = getattr(config, "hf_layout", "gpt2")
        if layout == "gpt2":
            return {
                "model_type": "gpt2",
                "architectures": ["GPT2LMHeadModel"],
                "vocab_size": config.vocab_size,
                "n_embd": config.d_model,
                "n_layer": config.n_layers,
                "n_head": config.num_heads,
                "n_inner": config.d_ff,
                "n_positions": config.max_seq_len,
                "n_ctx": config.max_seq_len,
                # The true trained activation, not a hardwired default — a
                # mislabeled config.json reloads with the wrong ACT2FN and
                # silently diverges.
                "activation_function": config.activation,
                "layer_norm_epsilon": config.norm_eps,
                "tie_word_embeddings": config.tie_embeddings,
                "torch_dtype": torch_dtype,
            }
        if layout == "gpt_neox":
            return {
                "model_type": "gpt_neox",
                "architectures": ["GPTNeoXForCausalLM"],
                "vocab_size": config.vocab_size,
                "hidden_size": config.d_model,
                "num_hidden_layers": config.n_layers,
                "num_attention_heads": config.num_heads,
                "intermediate_size": config.d_ff,
                "max_position_embeddings": config.max_seq_len,
                "rotary_pct": config.resolved_rotary_dim / config.head_dim,
                "rotary_emb_base": config.rope_theta,
                "hidden_act": config.activation,
                "use_parallel_residual": config.parallel_residual,
                "attention_bias": config.attn_bias,
                "layer_norm_eps": config.norm_eps,
                "tie_word_embeddings": config.tie_embeddings,
                "torch_dtype": torch_dtype,
            }
        if layout == "gptj":
            return {
                "model_type": "gptj",
                "architectures": ["GPTJForCausalLM"],
                "vocab_size": config.vocab_size,
                "n_embd": config.d_model,
                "n_layer": config.n_layers,
                "n_head": config.num_heads,
                "n_inner": config.d_ff,
                "n_positions": config.max_seq_len,
                "rotary_dim": config.resolved_rotary_dim,
                "activation_function": config.activation,
                "layer_norm_epsilon": config.norm_eps,
                "tie_word_embeddings": config.tie_embeddings,
                "torch_dtype": torch_dtype,
            }
        if layout == "opt":
            return {
                "model_type": "opt",
                "architectures": ["OPTForCausalLM"],
                "vocab_size": config.vocab_size,
                "hidden_size": config.d_model,
                "num_hidden_layers": config.n_layers,
                "num_attention_heads": config.num_heads,
                "ffn_dim": config.d_ff,
                "max_position_embeddings": config.max_seq_len,
                "word_embed_proj_dim": config.d_model,
                "do_layer_norm_before": True,
                "activation_function": config.activation,
                "tie_word_embeddings": config.tie_embeddings,
                "torch_dtype": torch_dtype,
            }
        raise ValueError(f"config_to_hf has no branch for gpt layout {layout!r}.")
    raise ValueError(f"config_to_hf has no branch for family {family!r}.")


def save_pretrained(
    path: str,
    family: str,
    config: Any,
    params: Params,
    *,
    max_shard_bytes: int = 4 << 30,
) -> str:
    """Export params to an HF-layout repo (``config.json`` + sharded
    safetensors with HF tensor names + ``model.safetensors.index.json``) —
    the return leg of the migration loop: a model trained here loads in
    `transformers.AutoModel.from_pretrained` unchanged. Inverse of
    `load_pretrained`; round-trip parity is tested against transformers.

    Quantized params must be dequantized first
    (`utils.quantization.dequantize_pytree`)."""
    from ..utils.quantization import has_quantized

    if has_quantized(params):
        raise ValueError(
            "save_pretrained needs full-precision params; run "
            "utils.quantization.dequantize_pytree first."
        )
    specs_map = hf_key_specs(family, config)
    # GPT-2 and GPT-NeoX re-FUSE q/k/v into one checkpoint tensor on the way
    # out — a dedicated generator, not per-leaf inverts.
    gpt_layout = getattr(config, "hf_layout", "gpt2") if family == "gpt" else None
    fused_qkv_export = gpt_layout in ("gpt2", "gpt_neox")
    if not fused_qkv_export:
        missing = [k for k, s in specs_map.items() if s.invert is None]
        if missing:
            raise NotImplementedError(
                f"Export has no inverse transform for leaves {missing[:4]} "
                f"(family {family!r})."
            )

    def leaf_for(dotted: str) -> Any:
        node: Any = params
        for part in dotted.split("."):
            node = node[part]
        return node

    # torch_dtype must reflect what lands on disk, or transformers
    # re-instantiates the export at the wrong precision.
    dtype_name = str(np.dtype(leaf_for(next(iter(specs_map))).dtype))
    if dtype_name not in ("bfloat16", "float16"):
        dtype_name = "float32"
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config_to_hf(family, config, torch_dtype=dtype_name), f, indent=2)

    def tensors() -> Any:
        if fused_qkv_export:
            gen = _gpt2_export_tensors if gpt_layout == "gpt2" else _neox_export_tensors
            yield from gen(config, params, leaf_for)
            return
        for key, src in specs_map.items():
            leaf = leaf_for(key)
            if src.per_layer:
                # One layer slice at a time: a 70B stacked leaf is tens of
                # GiB — the full-leaf device_get would OOM the host, the
                # per-slice gather keeps the spike to one layer's worth.
                for i in range(leaf.shape[0]):
                    arr = np.asarray(jax.device_get(leaf[i]))
                    if src.per_expert:
                        # (E, ...) expert stack un-fuses back into Mixtral's
                        # block_sparse_moe.experts.{e} tensors.
                        for e in range(arr.shape[0]):
                            yield src.key.format(i=i, e=e), src.invert(arr[e])
                        continue
                    yield src.key.format(i=i), src.invert(arr)
            else:
                yield src.key, src.invert(np.asarray(jax.device_get(leaf)))

    from safetensors.numpy import save_file

    # Task-model checkpoints prefix the backbone ("bert.embeddings...",
    # "vit.encoder...") while head weights stay bare; transformers refuses
    # the load otherwise. The maps here are canonical/unprefixed, so the
    # prefix is applied on the way out.
    if family == "gpt":
        prefix, exempt = {
            "gpt2": ("transformer.", ("lm_head.",)),
            "gptj": ("transformer.", ("lm_head.",)),
            "gpt_neox": ("gpt_neox.", ("embed_out.",)),
            "opt": ("model.", ("lm_head.",)),
        }[gpt_layout]
    else:
        prefix, exempt = {
            "bert": ("bert.", ("classifier.",)),
            "vit": ("vit.", ("classifier.",)),
        }.get(family, ("", ()))

    def exported_name(name: str) -> str:
        if prefix and not name.startswith(exempt):
            return prefix + name
        return name

    weight_map: dict[str, str] = {}
    shard: dict[str, np.ndarray] = {}
    shard_bytes = 0
    shard_idx = 0

    def flush() -> None:
        nonlocal shard, shard_bytes, shard_idx
        if not shard:
            return
        fname = f"model-{shard_idx:05d}.safetensors"
        save_file(shard, os.path.join(path, fname))
        for k in shard:
            weight_map[k] = fname
        shard = {}
        shard_bytes = 0
        shard_idx += 1

    total = 0
    for name, arr in tensors():
        name = exported_name(name)
        if shard_bytes + arr.nbytes > max_shard_bytes and shard:
            flush()
        shard[name] = arr
        shard_bytes += arr.nbytes
        total += arr.nbytes
    flush()
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as f:
        # transformers' hub loader requires the metadata block.
        json.dump(
            {"metadata": {"total_size": total}, "weight_map": weight_map}, f
        )
    return path


def _neox_export_tensors(config, params, leaf_for):
    """GPT-NeoX export: q/k/v re-fuse into ``query_key_value`` with the
    PER-HEAD ``[q|k|v]`` row layout (see `_neox_qkv`)."""

    def get(dotted):
        return np.asarray(jax.device_get(leaf_for(dotted)))

    yield "embed_in.weight", get("wte")
    yield "final_layer_norm.weight", get("lnf_scale")
    yield "final_layer_norm.bias", get("lnf_bias")
    if not config.tie_embeddings:
        yield "embed_out.weight", np.ascontiguousarray(get("lm_head").T)
    d = config.d_model
    for i in range(config.n_layers):
        L = f"layers.{i}."
        for ours, theirs in (
            ("ln1_scale", "input_layernorm.weight"),
            ("ln1_bias", "input_layernorm.bias"),
            ("ln2_scale", "post_attention_layernorm.weight"),
            ("ln2_bias", "post_attention_layernorm.bias"),
        ):
            yield L + theirs, np.asarray(jax.device_get(leaf_for(f"blocks.{ours}")[i]))
        attn = params["blocks"]["attn"]
        # (d, nh, h) x3 -> (nh, 3, h, d) -> (3d, d)
        qkv = np.stack(
            [np.asarray(jax.device_get(attn[k][i])).transpose(1, 2, 0) for k in ("wq", "wk", "wv")],
            axis=1,
        )
        yield L + "attention.query_key_value.weight", np.ascontiguousarray(
            qkv.reshape(-1, d)
        )
        if config.attn_bias:
            bias = np.stack(
                [np.asarray(jax.device_get(attn[k][i])) for k in ("bq", "bk", "bv")],
                axis=1,
            )  # (nh, 3, h)
            yield L + "attention.query_key_value.bias", np.ascontiguousarray(
                bias.reshape(-1)
            )
            yield L + "attention.dense.bias", np.asarray(jax.device_get(attn["bo"][i]))
        yield L + "attention.dense.weight", np.ascontiguousarray(
            np.asarray(jax.device_get(attn["wo"][i])).reshape(-1, d).T
        )
        mlp = params["blocks"]["mlp"]
        yield L + "mlp.dense_h_to_4h.weight", np.ascontiguousarray(
            np.asarray(jax.device_get(mlp["w_in"][i])).T
        )
        yield L + "mlp.dense_h_to_4h.bias", np.asarray(jax.device_get(mlp["b_in"][i]))
        yield L + "mlp.dense_4h_to_h.weight", np.ascontiguousarray(
            np.asarray(jax.device_get(mlp["w_out"][i])).T
        )
        yield L + "mlp.dense_4h_to_h.bias", np.asarray(jax.device_get(mlp["b_out"][i]))


def _gpt2_export_tensors(config, params, leaf_for):
    """GPT-2 export: unlike the 1:1 families, q/k/v re-FUSE into Conv1D
    ``c_attn`` (weights already (in, out) — concatenation, no transpose)."""

    def get(dotted):
        return np.asarray(jax.device_get(leaf_for(dotted)))

    yield "wte.weight", get("wte")
    yield "wpe.weight", get("wpe")
    yield "ln_f.weight", get("lnf_scale")
    yield "ln_f.bias", get("lnf_bias")
    if not config.tie_embeddings:
        # Untied head: params["lm_head"] is (d, V); HF stores (V, d).
        yield "lm_head.weight", np.ascontiguousarray(get("lm_head").T)
    d = config.d_model
    for i in range(config.n_layers):
        L = f"h.{i}."
        blk = {k: np.asarray(jax.device_get(leaf_for(f"blocks.{k}")[i]))
               for k in ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias")}
        yield L + "ln_1.weight", blk["ln1_scale"]
        yield L + "ln_1.bias", blk["ln1_bias"]
        yield L + "ln_2.weight", blk["ln2_scale"]
        yield L + "ln_2.bias", blk["ln2_bias"]
        attn = params["blocks"]["attn"]
        wq, wk, wv = (np.asarray(jax.device_get(attn[k][i])).reshape(d, -1)
                      for k in ("wq", "wk", "wv"))
        yield L + "attn.c_attn.weight", np.ascontiguousarray(
            np.concatenate([wq, wk, wv], axis=1)
        )
        bq, bk, bv = (np.asarray(jax.device_get(attn[k][i])).reshape(-1)
                      for k in ("bq", "bk", "bv"))
        yield L + "attn.c_attn.bias", np.concatenate([bq, bk, bv])
        yield L + "attn.c_proj.weight", np.ascontiguousarray(
            np.asarray(jax.device_get(attn["wo"][i])).reshape(-1, d)
        )
        yield L + "attn.c_proj.bias", np.asarray(jax.device_get(attn["bo"][i]))
        mlp = params["blocks"]["mlp"]
        yield L + "mlp.c_fc.weight", np.asarray(jax.device_get(mlp["w_in"][i]))
        yield L + "mlp.c_fc.bias", np.asarray(jax.device_get(mlp["b_in"][i]))
        yield L + "mlp.c_proj.weight", np.asarray(jax.device_get(mlp["w_out"][i]))
        yield L + "mlp.c_proj.bias", np.asarray(jax.device_get(mlp["b_out"][i]))
