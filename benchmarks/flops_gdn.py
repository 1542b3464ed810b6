"""Operations and bytes a hybrid decoder (gated-delta-rule linear attention
in most layers, full softmax attention in the rest) needs, from the
configuration's keys alone, whatever implements them: no tile padding (a
96 x 192 state is counted as 96 x 192, though the chip lays 192 lanes out as
256), no slot that is not decoding, no pad row. Keys are the published
`config.json` names of the Olmo-Hybrid family. Beside `flops.py`, whose rule
holds here too: a share computed from these can fall short of what the
hardware did and never exceed it.
"""

from __future__ import annotations

from typing import Any

BF16, F32 = 2, 4  # bytes
CHUNK = 64  # rows of a chunk of the chunkwise form, as published for the rule


def kinds(config: dict[str, Any]) -> list[str]:
    """The layer types run (a depth-cut configuration keeps the published
    layout whole and runs its first ``num_hidden_layers`` entries)."""
    return list(config["layer_types"][: config["num_hidden_layers"]])


def linear_layers(config: dict[str, Any]) -> int:
    return kinds(config).count("linear_attention")


def full_layers(config: dict[str, Any]) -> int:
    return kinds(config).count("full_attention")


def head_dim(config: dict[str, Any]) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def rule_dims(config: dict[str, Any]) -> tuple[int, int, int]:
    """(heads, key width, value width) of the delta rule."""
    return config["linear_num_key_heads"], config["linear_key_head_dim"], config["linear_value_head_dim"]


def conv_channels(config: dict[str, Any]) -> int:
    h, dk, dv = rule_dims(config)
    return h * (2 * dk + dv)


def mlp_params(config: dict[str, Any]) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def linear_layer_params(config: dict[str, Any]) -> int:
    """Weights of one linear-attention layer that every row is multiplied by:
    the q, k, v projections, the gate, a and b, the output projection, the
    convolution, and the feed-forward."""
    d, h = config["hidden_size"], config["linear_num_key_heads"]
    values = h * config["linear_value_head_dim"]
    mixer = d * (conv_channels(config) + values + 2 * h) + values * d
    return mixer + config["linear_conv_kernel_dim"] * conv_channels(config) + mlp_params(config)


def full_layer_params(config: dict[str, Any]) -> int:
    d, hd = config["hidden_size"], head_dim(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return d * hd * (2 * heads + 2 * kv) + mlp_params(config)


def state_bytes(config: dict[str, Any]) -> int:
    """One request's delta-rule state in one layer: float32."""
    h, dk, dv = rule_dims(config)
    return h * dk * dv * F32


def conv_tail_bytes(config: dict[str, Any]) -> int:
    """One request's convolution tail in one layer: bf16."""
    return (config["linear_conv_kernel_dim"] - 1) * conv_channels(config) * BF16


def kv_row_bytes(config: dict[str, Any]) -> int:
    """One position's keys and values in one full-attention layer, bf16."""
    return 2 * config["num_key_value_heads"] * head_dim(config) * BF16


def gdn_decode_bytes(config: dict[str, Any], states_live: float) -> float:
    """Bytes the rule's decode step must move for ``states_live`` (slot,
    layer) states: each read and written once in float32, with its q, k, v,
    alpha and beta read and its output written."""
    h, dk, dv = rule_dims(config)
    vectors = h * (2 * dk + 2 * dv + 2) * F32
    return states_live * (2 * state_bytes(config) + vectors)


def decode_step_bytes(config: dict[str, Any], states_live: float, kv_rows_live: float) -> float:
    """Bytes one decode step must move: every layer's weights and the head
    in bf16 (the embedding is a lookup of a few rows), the live states
    (``states_live`` (slot, layer) pairs: state and convolution tail read and
    written) and the live rows of the KV cache (``kv_rows_live`` rows, summed
    over the decoding slots, in each full-attention layer). Norms and the KV
    rows written are left out."""
    weights = linear_layers(config) * linear_layer_params(config) + full_layers(config) * full_layer_params(config)
    head = config["hidden_size"] * config["vocab_size"]
    states = states_live * 2 * (state_bytes(config) + conv_tail_bytes(config))
    kv = kv_rows_live * kv_row_bytes(config) * full_layers(config)
    return float((weights + head) * BF16 + states + kv)


def chunk_state_pass_flops(config: dict[str, Any], rows: float) -> float:
    """Operations of the chunk-to-chunk part of the chunkwise form for
    ``rows`` real rows in every linear layer: for a chunk of C rows and a head,
    ``w S``, ``q S`` and ``k^T v'`` (2 C d_k d_v each) and the lower triangle
    of ``p v'`` (C^2 d_v)."""
    h, dk, dv = rule_dims(config)
    per_chunk = 6 * CHUNK * dk * dv + CHUNK * CHUNK * dv
    return linear_layers(config) * h * (rows / CHUNK) * per_chunk


def chunk_form_flops(config: dict[str, Any], rows: float) -> float:
    """Operations of the whole chunkwise form for ``rows`` real rows in
    every linear layer: the state pass, and inside a chunk the strict lower
    triangle of ``beta k k^T`` (C^2 d_k), the triangular solve for the WY
    factors (C^2 (d_k + d_v)) and the lower triangle of ``q k^T`` (C^2 d_k)."""
    h, dk, dv = rule_dims(config)
    inside = CHUNK * CHUNK * (3 * dk + dv)
    return chunk_state_pass_flops(config, rows) + linear_layers(config) * h * (rows / CHUNK) * inside
