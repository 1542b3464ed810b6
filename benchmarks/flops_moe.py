"""Operations and bytes a sparse-expert decoder with two kinds of layer
needs, from shapes and from the routing alone (never from how the program
computes it: no padding rows, no tile sizes). Keys are the published
`config.json` names of the SmallThinker family. Beside `flops.py`, whose
rules hold here too: a share computed from these can fall short of what the
hardware did and never exceed it.
"""

from __future__ import annotations

from typing import Any

BF16 = 2  # bytes


def expert_params(config: dict[str, Any]) -> int:
    """Weights of one expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def dense_layer_params(config: dict[str, Any]) -> int:
    """Weights of one layer that every row is multiplied by: the attention
    projections and the router."""
    d, h = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return d * h * (2 * heads + 2 * kv) + d * config["moe_num_primary_experts"]


def experts_touched_bytes(config: dict[str, Any], experts_touched: float) -> float:
    """Bytes of the expert weights a step must read, given how many
    (layer, expert) pairs its routing gave at least one row: each is read
    once, in bf16. Activations are left out (under 1% at 16 rows)."""
    return experts_touched * expert_params(config) * BF16


def expert_flops(config: dict[str, Any], assignments: float) -> float:
    """Operations of the expert products for ``assignments`` (row, expert)
    pairs: three matrices, two operations a weight."""
    return assignments * 2 * expert_params(config)


def window_layers(config: dict[str, Any]) -> int:
    """Windowed layers among the ``num_hidden_layers`` run (a depth-cut
    configuration keeps the published layout whole)."""
    return sum(config["sliding_window_layout"][: config["num_hidden_layers"]])


def kv_row_bytes(config: dict[str, Any]) -> int:
    """Bytes of one position's keys and values in one layer, bf16."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BF16


def decode_step_bytes(
    config: dict[str, Any], experts_touched: float, kv_rows_full: float, kv_rows_window: float
) -> float:
    """Bytes one decode step must read from HBM: every layer's attention and
    router weights, the experts its routing touched, the head (the embedding
    is a lookup of a few rows), and the live rows of the KV cache:
    ``kv_rows_full`` rows in each full-attention layer and
    ``kv_rows_window`` (capped at the window) in each windowed one, summed
    over the decoding slots. Norms and the rows written are left out."""
    layers = config["num_hidden_layers"]
    windowed = window_layers(config)
    weights = layers * dense_layer_params(config) * BF16
    head = config["hidden_size"] * config["vocab_size"] * BF16
    kv = kv_row_bytes(config) * (kv_rows_full * (layers - windowed) + kv_rows_window * windowed)
    return float(weights + experts_touched_bytes(config, experts_touched) + head + kv)
