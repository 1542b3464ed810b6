"""Operations and bytes the algorithms need, from shapes alone.

These are the numerators of `mfu.train` and of every `*_roofline` metric.
They count what the mathematics requires and nothing a particular
implementation adds: no recomputation (remat, the score recompute inside a
flash backward), no padding, no embedding lookup. A share computed from them
can therefore fall short of what the hardware did, and never exceed it.
Keys are the published `config.json` names.
"""

from __future__ import annotations

from typing import Any


def head_dim(config: dict[str, Any]) -> int:
    return config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]


def layer_matmul_params(config: dict[str, Any]) -> int:
    """Weights of one layer that a token is multiplied by."""
    d, h = config["hidden_size"], head_dim(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return d * h * (2 * heads + 2 * kv) + 3 * d * config["intermediate_size"]


def matmul_params(config: dict[str, Any]) -> int:
    """All weights a token is multiplied by: the layers and the head. The
    embedding is a lookup and does no arithmetic."""
    head = config["hidden_size"] * config["vocab_size"]
    return config["num_hidden_layers"] * layer_matmul_params(config) + head


def attention_forward_flops(config: dict[str, Any], seq_len: int) -> float:
    """QK^T and PV of one causal sequence in one layer: two matmuls of
    ``2 * h`` operations for each (query, visible key) pair and head."""
    pairs = seq_len * (seq_len + 1) / 2
    return 2 * 2 * head_dim(config) * config["num_attention_heads"] * pairs


def train_flops_per_token(config: dict[str, Any], seq_len: int) -> float:
    """Forward plus backward of next-token training, per token: 6 operations
    per matmul weight (2 forward, 4 backward) and three times the forward
    attention (its backward is four matmuls of the same size as the
    forward's two)."""
    attention = 3 * config["num_hidden_layers"] * attention_forward_flops(config, seq_len) / seq_len
    return 6.0 * matmul_params(config) + attention


def flash_attention_train_cost(config: dict[str, Any], batch: int, seq_len: int, layers: int) -> dict[str, float]:
    """What the flash forward and backward calls of one training step must
    do: operations as above (forward 2 matmuls, backward 4; the backward's
    recomputation of the scores is the kernel's choice and is not counted),
    and the bytes of q, k, v, the output and their gradients in bf16, each
    moved once."""
    h, heads, kv = head_dim(config), config["num_attention_heads"], config["num_key_value_heads"]
    flops = 3 * layers * batch * attention_forward_flops(config, seq_len)
    qo = batch * seq_len * heads * h * 2
    kvb = batch * seq_len * kv * h * 2
    # forward: read q, k, v, write o; backward: read q, k, v, o, do, write dq, dk, dv
    moved = layers * ((2 * qo + 2 * kvb) + (4 * qo + 4 * kvb))
    return {"flops": flops, "bytes": float(moved)}


def decode_step_bytes(config: dict[str, Any], live_kv_tokens: float) -> float:
    """Bytes one decode step must read from HBM in the serving
    configuration: every layer's int8 weights, the bf16 head (the
    embedding is a lookup of a few rows), and the live rows of the bf16 KV
    cache. Scales, norms and the rows written are left out (under 0.1%)."""
    weights = config["num_hidden_layers"] * layer_matmul_params(config)  # 1 byte each
    head = 2 * config["hidden_size"] * config["vocab_size"]
    kv_row = config["num_hidden_layers"] * 2 * config["num_key_value_heads"] * head_dim(config) * 2
    return float(weights + head + kv_row * live_kv_tokens)
