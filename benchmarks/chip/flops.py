"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count what the mathematics of a dense decoder requires, not what a
given implementation executes: causal (and windowed) attention counts only
the positions a query may see, recomputation under remat is not counted, and
a decode step reads each weight once and only the live cache positions of
each sequence.  A multiply-add is two operations.

``c`` is a configuration dictionary as stored under ``configs/`` (keys of
the published ``config.json``).
"""
from __future__ import annotations

from typing import Iterable

BF16_BYTES = 2


def window(c: dict) -> int:
    """Sliding window of the configuration; 0 for full causal attention."""
    if not c.get("use_sliding_window", True):
        return 0
    return c.get("sliding_window") or 0


def _dims(c: dict):
    d = c["hidden_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    return d, c["num_attention_heads"], c["num_key_value_heads"], hd


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies by in one decoder layer."""
    d, h, kv, hd = _dims(c)
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * c["intermediate_size"]          # gated: gate, up, down
    return attn + mlp


def layer_params(c: dict) -> int:
    """All weights of one decoder layer: matmuls, two norms, QKV biases."""
    d, h, kv, hd = _dims(c)
    bias = (h * hd + 2 * kv * hd) if c.get("qkv_bias") else 0
    return layer_matmul_params(c) + 2 * d + bias


def matmul_params(c: dict) -> int:
    """Weights a token multiplies by: every layer and the output head (the
    embedding is a gather, not a product)."""
    return c["num_hidden_layers"] * layer_matmul_params(c) + \
        c["hidden_size"] * c["vocab_size"]


def context(c: dict, position: int) -> int:
    """Positions a query at ``position`` (0-based) attends to."""
    w = window(c)
    seen = position + 1
    return min(seen, w) if w else seen


def attention_flops(c: dict, positions: Iterable[int]) -> int:
    """Forward QK^T and PV of the queries at ``positions``, all layers."""
    _, h, _, hd = _dims(c)
    ctx = sum(context(c, p) for p in positions)
    return 4 * h * hd * ctx * c["num_hidden_layers"]


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward (three times the forward) per trained token of
    sequences of ``seq_len`` tokens."""
    fwd = 2 * matmul_params(c) + attention_flops(c, range(seq_len)) / seq_len
    return 3.0 * fwd


def prefill_flops(c: dict, rows: int, prompt_len: int) -> int:
    """A prefill of ``rows`` prompts of ``prompt_len`` tokens: every layer
    for every prompt token, the output head for the last position only."""
    layers = c["num_hidden_layers"] * layer_matmul_params(c)
    per_row = (2 * layers * prompt_len
               + 2 * c["hidden_size"] * c["vocab_size"]
               + attention_flops(c, range(prompt_len)))
    return rows * per_row


def decode_flops(c: dict, positions: Iterable[int]) -> int:
    """One decode step: one token per sequence, at ``positions``."""
    positions = list(positions)
    return (2 * matmul_params(c) * len(positions)
            + attention_flops(c, positions))


def kv_bytes_per_position(c: dict) -> int:
    _, _, kv, hd = _dims(c)
    return c["num_hidden_layers"] * 2 * kv * hd * BF16_BYTES


def decode_bytes(c: dict, positions: Iterable[int]) -> int:
    """HBM bytes one decode step needs: every weight once (bf16), the
    embedding rows of its tokens, and for each sequence its live cache
    positions: the earlier ones read, its new one written."""
    positions = list(positions)
    weights = (c["num_hidden_layers"] * layer_params(c)
               + c["hidden_size"] * c["vocab_size"]       # output head
               + c["hidden_size"]) * BF16_BYTES           # final norm
    embed_rows = len(positions) * c["hidden_size"] * BF16_BYTES
    per_pos = kv_bytes_per_position(c)
    cache = sum(context(c, p) for p in positions) * per_pos
    return weights + embed_rows + cache


def weight_bytes(c: dict) -> int:
    """Bytes of all weights in bf16, embedding and head included."""
    head = c["hidden_size"] * c["vocab_size"]
    embed = 0 if c.get("tie_word_embeddings") else head
    return (c["num_hidden_layers"] * layer_params(c) + head + embed
            + c["hidden_size"]) * BF16_BYTES
