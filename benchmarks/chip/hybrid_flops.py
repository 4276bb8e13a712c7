"""Operations and bytes the algorithm of a hybrid of Mamba-2 and attention
layers needs, from a configuration's shapes (keys of the published
``config.json`` of a granitemoehybrid model with no routed experts).

As in ``flops.py``, these count what the mathematics requires, not what an
implementation executes.  A multiply-add is two operations.  Causal
attention counts the positions a query may see.  The SSD (Dao & Gu 2024)
is counted in its chunked form at the published chunk: within a chunk of
Q, C B^T and its product with x over the positions a query may see, each
chunk's state (B^T x) and the state's contribution to each output (C h);
the passing of states between chunks, elementwise, is not counted.  A
decode step reads every weight once, the embedding rows of its tokens,
each Mamba layer's SSM state and conv tail of every live slot (read and
written), and each attention layer's live K/V (the earlier positions
read, the new one written).
"""
from __future__ import annotations

from typing import Iterable, List

BF16_BYTES = 2
F32_BYTES = 4


def _dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    kinds = c["layer_types"]
    sh = dict(d=d, heads=h, kv=c["num_key_value_heads"],
              hd=c.get("head_dim") or d // h, ff=c["shared_intermediate_size"],
              vocab=c["vocab_size"], H=c["mamba_n_heads"], P=c["mamba_d_head"],
              N=c["mamba_d_state"], G=c["mamba_n_groups"], K=c["mamba_d_conv"],
              Q=c["mamba_chunk_size"],
              n_mamba=sum(k == "mamba" for k in kinds),
              n_attn=sum(k == "attention" for k in kinds))
    sh["d_inner"] = sh["H"] * sh["P"]
    sh["conv_dim"] = sh["d_inner"] + 2 * sh["G"] * sh["N"]
    sh["in_dim"] = sh["d_inner"] + sh["conv_dim"] + sh["H"]
    return sh


def mamba_matmul_params(c: dict) -> int:
    """Weights one token multiplies by in a Mamba layer's mixer."""
    s = _dims(c)
    return s["d"] * s["in_dim"] + s["d_inner"] * s["d"]


def attention_matmul_params(c: dict) -> int:
    s = _dims(c)
    return 2 * s["d"] * s["heads"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]


def mlp_params(c: dict) -> int:
    s = _dims(c)
    return 3 * s["d"] * s["ff"]                   # gated: gate, up, down


def matmul_params(c: dict) -> int:
    """Weights a token multiplies by: every layer and the (tied) head."""
    s = _dims(c)
    return (s["n_mamba"] * mamba_matmul_params(c)
            + s["n_attn"] * attention_matmul_params(c)
            + (s["n_mamba"] + s["n_attn"]) * mlp_params(c)
            + s["d"] * s["vocab"])


def weight_bytes(c: dict) -> int:
    """Bytes of all weights as stored: bf16, but A_log, dt_bias and D in
    float32; the tied embedding once."""
    s = _dims(c)
    layers = s["n_mamba"] + s["n_attn"]
    mamba_bf16 = (mamba_matmul_params(c) + s["K"] * s["conv_dim"]
                  + s["conv_dim"] + s["d_inner"])   # conv w and b, gated norm
    bf16 = (s["n_mamba"] * mamba_bf16
            + s["n_attn"] * attention_matmul_params(c)
            + layers * (mlp_params(c) + 2 * s["d"])  # MLP, two norms
            + s["vocab"] * s["d"] + s["d"])          # embedding, final norm
    f32 = s["n_mamba"] * 3 * s["H"]
    return bf16 * BF16_BYTES + f32 * F32_BYTES


def ssm_state_bytes(c: dict, slots: int) -> int:
    """Recurrent state of ``slots`` sequences: each Mamba layer's SSM state
    (H, P, N) in float32 and its conv tail of K - 1 inputs in bf16."""
    s = _dims(c)
    per_slot = (s["H"] * s["P"] * s["N"] * F32_BYTES
                + (s["K"] - 1) * s["conv_dim"] * BF16_BYTES)
    return s["n_mamba"] * slots * per_slot


def kv_bytes_per_position(c: dict) -> int:
    s = _dims(c)
    return s["n_attn"] * 2 * s["kv"] * s["hd"] * BF16_BYTES


def kv_cache_bytes(c: dict, slots: int, max_len: int) -> int:
    """K/V of ``slots`` sequences at a capacity of ``max_len`` positions."""
    return slots * max_len * kv_bytes_per_position(c)


def ssm_step_flops(c: dict) -> int:
    """One token's state step in one Mamba layer: the update's and the
    readout's multiply-add per state element, and the conv."""
    s = _dims(c)
    return 4 * s["H"] * s["P"] * s["N"] + 2 * s["K"] * s["conv_dim"]


def attention_flops(c: dict, positions: Iterable[int]) -> int:
    """QK^T and PV of the queries at ``positions``, all attention layers."""
    s = _dims(c)
    return 4 * s["heads"] * s["hd"] * sum(p + 1 for p in positions) * s["n_attn"]


def ssd_flops(c: dict, prompt_len: int) -> int:
    """The chunked SSD of one sequence of ``prompt_len`` tokens in all
    Mamba layers (the last chunk may be partial)."""
    s = _dims(c)
    H, P, N, G, Q = s["H"], s["P"], s["N"], s["G"], s["Q"]
    total = 0
    for start in range(0, prompt_len, Q):
        q = min(Q, prompt_len - start)
        pairs = q * (q + 1) // 2                     # causal within the chunk
        total += (2 * G * N * pairs                  # C B^T per group
                  + 2 * H * P * pairs                # (C B^T . L) x per head
                  + 2 * H * P * N * q                # chunk state B^T x
                  + 2 * H * P * N * q)               # state to output C h
    conv = 2 * s["K"] * s["conv_dim"] * prompt_len
    return s["n_mamba"] * (total + conv)


def prefill_flops(c: dict, rows: int, prompt_len: int) -> int:
    """A prefill of ``rows`` prompts of ``prompt_len`` tokens: every layer
    for every prompt token, the head for the last position only."""
    s = _dims(c)
    layer_params = matmul_params(c) - s["d"] * s["vocab"]
    per_row = (2 * layer_params * prompt_len + 2 * s["d"] * s["vocab"]
               + attention_flops(c, range(prompt_len))
               + ssd_flops(c, prompt_len))
    return rows * per_row


def decode_flops(c: dict, positions: List[int]) -> int:
    """One decode step: one token per live slot, at ``positions``."""
    s = _dims(c)
    return ((2 * matmul_params(c) + s["n_mamba"] * ssm_step_flops(c))
            * len(positions) + attention_flops(c, positions))


def decode_bytes(c: dict, positions: List[int]) -> int:
    """HBM bytes one decode step needs, one live slot per position."""
    s = _dims(c)
    n = len(positions)
    embed_rows = n * s["d"] * BF16_BYTES
    state = 2 * ssm_state_bytes(c, n)                # read and written
    kv = sum(p + 1 for p in positions) * kv_bytes_per_position(c)
    return weight_bytes(c) + embed_rows + state + kv
