"""Operations and bytes that a GPT-style stack needs, from its shapes.

A matrix is counted once for each use in a matrix multiplication (two
operations per weight and token). Embedding tables that are looked up, not
multiplied, are NOT counted: ``bench.py``'s ``6 * n_params`` counted the
GPT's token and position tables and overstated MFU by about 7%.
Attention is counted as the causal half that is required, not the full
square. Nothing recomputed is counted.
"""
from __future__ import annotations


def layer_matmul_params(cfg) -> int:
    d, dff = int(cfg["d_model"]), int(cfg["d_ff"])
    return 4 * d * d + 2 * d * dff          # qkv 3d^2, out d^2, two FFN


def stack_matmul_params(cfg) -> int:
    return int(cfg["n_layers"]) * layer_matmul_params(cfg)


def head_params(cfg) -> int:
    return int(cfg["d_model"]) * int(cfg["vocab_size"])


def kv_bytes_per_token(cfg, itemsize=2) -> int:
    """K and V rows of one token over all layers."""
    return 2 * int(cfg["n_layers"]) * int(cfg["d_model"]) * itemsize


def attention_flops(cfg, attended: int) -> int:
    """QK^T and PV for query tokens that attend ``attended`` keys in all:
    2 * 2 * d_model per (query, key) pair and layer."""
    return 4 * int(cfg["d_model"]) * int(cfg["n_layers"]) * int(attended)


def causal_pairs(pos: int, n: int) -> int:
    """(query, key) pairs of n query tokens at positions pos..pos+n-1, each
    attending itself and everything before it."""
    return n * pos + n * (n + 1) // 2


def prefill_chunk_flops(cfg, pos: int, n: int, final: bool) -> int:
    """Model operations of one prefill chunk: ``n`` real tokens after
    ``pos`` cached ones; the head runs once, on the last chunk's last row."""
    f = 2 * stack_matmul_params(cfg) * n \
        + attention_flops(cfg, causal_pairs(pos, n))
    return f + (2 * head_params(cfg) if final else 0)


def decode_step_flops(cfg, ctx_lens) -> int:
    """One decode step: each sequence brings one token, which attends its
    ``ctx`` cached tokens and itself, and goes through the head."""
    per_token = 2 * (stack_matmul_params(cfg) + head_params(cfg))
    return sum(per_token + attention_flops(cfg, c + 1) for c in ctx_lens)


def decode_step_bytes(cfg, ctx_lens, weight_itemsize=2, kv_itemsize=2) -> int:
    """Bytes one decode step has to read: every matmul weight and the head
    once, and the K+V rows its sequences hold."""
    w = (stack_matmul_params(cfg) + head_params(cfg)) * weight_itemsize
    return w + sum(ctx_lens) * kv_bytes_per_token(cfg, kv_itemsize)


def stream_linear_bytes(cfg, weight_itemsize=2) -> int:
    """Bytes the weight-stream kernels of one decode step read: the four
    stacks of every layer and the head."""
    return (stack_matmul_params(cfg) + head_params(cfg)) * weight_itemsize


def train_flops_per_token(cfg, seq: int, causal=True, tied_head=False,
                          extra_matmul_params=0) -> int:
    """Forward and backward of one token in a sequence of ``seq``: 6 per
    matmul weight, and three times the forward attention."""
    p = stack_matmul_params(cfg) + head_params(cfg) + extra_matmul_params
    pairs = causal_pairs(0, seq) / seq if causal else seq
    return int(6 * p + 3 * attention_flops(cfg, 1) * pairs)
