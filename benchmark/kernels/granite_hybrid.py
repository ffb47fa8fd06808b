"""Operations and bytes that ``family: granite_hybrid`` needs, from its
shapes and the program's pick counters, the same whatever implements them.

A matrix is counted once for each use (two operations per weight and
token); the embedding that is looked up is not counted, the tied head is.
Routed experts are counted for the picks that LANDED on an expert held
here (``picks_share`` of top_k a token and layer: the program's
``serving.moe.picks_here`` over ``serving.moe.picks``): what a dense pass
over rows that did not pick the expert computes beside that is not work
the model asks for. Their BYTES are every held expert once a decode step:
at 64 rows each one is hit. Attention is the causal half; the recurrence
is 5 operations an element of state a token (decay, the outer-product
accumulate, the contraction with C).
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _i(cfg, k):
    return int(cfg[k])


def d_inner(cfg):
    return _i(cfg, "mamba_n_heads") * _i(cfg, "mamba_d_head")


def conv_dim(cfg):
    return d_inner(cfg) + 2 * _i(cfg, "mamba_n_groups") \
        * _i(cfg, "mamba_d_state")


def kinds(cfg):
    return list(cfg["layer_types"][:_i(cfg, "num_hidden_layers")])


def n_mamba(cfg):
    return kinds(cfg).count("mamba")


def n_attention(cfg):
    return kinds(cfg).count("attention")


def mamba_params(cfg):
    d, di = _i(cfg, "hidden_size"), d_inner(cfg)
    return d * (di + conv_dim(cfg) + _i(cfg, "mamba_n_heads")) + di * d


def attention_params(cfg):
    d = _i(cfg, "hidden_size")
    hd = d // _i(cfg, "num_attention_heads")
    nq, nkv = _i(cfg, "num_attention_heads"), _i(cfg, "num_key_value_heads")
    return d * (nq + 2 * nkv) * hd + nq * hd * d


def expert_params(cfg):
    return 3 * _i(cfg, "hidden_size") * _i(cfg, "intermediate_size")


def shared_params(cfg):
    return 3 * _i(cfg, "hidden_size") * _i(cfg, "shared_intermediate_size")


def router_params(cfg):
    return _i(cfg, "hidden_size") * _i(cfg, "router_width")


def head_params(cfg):
    return _i(cfg, "hidden_size") * _i(cfg, "vocab_size")


def experts_held(cfg):
    return int(cfg["experts_held"][1])


def state_elems(cfg):
    """Elements of SSM state a sequence and layer."""
    return _i(cfg, "mamba_d_state") * d_inner(cfg)


def kv_bytes_per_token(cfg):
    hd = _i(cfg, "hidden_size") // _i(cfg, "num_attention_heads")
    return 2 * n_attention(cfg) * _i(cfg, "num_key_value_heads") * hd * BF16


def dense_params(cfg):
    """Matmul weights every token passes, all layers (no experts, no head)."""
    n = len(kinds(cfg))
    return n_mamba(cfg) * mamba_params(cfg) \
        + n_attention(cfg) * attention_params(cfg) \
        + n * (shared_params(cfg) + router_params(cfg))


def token_flops(cfg, picks_share):
    """One token through the stack's matrices and the recurrence, without
    attention over a context and without the head."""
    n = len(kinds(cfg))
    routed = n * _i(cfg, "num_experts_per_tok") * picks_share \
        * expert_params(cfg)
    ssm = n_mamba(cfg) * (5 * state_elems(cfg)
                          + 2 * _i(cfg, "mamba_d_conv") * conv_dim(cfg))
    return 2 * (dense_params(cfg) + routed) + ssm


def attention_flops(cfg, attended):
    """QK^T and PV of query tokens that attend ``attended`` keys in all."""
    hd = _i(cfg, "hidden_size") // _i(cfg, "num_attention_heads")
    return 4 * _i(cfg, "num_attention_heads") * hd * n_attention(cfg) \
        * int(attended)


def causal_pairs(pos, n):
    return n * pos + n * (n + 1) // 2


def decode_step_flops(cfg, ctx_lens, picks_share):
    per = token_flops(cfg, picks_share) + 2 * head_params(cfg)
    return sum(per + attention_flops(cfg, c + 1) for c in ctx_lens)


def weight_bytes(cfg):
    """Every weight a decode step streams: the mixers, the shared MLPs, the
    float32 routers, all held experts, the tied head."""
    n = len(kinds(cfg))
    return BF16 * (dense_params(cfg) - n * router_params(cfg)
                   + n * experts_held(cfg) * expert_params(cfg)
                   + head_params(cfg)) + F32 * n * router_params(cfg)


def ssm_decode_bytes(cfg, n_seqs):
    """The recurrent state of ``n_seqs`` sequences read and written once,
    every mamba layer (float32), with their conv tails (bf16)."""
    tail = (_i(cfg, "mamba_d_conv") - 1) * conv_dim(cfg) * BF16
    return n_seqs * n_mamba(cfg) * 2 * (state_elems(cfg) * F32 + tail)


def decode_step_bytes(cfg, ctx_lens):
    return weight_bytes(cfg) + ssm_decode_bytes(cfg, len(ctx_lens)) \
        + sum(ctx_lens) * kv_bytes_per_token(cfg)


def moe_stream_bytes(cfg):
    """Held experts of every layer, once: one decode step's expert stream."""
    return len(kinds(cfg)) * experts_held(cfg) * expert_params(cfg) * BF16


def moe_stream_flops(cfg, n_tokens, picks_share):
    return 2 * len(kinds(cfg)) * n_tokens * expert_params(cfg) \
        * _i(cfg, "num_experts_per_tok") * picks_share


def prefill_chunk_flops(cfg, pos, n, final, picks_share):
    f = n * token_flops(cfg, picks_share) \
        + attention_flops(cfg, causal_pairs(pos, n))
    return f + (2 * head_params(cfg) if final else 0)


def ssd_chunk_flops(cfg, n):
    """The chunked scan of ``n`` tokens (one SSD chunk or less), every mamba
    layer: the causal half of C B^T once, per head the causal half of the
    in-chunk product and the two products with the passed state."""
    N, P, H = _i(cfg, "mamba_d_state"), _i(cfg, "mamba_d_head"), \
        _i(cfg, "mamba_n_heads")
    return n_mamba(cfg) * (n * n * N + H * (n * n * P + 4 * n * N * P))


def ssd_chunk_bytes(cfg, n):
    """u, B, C, dt read and y written for ``n`` tokens (bf16 in, float32
    out), the state read and written once; every mamba layer."""
    io = n * (conv_dim(cfg) * BF16 + _i(cfg, "mamba_n_heads") * F32
              + d_inner(cfg) * F32)
    return n_mamba(cfg) * (io + 2 * state_elems(cfg) * F32)
