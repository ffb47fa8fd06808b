"""Operations and bytes that ``family: mimo_v2_flash`` needs, from its shapes
and the program's counters, the same whatever implements them.

A matrix is counted once for each use (two operations per weight and
token); the embedding that is looked up is not counted, the untied head is.
A query-key pair costs ``heads x (head_dim + v_head_dim) x 2`` operations
in either kind of layer: causal pairs in the full layers, at most
``sliding_window`` a query in the window layers. BYTES of attention are the
model's CONTENT: a token's K and V of one layer, ``kv heads x (head_dim +
v_head_dim) x 2`` B (the full layers' packed row stores exactly that; a
window ring stores K heads in 256 lanes, 1.2 x that).

Routed experts are counted for the picks that LANDED on an expert held here
(``picks_share`` of top_k a token and expert layer); their bytes are every
held expert once a decode step.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _i(cfg, k):
    return int(cfg[k])


def layers(cfg):
    return _i(cfg, "num_hidden_layers")


def kinds(cfg):
    """0 full, 1 window, a layer."""
    return [int(k) for k in cfg["hybrid_layer_pattern"][:layers(cfg)]]


def n_full(cfg):
    return kinds(cfg).count(0)


def n_window(cfg):
    return kinds(cfg).count(1)


def n_moe(cfg):
    return sum(int(k) for k in cfg["moe_layer_freq"][:layers(cfg)])


def n_dense(cfg):
    return layers(cfg) - n_moe(cfg)


def kv_heads(cfg, window):
    return _i(cfg, "swa_num_key_value_heads" if window
              else "num_key_value_heads")


def attention_params(cfg, window):
    d, H = _i(cfg, "hidden_size"), _i(cfg, "num_attention_heads")
    dk, dv, G = _i(cfg, "head_dim"), _i(cfg, "v_head_dim"), \
        kv_heads(cfg, window)
    return d * (H * dk + G * dk + G * dv) + H * dv * d


def row_bytes(cfg, window):
    """One token's K and V of one layer, content."""
    return kv_heads(cfg, window) \
        * (_i(cfg, "head_dim") + _i(cfg, "v_head_dim")) * BF16


def expert_params(cfg):
    return 3 * _i(cfg, "hidden_size") * _i(cfg, "moe_intermediate_size")


def dense_mlp_params(cfg):
    return 3 * _i(cfg, "hidden_size") * _i(cfg, "intermediate_size")


def router_params(cfg):
    return _i(cfg, "hidden_size") * _i(cfg, "router_width")


def head_params(cfg):
    return _i(cfg, "hidden_size") * _i(cfg, "vocab_size")


def experts_held(cfg):
    return int(cfg["experts_held"][1])


def dense_params(cfg):
    """Matmul weights every token passes, all layers (no routed experts, no
    head)."""
    return n_full(cfg) * attention_params(cfg, False) \
        + n_window(cfg) * attention_params(cfg, True) \
        + n_dense(cfg) * dense_mlp_params(cfg) \
        + n_moe(cfg) * router_params(cfg)


def weight_params(cfg):
    """Every parameter the chip holds: the stack, the embedding, the head."""
    return dense_params(cfg) + n_moe(cfg) * experts_held(cfg) \
        * expert_params(cfg) + 2 * head_params(cfg)


def token_flops(cfg, picks_share):
    """One token through the stack's matrices, without attention over a
    context and without the head."""
    routed = n_moe(cfg) * _i(cfg, "num_experts_per_tok") * picks_share \
        * expert_params(cfg)
    return 2 * (dense_params(cfg) + routed)


def pair_flops(cfg):
    """QK^T and PV of ONE query-key pair of one layer, all heads."""
    return 2 * _i(cfg, "num_attention_heads") \
        * (_i(cfg, "head_dim") + _i(cfg, "v_head_dim"))


def causal_pairs(pos, n):
    return n * pos + n * (n + 1) // 2


def window_pairs(pos, n, window):
    """Pairs of ``n`` rows at ``pos``: row i sees min(pos + i + 1, window)."""
    lo, hi = pos + 1, pos + n
    m = min(hi, window)
    below = (m * (m + 1) - (lo - 1) * lo) // 2 if m >= lo else 0
    return below + window * (hi - max(m, lo - 1))


def decode_step_flops(cfg, ctx_lens, picks_share):
    W = _i(cfg, "sliding_window")
    per = token_flops(cfg, picks_share) + 2 * head_params(cfg)
    return sum(per + pair_flops(cfg) * (n_full(cfg) * (c + 1)
                                        + n_window(cfg) * min(c + 1, W))
               for c in ctx_lens)


def weight_bytes(cfg):
    """Every weight a decode step streams: attention, the dense MLP, the
    float32 routers, all held experts, the untied head."""
    routers = n_moe(cfg) * router_params(cfg)
    return BF16 * (dense_params(cfg) - routers + n_moe(cfg)
                   * experts_held(cfg) * expert_params(cfg)
                   + head_params(cfg)) + F32 * routers


def decode_step_bytes(cfg, ctx_lens):
    """Weights once, and the K and V each sequence's token reads: its whole
    context in the full layers, at most the window in the others."""
    W = _i(cfg, "sliding_window")
    return weight_bytes(cfg) + sum(
        n_full(cfg) * c * row_bytes(cfg, False)
        + n_window(cfg) * min(c, W) * row_bytes(cfg, True)
        for c in ctx_lens)


def moe_stream_bytes(cfg):
    """Held experts of every expert layer, once: one decode step's stream."""
    return n_moe(cfg) * experts_held(cfg) * expert_params(cfg) * BF16


def moe_stream_flops(cfg, n_tokens, picks_share):
    return 2 * n_moe(cfg) * n_tokens * expert_params(cfg) \
        * _i(cfg, "num_experts_per_tok") * picks_share


def prefill_chunk_flops(cfg, pos, n, final, picks_share):
    W = _i(cfg, "sliding_window")
    f = n * token_flops(cfg, picks_share) + pair_flops(cfg) * (
        n_full(cfg) * causal_pairs(pos, n)
        + n_window(cfg) * window_pairs(pos, n, W))
    return f + (2 * head_params(cfg) if final else 0)


def prefill_attention_flops(cfg, pair_layers):
    """``pair_layers``: query-key pairs summed over chunks AND layers (the
    program's ``serving.attn.prefill_pairs`` / ``.window.prefill_pairs``)."""
    return pair_flops(cfg) * int(pair_layers)


def decode_attention_bytes(cfg, row_layers, window):
    """``row_layers``: K|V rows read, summed over steps AND layers (the
    program's ``serving.attn.rows_read`` / ``.window.rows_read``)."""
    return row_bytes(cfg, window) * int(row_layers)
