"""Operations and bytes that ``family: mistral4_mla`` needs, from its shapes
and the program's counters, the same whatever implements them.

A matrix is counted once for each use (two operations per weight and
token); the embedding that is looked up is not counted, the untied head is.
Latent attention is counted in the model's OWN (expanded) form, whatever the
kernels do: a token passes ``W_dq``, ``W_uq``, ``W_dkv``, ``W_ukv`` and
``W_o`` once, and a causal query-key pair costs ``heads x (qk_head_dim +
v_head_dim) x 2`` operations. The absorbed form the kernels take spends 2.5
times that on a pair (a 384-lane key, a 256-lane value) and none on
``W_ukv`` for cached tokens; neither shows here. BYTES of the latent pool
are counted as STORED: ``row_width`` (384) values a token and layer, the 64
pad lanes among them.

Routed experts are counted for the picks that LANDED on an expert held here
(``picks_share`` of top_k a token and layer); their bytes are every held
expert once a decode step.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _i(cfg, k):
    return int(cfg[k])


def layers(cfg):
    return _i(cfg, "num_hidden_layers")


def row_width(cfg):
    used = _i(cfg, "kv_lora_rank") + _i(cfg, "qk_rope_head_dim")
    return -(-used // 128) * 128


def latent_row_bytes(cfg):
    """One token's cache row of one layer, as stored."""
    return row_width(cfg) * BF16


def attention_params(cfg):
    d, H = _i(cfg, "hidden_size"), _i(cfg, "num_attention_heads")
    qr, kr = _i(cfg, "q_lora_rank"), _i(cfg, "kv_lora_rank")
    n, r, v = (_i(cfg, "qk_nope_head_dim"), _i(cfg, "qk_rope_head_dim"),
               _i(cfg, "v_head_dim"))
    return d * qr + qr * H * (n + r) + d * (kr + r) + kr * H * (n + v) \
        + H * v * d


def expert_params(cfg):
    return 3 * _i(cfg, "hidden_size") * _i(cfg, "moe_intermediate_size")


def shared_params(cfg):
    return _i(cfg, "n_shared_experts") * expert_params(cfg)


def router_params(cfg):
    return _i(cfg, "hidden_size") * _i(cfg, "router_width")


def head_params(cfg):
    return _i(cfg, "hidden_size") * _i(cfg, "vocab_size")


def experts_held(cfg):
    return int(cfg["experts_held"][1])


def dense_params(cfg):
    """Matmul weights every token passes, all layers (no experts, no head)."""
    return layers(cfg) * (attention_params(cfg) + shared_params(cfg)
                          + router_params(cfg))


def token_flops(cfg, picks_share):
    """One token through the stack's matrices, without attention over a
    context and without the head."""
    routed = layers(cfg) * _i(cfg, "num_experts_per_tok") * picks_share \
        * expert_params(cfg)
    return 2 * (dense_params(cfg) + routed)


def pair_flops(cfg):
    """QK^T and PV of ONE causal query-key pair of one layer, all heads."""
    return 2 * _i(cfg, "num_attention_heads") \
        * (_i(cfg, "qk_nope_head_dim") + _i(cfg, "qk_rope_head_dim")
           + _i(cfg, "v_head_dim"))


def attention_flops(cfg, attended):
    """Query tokens that attend ``attended`` keys in all, every layer."""
    return pair_flops(cfg) * layers(cfg) * int(attended)


def causal_pairs(pos, n):
    return n * pos + n * (n + 1) // 2


def decode_step_flops(cfg, ctx_lens, picks_share):
    per = token_flops(cfg, picks_share) + 2 * head_params(cfg)
    return sum(per + attention_flops(cfg, c + 1) for c in ctx_lens)


def weight_bytes(cfg):
    """Every weight a decode step streams: latent attention, the shared
    experts, the float32 routers, all held experts, the untied head."""
    n = layers(cfg)
    return BF16 * (n * (attention_params(cfg) + shared_params(cfg)
                        + experts_held(cfg) * expert_params(cfg))
                   + head_params(cfg)) + F32 * n * router_params(cfg)


def decode_step_bytes(cfg, ctx_lens):
    return weight_bytes(cfg) \
        + sum(ctx_lens) * layers(cfg) * latent_row_bytes(cfg)


def moe_stream_bytes(cfg):
    """Held experts of every layer, once: one decode step's expert stream."""
    return layers(cfg) * experts_held(cfg) * expert_params(cfg) * BF16


def moe_stream_flops(cfg, n_tokens, picks_share):
    return 2 * layers(cfg) * n_tokens * expert_params(cfg) \
        * _i(cfg, "num_experts_per_tok") * picks_share


def prefill_chunk_flops(cfg, pos, n, final, picks_share):
    f = n * token_flops(cfg, picks_share) \
        + attention_flops(cfg, causal_pairs(pos, n))
    return f + (2 * head_params(cfg) if final else 0)


def mla_prefill_flops(cfg, pair_layers):
    """``pair_layers``: causal pairs summed over chunks AND layers (the
    program's ``serving.mla.prefill_pairs``)."""
    return pair_flops(cfg) * int(pair_layers)


def mla_decode_bytes(cfg, row_layers):
    """``row_layers``: latent rows read, summed over steps AND layers (the
    program's ``serving.mla.rows_read``)."""
    return latent_row_bytes(cfg) * int(row_layers)
