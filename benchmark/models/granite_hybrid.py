"""The system under test for ``family: granite_hybrid``: the program's
``HybridCausalLM`` (a ``LayerPattern`` of Mamba-2 and NoPE-attention
layers over routed gated experts) behind ``paddle_tpu.serving
.ServingEngine``, built from a configuration file and given the
benchmark's own seeded weights."""
from __future__ import annotations

import functools

from benchmark.models.fused_causal_lm import program_memory  # noqa: F401
from benchmark.reference import granite_hybrid as ref


def pattern(cfg: dict):
    """The configuration file's keys (HF ``GraniteMoeHybridConfig`` names)
    as the program's layer-pattern description."""
    from paddle_tpu.incubate.nn.layer_pattern import (
        AttentionSpec, LayerPattern, MambaSpec, MoESpec)

    D = ref.dims(cfg)
    if cfg["position_embedding_type"] != "nope":
        raise ValueError("granite_hybrid is served without positions")
    return LayerPattern(
        d_model=D.d, period=D.kinds, n_periods=1,
        attention=AttentionSpec(D.a_heads, D.a_kv, D.a_hd,
                                scale=D.a_scale, rope_theta=None),
        mamba=MambaSpec(D.m_heads, D.m_hd, D.m_state,
                        n_groups=int(cfg["mamba_n_groups"]),
                        d_conv=D.m_conv,
                        chunk_size=int(cfg["mamba_chunk_size"])),
        moe=MoESpec(D.experts, D.top_k, D.f, shared_dim=D.fs,
                    experts_held=(D.held_first, D.held)),
        norm=cfg["normalization_function"], gated=True, bias=False,
        activation=cfg["hidden_act"], epsilon=D.eps,
        embedding_multiplier=D.emb_mult, residual_multiplier=D.res_mult,
        logits_scaling=D.logit_div)


def load_weights(model, seed: int, cfg: dict):
    """Rebind the model's parameters to the reference's seeded values in
    their serving types, a layer at a time and IN PLACE (each stack is
    donated to the program that writes one layer into it): the expert
    banks are gigabytes and must never exist twice."""
    import jax
    import jax.numpy as jnp

    D = ref.dims(cfg)
    key = ref.seed_key(seed)
    st = model.stack
    pat = st.pattern

    @functools.partial(jax.jit, donate_argnums=(0,))
    def put(stack, val, i):
        return jax.lax.dynamic_update_index_in_dim(
            stack, val.astype(stack.dtype), i, 0)

    def fill(name, val, i):
        p = getattr(st, name)
        p._rebind(put(p._data, val, jnp.int32(i)))

    mixer = {k: jax.jit(functools.partial(ref.mixer_weights, D=D, kind=k))
             for k in set(D.kinds)}
    ffn = jax.jit(functools.partial(ref.ffn_weights, D=D))
    bank = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda a: a.astype(st.e_w1._data.dtype), ref.expert_bank(k, D)))
    model.embed._rebind(jax.jit(
        lambda k: ref.embedding(k, D).astype(model.embed._data.dtype))(key))
    model.norm_scale._rebind(ref.final_norm(key, D))
    names = {"mamba": {"norm": "m_norm", "in": "m_in",
                       "conv_w": "m_conv_w", "conv_b": "m_conv_b",
                       "A_log": "m_A_log", "dt_bias": "m_dt_bias",
                       "D": "m_D", "gnorm": "m_gnorm", "out": "m_out"},
             "attention": {"norm": "a_norm", "qkv": "qkv_weight",
                           "out": "out_weight"}}
    for l, kind in enumerate(D.kinds):
        lk = ref.layer_key(key, l)
        li = pat.kind_index(l)
        mw = mixer[kind](lk)
        for src, dst in names[kind].items():
            fill(dst, mw[src], li)
        fw = ffn(lk)
        for src, dst in (("norm", "f_norm"), ("router", "f_router"),
                         ("s_w1", "s_w1"), ("s_w2", "s_w2")):
            fill(dst, fw[src], l)
        w1, w2 = bank(lk)
        fill("e_w1", w1, l)
        fill("e_w2", w2, l)


def build_engine(cfg: dict, seed: int):
    """(model, engine) as a user builds them: the model's constructor from
    its layer pattern, the weights rebound to the seeded ones, then the
    engine with the geometry the configuration file states."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference.hybrid import HybridCausalLM
    from paddle_tpu.serving import ServingEngine, SLOConfig

    sv = cfg["serving"]
    if sv.get("flags"):
        paddle.set_flags(sv["flags"])
    paddle.seed(seed & 0x7FFFFFFF)
    model = HybridCausalLM(int(cfg["vocab_size"]), pattern(cfg),
                           dtype=jnp.dtype(cfg.get("weights_dtype",
                                                   "bfloat16")))
    load_weights(model, seed, cfg)
    engine = ServingEngine(model, slo=SLOConfig(**sv.get("slo", {})),
                           **sv["engine"])
    return model, engine
