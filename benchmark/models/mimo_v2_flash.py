"""The system under test for ``family: mimo_v2_flash``: the program's
``HybridCausalLM`` (a ``LayerPattern`` of full GQA layers over a paged pool
of packed K|V rows and sliding-window GQA layers with sinks over a
slot-indexed ring, a leading dense layer, sigmoid-routed experts with a
selection bias, an untied head) behind ``paddle_tpu.serving.ServingEngine``,
built from a configuration file and given the benchmark's own seeded
weights."""
from __future__ import annotations

import functools

from benchmark.models.fused_causal_lm import program_memory  # noqa: F401
from benchmark.models.mistral4_mla import warm  # noqa: F401
from benchmark.reference import mimo_v2_flash as ref

#: the published layer list after its leading layers: [full, window x 5]
PERIOD = (0, 1, 1, 1, 1, 1)
LEAD = 5


def pattern(cfg: dict):
    """The configuration file's keys (HF ``mimo_v2_flash`` names) as the
    program's layer-pattern description: layers ``0 .. LEAD - 1`` as lead,
    then the period, cut to ``num_hidden_layers``."""
    from paddle_tpu.incubate.nn.layer_pattern import (
        ATTENTION, WINDOW, AttentionSpec, LayerPattern, MoESpec)

    D = ref.dims(cfg)
    if cfg["tie_word_embeddings"] or cfg["n_group"] != 1 \
            or cfg["n_shared_experts"] or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" \
            or cfg["routed_scaling_factor"] not in (None, 1):
        raise ValueError("mimo_v2_flash is served with an untied head, "
                         "ungrouped sigmoid routing with a selection bias, "
                         "no shared expert and routed_scaling_factor 1")
    kind = {0: ATTENTION, 1: WINDOW}
    dense = D.moe.index(1) if 1 in D.moe else D.layers
    if any(m == 0 for m in D.moe[dense:]):
        raise ValueError("mimo_v2_flash: dense layers lead, experts follow")
    n_periods = -(-max(D.layers - LEAD, 0) // len(PERIOD))
    p = LayerPattern(
        d_model=D.d, lead=tuple(kind[k] for k in D.kinds[:LEAD]),
        period=tuple(kind[k] for k in PERIOD), n_periods=n_periods,
        depth=D.layers,
        attention=AttentionSpec(
            D.heads, D.kv_full, D.dk, None, D.theta_full, v_head_dim=D.dv,
            rope_dim=D.rot, sink=D.sink_full, value_scale=D.value_scale),
        window_attention=AttentionSpec(
            D.heads, D.kv_window, D.dk, None, D.theta_window,
            v_head_dim=D.dv, rope_dim=D.rot, window=D.window,
            sink=D.sink_window, value_scale=D.value_scale),
        d_ff=D.d_ff, dense_lead=dense,
        moe=MoESpec(D.experts, D.top_k, D.f,
                    experts_held=(D.held_first, D.held),
                    routing="sigmoid_bias"),
        norm="rmsnorm", gated=True, bias=False,
        activation=cfg["hidden_act"], epsilon=D.eps, tie_embeddings=False)
    if p.kinds() != tuple(kind[k] for k in D.kinds):
        raise ValueError("mimo_v2_flash: hybrid_layer_pattern is not "
                         f"{D.kinds[:LEAD]} followed by {PERIOD} repeated")
    if D.sink_full:
        raise ValueError("mimo_v2_flash: a full-attention sink is not "
                         "served")
    return p


def program_attention_weights(aw: dict) -> dict:
    """The reference's attention matrices in the program's layout: one
    ``[q | k | v]`` projection."""
    import jax.numpy as jnp

    return {"norm": aw["norm"],
            "qkv": jnp.concatenate([aw["q"], aw["k"], aw["v"]], -1),
            "o": aw["o"], "sink": aw.get("sink")}


def load_weights(model, seed: int, cfg: dict):
    """Rebind the model's parameters to the reference's seeded values in
    their serving types, a layer at a time and IN PLACE (each stack is
    donated to the program that writes one layer into it)."""
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

    attn = jax.jit(lambda k, window: program_attention_weights(
        ref.attention_weights(k, D, window)), static_argnums=(1,))
    ffn = jax.jit(lambda k, dense: ref.ffn_weights(k, D, dense),
                  static_argnums=(1,))
    bank = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda a: a.astype(st.e_w1._data.dtype), ref.expert_bank(k, D)))
    model.embed._rebind(jax.jit(
        lambda k: ref.embedding(k, D).astype(model.embed._data.dtype))(key))
    model.head._rebind(jax.jit(
        lambda k: ref.head(k, D).astype(model.head._data.dtype))(key))
    model.norm_scale._rebind(ref.final_norm(key, D))
    for l in range(D.layers):
        lk = ref.layer_key(key, l)
        window = D.kinds[l] == 1
        li = pat.kind_index(l)
        aw = attn(lk, window)
        names = ("w_norm", "w_qkv", "w_out") if window \
            else ("a_norm", "qkv_weight", "out_weight")
        for src, dst in zip(("norm", "qkv", "o"), names):
            fill(dst, aw[src], li)
        if window and aw["sink"] is not None:
            fill("w_sink", aw["sink"], li)
        dense = D.moe[l] == 0
        fw = ffn(lk, dense)
        fill("f_norm", fw["norm"], l)
        if dense:
            fill("d_w1", fw["w1"], l)
            fill("d_w2", fw["w2"], l)
            continue
        m = pat.moe_index(l)
        fill("f_router", fw["router"], m)
        fill("e_bias", fw["bias"], m)
        w1, w2 = bank(lk)
        fill("e_w1", w1, m)
        fill("e_w2", w2, m)


def build_engine(cfg: dict, seed: int):
    """(model, engine) as a user builds them: the model's constructor from
    its layer pattern, the weights rebound to the seeded ones, the engine
    with the geometry the configuration file states, every shape warm."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference.hybrid import HybridCausalLM
    from paddle_tpu.serving import ServingEngine, SLOConfig

    sv = cfg["serving"]
    if sv.get("flags"):
        paddle.set_flags(sv["flags"])
    paddle.seed(seed & 0x7FFFFFFF)
    # no random draw: load_weights rebinds every parameter (on a TPU v5e
    # the draws' programs took 88 s of a 212 s set-up)
    model = HybridCausalLM(int(cfg["vocab_size"]), pattern(cfg),
                           dtype=jnp.dtype(cfg.get("weights_dtype",
                                                   "bfloat16")),
                           draw=False)
    load_weights(model, seed, cfg)
    engine = ServingEngine(model, slo=SLOConfig(**sv.get("slo", {})),
                           **sv["engine"])
    warm(engine, int(cfg["vocab_size"]))
    return model, engine
