"""The system under test for ``family: mistral4_mla``: the program's
``HybridCausalLM`` (a ``LayerPattern`` of latent-attention layers over
routed gated experts and a shared expert, an untied head) behind
``paddle_tpu.serving.ServingEngine``, built from a configuration file and
given the benchmark's own seeded weights."""
from __future__ import annotations

import functools

from benchmark.models.fused_causal_lm import program_memory  # noqa: F401
from benchmark.reference import mistral4_mla as ref


def pattern(cfg: dict):
    """The configuration file's keys (HF ``mistral4`` names) as the
    program's layer-pattern description."""
    from paddle_tpu.incubate.nn.layer_pattern import (
        LATENT, LatentAttentionSpec, LayerPattern, MoESpec, YarnSpec)

    D = ref.dims(cfg)
    rp = cfg["rope_parameters"]
    if rp["rope_type"] != "yarn" or not cfg["rope_interleave"] \
            or cfg["tie_word_embeddings"] or cfg["first_k_dense_replace"] \
            or cfg["n_group"] != 1 or cfg["routed_scaling_factor"] != 1:
        raise ValueError("mistral4_mla is served with interleaved YaRN "
                         "rotary, an untied head, experts in every layer, "
                         "ungrouped routing and routed_scaling_factor 1")
    return LayerPattern(
        d_model=D.d, period=(LATENT,), n_periods=D.layers,
        latent=LatentAttentionSpec(
            D.heads, D.q_rank, D.kv_rank, D.nope, D.rope, D.v,
            rope_theta=D.theta,
            yarn=YarnSpec(D.factor, D.orig, D.beta_fast, D.beta_slow,
                          D.mscale, D.mscale_all),
            temperature_beta=D.temp_beta, temperature_period=D.orig),
        moe=MoESpec(D.experts, D.top_k, D.f, shared_dim=D.fs,
                    experts_held=(D.held_first, D.held)),
        norm="rmsnorm", gated=True, bias=False,
        activation=cfg["hidden_act"], epsilon=D.eps, tie_embeddings=False)


def program_attention_weights(aw: dict, D) -> dict:
    """The reference's attention matrices in the program's layout: the
    latent + rope-key projection padded to the pool's row, ``W_ukv`` split
    into the per-head ``W_uk [H, nope, rank]`` / ``W_uv [H, rank, v]`` the
    absorbed form contracts with."""
    import jax.numpy as jnp

    W = -(-(D.kv_rank + D.rope) // 128) * 128
    ukv = aw["ukv"].reshape(D.kv_rank, D.heads, D.nope + D.v)
    return {"l_norm": aw["norm"], "l_dq": aw["dq"], "l_qnorm": aw["q_norm"],
            "l_uq": aw["uq"],
            "l_dkv": jnp.pad(aw["dkv"],
                             ((0, 0), (0, W - D.kv_rank - D.rope))),
            "l_kvnorm": aw["kv_norm"],
            "l_uk": jnp.transpose(ukv[..., :D.nope], (1, 2, 0)),
            "l_uv": jnp.transpose(ukv[..., D.nope:], (1, 0, 2)),
            "l_o": aw["o"]}


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

    @functools.partial(jax.jit, donate_argnums=(0,))
    def put(stack, val, i):
        return jax.lax.dynamic_update_index_in_dim(
            stack, val.astype(stack.dtype), i, 0)

    def fill(name, val, i):
        p = getattr(st, name)
        p._rebind(put(p._data, val, jnp.int32(i)))

    attn = jax.jit(lambda k: program_attention_weights(
        ref.attention_weights(k, D), D))
    ffn = jax.jit(functools.partial(ref.ffn_weights, D=D))
    bank = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda a: a.astype(st.e_w1._data.dtype), ref.expert_bank(k, D)))
    model.embed._rebind(jax.jit(
        lambda k: ref.embedding(k, D).astype(model.embed._data.dtype))(key))
    model.head._rebind(jax.jit(
        lambda k: ref.head(k, D).astype(model.head._data.dtype))(key))
    model.norm_scale._rebind(ref.final_norm(key, D))
    for l in range(D.layers):
        lk = ref.layer_key(key, l)
        for name, val in attn(lk).items():
            fill(name, val, l)
        fw = ffn(lk)
        for src, dst in (("norm", "f_norm"), ("router", "f_router"),
                         ("s_w1", "s_w1"), ("s_w2", "s_w2")):
            fill(dst, fw[src], l)
        w1, w2 = bank(lk)
        fill("e_w1", w1, l)
        fill("e_w2", w2, l)


def warm(engine, vocab: int):
    """Every shape the window can use, through ``submit()`` / ``run()``:
    each prefill chunk size the engine forms (the mix's shortest prompt is
    longer than a chunk, so the driver's own warm-up forms none) and the
    decode chunk."""
    import numpy as np

    rng = np.random.RandomState(12345)
    chunk, bucket = engine.slo.prefill_chunk, engine.prompt_bucket
    for c in range(bucket, chunk + 1, bucket):
        engine.submit(rng.randint(0, vocab, c - 1).tolist(),
                      max_new_tokens=engine.decode_chunk + 1)
    bad = [r.id for r in engine.run() if r.state != "ok"]
    if bad:
        raise RuntimeError(f"warm-up requests not served: {bad}")


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
    model = HybridCausalLM(int(cfg["vocab_size"]), pattern(cfg),
                           dtype=jnp.dtype(cfg.get("weights_dtype",
                                                   "bfloat16")))
    load_weights(model, seed, cfg)
    engine = ServingEngine(model, slo=SLOConfig(**sv.get("slo", {})),
                           **sv["engine"])
    warm(engine, int(cfg["vocab_size"]))
    return model, engine
