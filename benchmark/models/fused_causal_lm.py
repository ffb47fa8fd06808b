"""The system under test for ``family: fused_causal_lm``: the program's
``FusedCausalLM`` behind ``paddle_tpu.serving.ServingEngine``, built from a
configuration file and given the benchmark's own seeded weights."""
from __future__ import annotations

from benchmark.reference import fused_causal_lm as ref


def build_engine(cfg: dict, seed: int):
    """(model, engine) as a user builds them: the model's constructor, the
    weights rebound to the seeded ones in their serving types, then the
    engine with the geometry the configuration file states."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import FusedCausalLM
    from paddle_tpu.serving import ServingEngine, SLOConfig

    sv = cfg["serving"]
    if sv.get("flags"):
        paddle.set_flags(sv["flags"])
    paddle.seed(seed & 0x7FFFFFFF)
    eng = dict(sv["engine"])
    model = FusedCausalLM(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["d_model"],
        num_heads=cfg["n_heads"], dim_feedforward=cfg["d_ff"],
        num_layers=cfg["n_layers"], max_position=eng["max_length"] + 1,
        rope_theta=cfg.get("rope_theta", 10000.0))
    w = ref.make_weights(seed, cfg)
    model.embed._rebind(w["embed"])
    model.lnf_scale._rebind(w["lnf_scale"])
    model.lnf_bias._rebind(w["lnf_bias"])
    for n in ref.STACKED:
        getattr(model.stack, n)._rebind(w[n])
    del w
    engine = ServingEngine(model, slo=SLOConfig(**sv.get("slo", {})), **eng)
    return model, engine


def program_memory(engine) -> dict:
    """name -> argument + temp bytes of each serving program that compiled
    (the compiler's own count; the runtime's peak counter leaves the temp
    out, PERF.md section 7)."""
    progs = list(engine._chunk_jit.values()) \
        + list(engine._gen._decode_k_jit.values())
    out = {}
    for prog in progs:
        for exe in prog._exes.values():
            m = exe.memory_analysis()
            if m is not None:
                out[prog.name] = int(m.argument_size_in_bytes
                                     + m.temp_size_in_bytes)
    return out
