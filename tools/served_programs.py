#!/usr/bin/env python
"""served_programs — the lowered text of the programs the benchmark's
serving cells run, without a chip and without weights.

For each configuration under ``benchmark/configs`` this lowers (does not
compile, does not run) the decode-chunk and the prefill-chunk program
its engine would jit, at the cell's own shapes (slots, pages, chunks,
widths, depth), routed as on the chip, and writes the StableHLO text
without source locations to ``<out>/<config>.<program>.mlir``. Two trees
serve the same programs exactly when these files are equal:

    python tools/served_programs.py /tmp/a      # in each tree
    diff -r /tmp/a /tmp/b

Models are built under ``jax.eval_shape`` (their constructors run, no
array is ever alive); the engines' program families are bare objects
with the few attributes the pure program functions read.
"""
from __future__ import annotations

import functools
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

_sds = jax.ShapeDtypeStruct


def _abstract_model(build):
    """``build()`` under eval_shape: (the model object, its stacked
    weights and its embedding as ShapeDtypeStructs). The object keeps
    tracers where its parameters were; only its configuration is read
    afterwards."""
    import paddle_tpu as paddle

    held = []

    def run():
        held.append(build())
        return held[0].stack._stack(), held[0].embed._data

    weights, embed = jax.eval_shape(run)
    paddle.seed(0)            # the generator's key was traced: reset it
    return held[0], weights, embed


def _bare(cls, **attrs):
    obj = object.__new__(cls)
    for n, v in attrs.items():
        object.__setattr__(obj, n, v)
    return obj


def _geometry(sv):
    """(slots, pages a sequence, pool pages a layer, page size, decode
    chunk, prefill chunk) as the engine derives them (one scratch page,
    the pool rounded by the engine's own rule)."""
    from paddle_tpu.inference.engine import _round_pool_pages

    e = sv["engine"]
    ps = e["page_size"]
    return (e["max_batch"], -(-e["max_length"] // ps),
            _round_pool_pages(e["num_pages"] + 1, ps), ps,
            e["decode_chunk"], sv["slo"]["prefill_chunk"])


def uniform_programs(cfg):
    """``family: fused_causal_lm``: ``GenerationEngine._decode_k_fn`` and
    ``ServingEngine._chunk_prefill_fn`` over bf16 stacks and pool."""
    from paddle_tpu.incubate.nn.fused_transformer import rope_table
    from paddle_tpu.inference import FusedCausalLM
    from paddle_tpu.inference.engine import GenerationEngine
    from paddle_tpu.serving import ServingEngine

    slots, pp, pages, ps, k, c = _geometry(cfg["serving"])
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    model, w, embed = _abstract_model(lambda: FusedCausalLM(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["d_model"],
        num_heads=cfg["n_heads"], dim_feedforward=cfg["d_ff"],
        num_layers=cfg["n_layers"],
        max_position=cfg["serving"]["engine"]["max_length"] + 1,
        rope_theta=cfg.get("rope_theta", 10000.0)))
    # the benchmark's serving types: bf16 matmul stacks and biases
    w = {n: _sds(a.shape, f32 if n.startswith("ln") else bf)
         for n, a in w.items()}
    st, d = model.stack, cfg["d_model"]
    cos, sin = rope_table(st.max_position, st.head_dim, st.rope_theta)
    gen = _bare(GenerationEngine, model=model, _cdtype=bf, _a8w8=False,
                _tp=None, _cos=cos, _sin=sin)
    eng = _bare(ServingEngine, model=model, _gen=gen)
    lead = (w, embed, _sds((d, cfg["vocab_size"]), bf), _sds((d,), f32),
            _sds((d,), f32))
    pool = _sds((st.num_layers * pages, st.num_kv_heads, ps, st.head_dim),
                bf)
    return {
        "decode_chunk": (
            jax.jit(functools.partial(gen._decode_k_fn, k=k),
                    donate_argnums=(7, 8)),
            (*lead, _sds((slots,), i32), _sds((slots,), i32), pool, pool,
             _sds((slots, pp), i32))),
        "prefill_chunk": (
            jax.jit(eng._chunk_prefill_fn, donate_argnums=(8, 9)),
            (*lead, _sds((1, c), i32), _sds((1,), i32), _sds((1,), i32),
             pool, pool, _sds((1, pp), i32)))}


def hybrid_programs(cfg):
    """``family: granite_hybrid``: ``HybridPrograms``' two programs, the
    recurrent state donated beside the pool."""
    from benchmark.models.granite_hybrid import pattern
    from paddle_tpu.incubate.nn.hybrid_stack import RecurrentState
    from paddle_tpu.inference.hybrid import HybridCausalLM, HybridPrograms

    slots, pp, pages, ps, k, c = _geometry(cfg["serving"])
    dt = jnp.dtype(cfg.get("weights_dtype", "bfloat16"))
    f32, i32 = jnp.float32, jnp.int32
    model, w, embed = _abstract_model(lambda: HybridCausalLM(
        int(cfg["vocab_size"]), pattern(cfg), dtype=dt))
    p = model.stack.pattern
    att, m = p.attention, p.mamba
    gen = _bare(HybridPrograms, model=model, _cdtype=dt, _cos=None,
                _sin=None)
    pool = _sds((p.n_attention * pages, att.num_kv_heads, ps,
                 att.head_dim), dt)
    rs = RecurrentState(
        _sds((p.n_mamba, slots, m.d_state, m.d_inner), f32),
        _sds((p.n_mamba, slots, m.d_conv - 1, m.conv_dim), dt))
    lead = (w, embed, embed, _sds((p.d_model,), f32), None)
    return {
        "decode_chunk": (
            jax.jit(functools.partial(gen._decode_k_fn, k=k),
                    donate_argnums=(7, 8, 9)),
            (*lead, _sds((slots,), i32), _sds((slots,), i32), pool, pool,
             rs, _sds((slots, pp), i32), _sds((slots,), jnp.bool_))),
        "prefill_chunk": (
            jax.jit(gen._chunk_prefill_fn, donate_argnums=(8, 9, 10)),
            (*lead, _sds((1, c), i32), _sds((1,), i32), _sds((1,), i32),
             pool, pool, rs, _sds((1, pp), i32), _sds((1,), i32),
             _sds((1,), jnp.bool_)))}


FAMILIES = {"fused_causal_lm": uniform_programs,
            "granite_hybrid": hybrid_programs}


def lowered_text(jitted, args) -> str:
    """StableHLO of ``jitted`` at ``args`` for the TPU, routed as on the
    chip (the probe patched as tests/test_chip_compile.py patches it),
    every ``loc(...)`` and ``#loc`` line dropped and the kernels'
    serialized bodies stripped of theirs."""
    from jax._src import tpu_custom_call as tcc
    from jaxlib.mlir.passmanager import PassManager

    from paddle_tpu.device import chip

    serialize = tcc._lower_mosaic_module_to_asm

    def without_locations(module, **kw):
        with module.context:
            PassManager.parse("builtin.module(strip-debuginfo)").run(
                module.operation)
        return serialize(module, **kw)

    probe, chip.on_tpu = chip.on_tpu, lambda: True
    tcc._lower_mosaic_module_to_asm = without_locations
    try:
        text = jitted.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        chip.on_tpu = probe
        tcc._lower_mosaic_module_to_asm = serialize
    text = re.sub(r"\s*loc\((?:[^()]|\([^()]*\))*\)", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not line.startswith("#loc")) + "\n"


def write_programs(out: str, configs=None) -> dict:
    """Write every program of ``configs`` (names; default: all) under
    ``out``; returns path -> text."""
    os.makedirs(out, exist_ok=True)
    cfg_dir = os.path.join(ROOT, "benchmark", "configs")
    written = {}
    for fname in sorted(os.listdir(cfg_dir)):
        with open(os.path.join(cfg_dir, fname)) as f:
            cfg = json.load(f)
        if configs and cfg["name"] not in configs:
            continue
        for prog, (jitted, operands) in FAMILIES[cfg["family"]](cfg).items():
            path = os.path.join(out, f"{cfg['name']}.{prog}.mlir")
            written[path] = lowered_text(jitted, operands)
            with open(path, "w") as f:
                f.write(written[path])
    return written


if __name__ == "__main__":
    for path, text in write_programs(*sys.argv[1:2]).items():
        print(f"{path}: {len(text.splitlines())} lines, "
              f"{text.count('tpu_custom_call')} tpu_custom_call")
