"""The repo's whole-program inventory + the jaxpr dry-trace driver.

Where :mod:`.sites` enumerates ``pallas_call`` KERNEL launch sites, this
module enumerates the compiled PROGRAMS the repo actually runs — the
jit'd composite raws from ``ops/dispatch``, the whole-training-step
program (``jit/train_step.py``) and the serving prefill/decode programs
(``inference/engine.py``) — and dry-traces each one to a closed jaxpr
with ``jax.make_jaxpr`` over ShapeDtypeStructs (abstract eval: no
arrays are materialized, no XLA compile happens, so a 13B-shaped decode
program "runs" here in milliseconds on CPU).

The program-level passes consume these traces:

- :mod:`.dtype_flow`  (X-PROMOTE / X-F64)  — silent precision changes
- :mod:`.host_sync`   (X-SYNC / X-CHURN)   — host round-trips in loops
- :mod:`.hbm`         (M-HBM)              — static HBM-peak bound

Each :class:`ProgramSite` declares the properties the passes verify:
``compute_dtype`` ("bfloat16" marks a declared-bf16 serving path whose
matmuls must not silently upcast), ``hot_loop`` (decode-step semantics:
no host callback anywhere, not just inside loop bodies), and
``donate_argnums`` (feeds the donation-aware liveness walk). Findings
anchor to the site's builder, so inline ``tpu-lint: ok(...)`` waivers
work at the registration point.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

__all__ = ["ProgramSite", "TracedProgram", "PROGRAM_SITES",
           "trace_program", "trace_all_programs", "site_for_fn"]


@dataclasses.dataclass
class ProgramSite:
    name: str                   # "inference.decode", "jit.train_step", ...
    build: Callable             # () -> (fn, args) for jax.make_jaxpr
    compute_dtype: Optional[str] = None  # "bfloat16" => declared-bf16 path
    hot_loop: bool = False      # decode-step: host sync forbidden anywhere
    donate_argnums: Tuple[int, ...] = ()
    static_kwargs: Optional[Dict] = None  # jit statics to churn-check
    path: str = ""              # builder location (waiver anchor)
    line: int = 0

    def __post_init__(self):
        code = getattr(self.build, "__code__", None)
        if code is not None and not self.path:
            import os

            repo = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            fname = code.co_filename
            self.path = os.path.relpath(fname, repo) \
                if fname.startswith(repo) else fname
            self.line = code.co_firstlineno


@dataclasses.dataclass
class TracedProgram:
    site: ProgramSite
    closed: object                    # jax.extend.core.ClosedJaxpr
    donated_invars: frozenset         # flat invar indices that may die


def site_for_fn(name: str, fn, args, **kwargs) -> ProgramSite:
    """Ad-hoc site over an explicit (fn, args) pair — the synthetic-
    bad-program tests and one-off checks use this."""
    return ProgramSite(name=name, build=lambda: (fn, args), **kwargs)


@contextlib.contextmanager
def _trace_regime():
    """Trace under x64=False — the regime every compiled program in the
    repo runs with on TPU (mirrors sites._force_tpu_routing)."""
    import jax

    x64 = bool(jax.config.jax_enable_x64)
    try:
        jax.config.update("jax_enable_x64", False)
        yield
    finally:
        jax.config.update("jax_enable_x64", x64)


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _donated_flat(args, donate_argnums) -> frozenset:
    """Map positional donate_argnums to FLAT invar indices of the traced
    jaxpr (jaxpr.invars follow tree_flatten order over the args)."""
    if not donate_argnums:
        return frozenset()
    from jax import tree_util as jtu

    donated = set()
    offset = 0
    dset = set(donate_argnums)
    for i, a in enumerate(args):
        n = len(jtu.tree_leaves(a))
        if i in dset:
            donated.update(range(offset, offset + n))
        offset += n
    return frozenset(donated)


def trace_program(site: ProgramSite) -> TracedProgram:
    """Dry-trace one program site to its closed jaxpr."""
    import jax

    fn, args = site.build()
    with _trace_regime():
        closed = jax.make_jaxpr(fn)(*args)
    return TracedProgram(site=site, closed=closed,
                         donated_invars=_donated_flat(
                             args, site.donate_argnums))


def trace_all_programs(sites=None) -> Dict[str, TracedProgram]:
    """name -> trace for the full program inventory (or ``sites``)."""
    return {s.name: trace_program(s)
            for s in (PROGRAM_SITES if sites is None else sites)}


# --------------------------------------------------------------- builders
# Serving-shaped but tiny: make_jaxpr is abstract, so shapes only affect
# trace time, not memory — the composites use real serving widths, the
# engine programs a scaled-down stack (trace cost is per-eqn, and the
# decode jaxpr is shape-generic over the model dims).

def _build_gelu():
    import jax.numpy as jnp

    from ..nn.functional.activation import gelu

    return gelu.raw_fn, (_sds((32, 8192), jnp.bfloat16),)


def _build_softmax():
    import jax.numpy as jnp

    from ..nn.functional.activation import softmax

    return softmax.raw_fn, (_sds((8, 16, 512, 512), jnp.bfloat16),)


def _build_layer_norm():
    import functools

    import jax.numpy as jnp

    from ..nn.functional.norm import _layer_norm_raw

    fn = functools.partial(_layer_norm_raw, n_norm=1, epsilon=1e-5,
                           has_w=True, has_b=True)
    return fn, (_sds((32, 2048), jnp.bfloat16),
                _sds((2048,), jnp.float32), _sds((2048,), jnp.float32))


def _build_cross_entropy():
    import jax.numpy as jnp

    from ..nn.functional.loss import _cross_entropy_raw

    return _cross_entropy_raw, (_sds((64, 51200), jnp.bfloat16),
                                _sds((64,), jnp.int32))


def _build_train_step():
    """Whole-step program (fwd+bwd+AdamW) over a small MLP — the same
    ``TrainStep._pure_step`` bench.py compiles, traced with its real
    argument assembly (``_build_args``)."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 8))
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
    step = paddle.jit.TrainStep(net, F.mse_loss, opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(16, 32).astype(np.float32))
    y = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
    return step._pure_step, step._build_args([x], [y])


_ENGINE_CACHE: dict = {}


def _tiny_engine(cast_bf16: bool = True):
    """A serving GenerationEngine over a scaled-down FusedCausalLM
    (d64 L2) with a live paged pool — cached: prefill and decode sites
    share it. With ``cast_bf16`` the stack weights are cast first, so
    the engine's compute dtype matches the serving deployment
    (``_cdtype`` follows the weights) and the DTYPE pass actually
    guards the bf16 contract; the f32 variant exists for the XLA
    memory-analysis cross-check (CPU emulates bf16 through f32 temp
    copies, which would skew the comparison)."""
    if cast_bf16 in _ENGINE_CACHE:
        return _ENGINE_CACHE[cast_bf16]
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..inference.engine import FusedCausalLM, GenerationEngine
    from ..inference.kv_cache import BlockKVCacheManager

    paddle.seed(0)
    model = FusedCausalLM(vocab_size=256, embed_dim=64, num_heads=2,
                          dim_feedforward=128, num_layers=2,
                          max_position=256)
    st = model.stack
    if cast_bf16:
        for n in ("qkv", "out", "ffn1", "ffn2"):
            for suffix in ("weight", "bias"):
                p = getattr(st, f"{n}_{suffix}")
                p._rebind(p._data.astype(jnp.bfloat16))
    eng = GenerationEngine(model, page_size=16, max_length=64)
    b, pages_per_seq = 4, 4
    mgr = BlockKVCacheManager(st.num_layers, st.num_kv_heads,
                              st.head_dim, 16, num_pages=64,
                              dtype=eng._kv_dtype, reserve_scratch=True)
    for i in range(b):
        mgr.allocate(i, 16)
    tables = mgr.block_tables(range(b), pages_per_seq)
    cache = mgr.fresh_cache()
    _ENGINE_CACHE[cast_bf16] = (model, eng, cache, tables, b)
    return _ENGINE_CACHE[cast_bf16]


def _engine_common_args(model, eng, cache, tables):
    return (model.stack._stack(), model.embed._data, eng._head_t,
            model.lnf_scale._data, model.lnf_bias._data)


def _build_prefill():
    import jax.numpy as jnp

    model, eng, cache, tables, b = _tiny_engine()
    head = _engine_common_args(model, eng, cache, tables)
    args = head + (_sds((b, 16), jnp.int32), _sds((b,), jnp.int32),
                   cache.k, cache.v, tables)
    return eng._prefill_fn, args


def _build_decode():
    return build_decode_program(cast_bf16=True)


def build_decode_program(cast_bf16: bool = True):
    """(fn, args) for the k-step decode program; the f32 variant backs
    the memory_analysis cross-check test."""
    import functools

    import jax.numpy as jnp

    model, eng, cache, tables, b = _tiny_engine(cast_bf16)
    head = _engine_common_args(model, eng, cache, tables)
    fn = functools.partial(eng._decode_k_fn, k=8, sample_cfg=None)
    args = head + (_sds((b,), jnp.int32), _sds((b,), jnp.int32),
                   cache.k, cache.v, tables)
    return fn, args


def _build_decode_lora():
    """The adaptered k-step decode program (ISSUE 18): the same decode
    loop with the per-slot adapter ids and the AdapterBank's traced
    ``{proj}_a``/``{proj}_b`` operands riding along — every adapter's
    ragged grouped delta is fused onto the weight stream inside the
    step, so the hot-loop/host-sync and donation contracts must hold
    exactly as on the plain decode program."""
    import functools

    import jax.numpy as jnp

    from ..serving.adapters import AdapterBank

    model, eng, cache, tables, b = _tiny_engine()
    head = _engine_common_args(model, eng, cache, tables)
    bank = AdapterBank.from_stack(model.stack._stack(), slots=4,
                                  rank=8)
    bank.load(bank.random_adapter("site"))
    fn = functools.partial(eng._decode_k_fn, k=8, sample_cfg=None)
    args = head + (_sds((b,), jnp.int32), _sds((b,), jnp.int32),
                   cache.k, cache.v, tables, None, None,
                   _sds((b,), jnp.int32), bank.operands())
    return fn, args


def _build_spec_verify():
    """The speculative-decoding batched verify program (ISSUE 12,
    inference/speculative.py): one streamed prefill-chunk pass over the
    (k+1)-token draft window with the fused accept-prefix/bonus tail.
    Built over a bf16-cast tiny ContinuousBatchingEngine so the DTYPE
    pass guards the serving bf16 contract on the verify path too."""
    import functools

    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..inference.engine import (ContinuousBatchingEngine,
                                    FusedCausalLM)

    paddle.seed(0)
    model = FusedCausalLM(vocab_size=256, embed_dim=64, num_heads=2,
                          dim_feedforward=128, num_layers=2,
                          max_position=256)
    st = model.stack
    for n in ("qkv", "out", "ffn1", "ffn2"):
        for suffix in ("weight", "bias"):
            p = getattr(st, f"{n}_{suffix}")
            p._rebind(p._data.astype(jnp.bfloat16))
    eng = ContinuousBatchingEngine(model, max_batch=4, page_size=16,
                                   max_length=64, speculative="self",
                                   spec_k=4)
    spec = eng._spec
    b, k = eng.max_batch, spec.k
    tables = eng._mgr.block_tables(
        [("slot", i) for i in range(b)], eng._pages_per_seq,
        allow_missing=True)
    fn = functools.partial(spec._verify_fn, k=k)
    args = (eng._gen._weights(), eng._gen._embed(), eng._gen._head_t,
            model.lnf_scale._data, model.lnf_bias._data,
            _sds((b, k + 1), jnp.int32), _sds((b,), jnp.int32),
            _sds((b,), jnp.int32), _sds((b, k), jnp.int32),
            eng._ck, eng._cv, tables)
    return fn, args


def _build_varlen_packed():
    """The packed varlen flash-attention program (ISSUE 13) as the
    dispatch layer compiles it: cu_seqlens ride as TRACED operands
    (the recompile-storm fix), the XLA tile-walk fallback is the
    CPU-traced body. bf16 inputs so the DTYPE pass guards the fp32
    softmax-accumulator waivers."""
    import functools

    import jax.numpy as jnp

    from ..nn.functional.attention import _unpadded_varlen_raw

    fn = functools.partial(_unpadded_varlen_raw, scale=0.088,
                           causal=True)
    T, h, d = 1024, 8, 128
    return fn, (_sds((T, h, d), jnp.bfloat16),
                _sds((T, h, d), jnp.bfloat16),
                _sds((T, h, d), jnp.bfloat16),
                _sds((5,), jnp.int32), _sds((5,), jnp.int32))


def _build_moe_ffn():
    """The no-drop MoE FFN program (ISSUE 15): fp32 router → stable
    sort by expert → two ragged grouped GEMMs → scatter-combine, as
    the dispatch layer compiles it off-TPU (the math-identical XLA
    tile walk). bf16 inputs so the DTYPE pass guards the fp32-router
    waivers; serving-ish expert-bank widths."""
    import functools

    import jax.numpy as jnp

    from ..nn.functional.grouped_gemm import moe_ffn_nodrop

    T, d, dff, E = 256, 512, 1024, 8
    fn = functools.partial(moe_ffn_nodrop, top_k=2, activation="gelu",
                           backend="xla")
    return fn, (_sds((T, d), jnp.bfloat16),
                _sds((d, E), jnp.float32),
                _sds((E, d, dff), jnp.bfloat16),
                _sds((E, dff), jnp.float32),
                _sds((E, dff, d), jnp.bfloat16),
                _sds((E, d), jnp.float32))


def _build_kv_restore():
    """The host-tier KV restore scatter (ISSUE 20): a run of spilled
    pages lands back in the paged pool as one row-indexed scatter,
    pool donated so XLA updates in place instead of copying the whole
    cache. Pool geometry mirrors the serving default (2 layers x 64
    pages worth of rows at serving head widths)."""
    import jax.numpy as jnp

    from ..inference.kv_cache import restore_scatter

    L, P, H, ps, hd = 2, 64, 4, 8, 16
    n = 4       # pages restored per run
    return restore_scatter, (_sds((L * P, H, ps, hd), jnp.bfloat16),
                             _sds((L * n,), jnp.int32),
                             _sds((L * n, H, ps, hd), jnp.bfloat16))


PROGRAM_SITES: List[ProgramSite] = [
    ProgramSite("dispatch.gelu", _build_gelu,
                compute_dtype="bfloat16",
                static_kwargs={"approximate": False}),
    ProgramSite("dispatch.softmax", _build_softmax,
                compute_dtype="bfloat16", static_kwargs={"axis": -1}),
    ProgramSite("dispatch.layer_norm", _build_layer_norm,
                compute_dtype="bfloat16",
                static_kwargs={"n_norm": 1, "epsilon": 1e-5,
                               "has_w": True, "has_b": True}),
    ProgramSite("dispatch.cross_entropy", _build_cross_entropy,
                compute_dtype="bfloat16",
                static_kwargs={"reduction": "mean", "axis": -1}),
    ProgramSite("jit.train_step", _build_train_step,
                donate_argnums=(0, 1)),
    ProgramSite("inference.prefill", _build_prefill,
                compute_dtype="bfloat16", donate_argnums=(7, 8)),
    ProgramSite("inference.decode", _build_decode,
                compute_dtype="bfloat16", hot_loop=True,
                donate_argnums=(7, 8)),
    ProgramSite("inference.decode_lora", _build_decode_lora,
                compute_dtype="bfloat16", hot_loop=True,
                donate_argnums=(7, 8)),
    ProgramSite("serve.verify", _build_spec_verify,
                compute_dtype="bfloat16", donate_argnums=(9, 10)),
    ProgramSite("attn.varlen_packed", _build_varlen_packed,
                compute_dtype="bfloat16"),
    ProgramSite("moe.ffn", _build_moe_ffn, compute_dtype="bfloat16"),
    ProgramSite("serve.kv_restore", _build_kv_restore,
                compute_dtype="bfloat16", donate_argnums=(0,)),
]
