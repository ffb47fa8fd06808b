"""Compile rehearsals: every main-path Pallas kernel, compiled for a
DESCRIBED TPU v5e (no chip attached) with ``interpret=False``.

Interpret-mode tests cannot see what the chip's compiler refuses — a
dot precision Mosaic does not lower, a block the tiling rejects, more
fast memory than a kernel may use. These compiles can, in about two
seconds each, at the 1.3B serving/training widths (d_model 2048, ffn
8192, 16 heads x 128, page 16), under the package's real config (x64
on, ``jax_default_matmul_precision="high"``).

Rules this file keeps (see the on-chip-measurement guide):
- the topology is described inside a module-scoped fixture, never at
  import, in a ``skipif``, in ``parametrize`` arguments or in conftest —
  only the xdist worker that is handed this file loads the TPU library;
- everything compiles in the test's own process;
- ONE file holds all of them (a second file could land on a worker that
  cannot load the library and would skip in silence);
- the persistent compile cache is off around them (a compile for a
  described device is written but cannot be read back without a chip).

A compile that passes is not a chip run: nothing here says the kernels
are right or fast.
"""
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (the package's jax config is part of the test)

#: static list — parametrize arguments must not touch the topology
KERNELS = [
    "stream_linear.bf16",
    "stream_linear.a8w8",
    "stream_layer_tail.bf16",
    "stream_layer_tail.bf16.next_qkv",
    "stream_layer_tail.int8",
    "stream_layer_tail.int8.next_qkv",
    "paged_attention.decode_inplace",
    "paged_attention.decode_inplace_q",
    "flash_varlen.packed_fwd",
    "flash_varlen.packed_bwd",
    "flash_varlen.paged",
    "flash_varlen.paged.c256",
    "flash_varlen.paged.c512",
    "paged_kv.write",
    "paged_kv.write.c256",
    "grouped_gemm.fwd",
    "grouped_gemm.bwd",
    "lora.delta",
    "attention.flash",
    "ssd.chunk_scan",
    "ssm.decode_update",
    "moe.stream_experts",
    "mla.paged_prefill",
    "mla.paged_decode",
]

L, D, DFF, NQ, HEADS, HEAD_DIM, PAGE = 24, 2048, 8192, 3 * 2048, 16, 128, 16


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _layer_tail(int8: bool, next_qkv: bool):
    """stream_layer_tail at the real depth (L=24): stacked weights, a
    traced layer index, bf16 or weight-only-int8 stacks (so/s1/s2
    dequant scales), with and without the cross-layer QKV prefetch."""
    import paddle_tpu.nn.functional.stream_linear as sl

    bf, f32 = jnp.bfloat16, jnp.float32
    wdt = jnp.int8 if int8 else bf

    def fn(att, h, wo, w1, w2, bo, b1, b2, ln2s, ln2b, so, s1, s2,
           wq, bq, sq, ln1s, ln1b, layer):
        nq = None
        if next_qkv:
            nq = {"w": wq, "b": bq, "ln_s": ln1s, "ln_b": ln1b,
                  "layer": jnp.minimum(layer + 1, L - 1)}
            if int8:
                nq["s"] = sq
        scales = dict(so=so, s1=s1, s2=s2) if int8 else {}
        return sl.stream_layer_tail(
            att, h, wo, w1, w2, layer=layer, bo=bo, b1=b1, b2=b2,
            ln2_scale=ln2s, ln2_bias=ln2b, epsilon=1e-5,
            activation="gelu", next_qkv=nq, **scales)

    args = (_sds((8, D), bf), _sds((8, D), bf),
            _sds((L, D, D), wdt), _sds((L, D, DFF), wdt),
            _sds((L, DFF, D), wdt),
            _sds((L, D), bf), _sds((L, DFF), bf), _sds((L, D), bf),
            _sds((L, D), bf), _sds((L, D), bf),
            _sds((L, D), f32), _sds((L, DFF), f32), _sds((L, D), f32),
            _sds((L, D, NQ), wdt), _sds((L, NQ), bf), _sds((L, NQ), f32),
            _sds((L, D), bf), _sds((L, D), bf),
            _sds((), jnp.int32))
    return fn, args


def _paged_prefill(chunk: int):
    """The default chunked-prefill attention at the serving engine's own
    geometry: one row, 16 heads x 128, page 16, the 8 x 1088-token pool
    of chip_smoke.py (576 pages x 24 layers), 68 pages per sequence."""
    from paddle_tpu.nn.functional.flash_varlen import (
        paged_prefill_attention)

    def fn(q, kc, vc, tables, start):
        return paged_prefill_attention(q, kc, vc, tables, start,
                                       n_kv=HEADS, backend="pallas")

    pool = _sds((L * 576, HEADS, PAGE, HEAD_DIM), jnp.bfloat16)
    return fn, (_sds((1, chunk, HEADS, HEAD_DIM), jnp.bfloat16), pool,
                pool, _sds((1, 68), jnp.int32), _sds((1,), jnp.int32))


def _build(name):
    if name.startswith("stream_layer_tail."):
        return _layer_tail(int8=".int8" in name,
                           next_qkv=name.endswith(".next_qkv"))
    if name.startswith("flash_varlen.paged.c"):
        return _paged_prefill(int(name.rsplit("c", 1)[1]))
    if name.startswith("paged_kv.write.c"):
        from paddle_tpu.analysis.sites import _build_paged_kv_write

        return _build_paged_kv_write(int(name.rsplit("c", 1)[1]))
    from paddle_tpu.analysis.sites import KERNEL_SITES

    # the lint's own inventory (analysis/sites.py) at its 1.3B widths
    return {s.name: s for s in KERNEL_SITES}[name].build()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_chip(monkeypatch):
    """Route the kernel modules as on the chip: the one platform probe
    answers True, so launches take ``interpret=False`` and trace with
    x64 off (paged_attention._enable_x64) exactly as they do there."""
    from paddle_tpu.device import chip

    monkeypatch.setattr(chip, "on_tpu", lambda: True)


def _placed(args, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding), args)


def _kernel_names(hlo_text):
    """Names of the compiled Pallas launches (what the device trace
    shows), numeric suffixes dropped."""
    return {m.group(1) for m in re.finditer(
        r"%([\w-]+?)(?:\.\d+)* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo_text)}


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, one_chip, as_on_chip):
    assert jax.config.jax_enable_x64            # the package's config
    assert jax.config.jax_default_matmul_precision == "high"
    fn, args = _build(name)
    text = jax.jit(fn).lower(*_placed(args, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text, (
        f"{name}: compiled without a Pallas kernel — a reference path "
        f"was taken")
    # the compiled instruction's name is what the device trace shows:
    # each launch carries its name from the inventory's table, none is
    # left as ``closed_call`` / ``custom-call``
    from paddle_tpu.analysis.sites import KERNEL_SITES

    site = {s.name: s for s in KERNEL_SITES}.get(
        re.sub(r"\.c\d+$", "", name))
    shown = _kernel_names(text)
    assert all(n.startswith("pt_") for n in shown), shown
    if site is not None:
        assert shown == set(site.kernels)


#: granite-4.0-h-small's prefill chunk: 256 tokens x top-10 sorted rows
#: against the layer-stacked bank of 10 layers x 36 held experts, read in
#: place from layer 5's first group (gate/up d -> 2f, then down f -> d)
_BANKED = {"up": (4096, 1536), "down": (768, 4096)}


@pytest.mark.parametrize("gemm", sorted(_BANKED))
def test_banked_grouped_gemm_compiles_at_the_cell_shapes(gemm, one_chip,
                                                         as_on_chip):
    """``grouped_gemm_banked`` as ``moe_gated_grouped`` calls it in the
    rag cell (the ``grouped_gemm.fwd`` site compiles ``grouped_gemm`` at
    the 1.3B widths, never the bank read in place): it compiles for the
    chip under ``KERNEL_VMEM_LIMIT_BYTES`` with the blocks ``_geometry``
    chooses for these widths, as one ``pt_grouped_gemm_fwd`` launch and
    with no copy of the bank."""
    from paddle_tpu.device.vmem import KERNEL_VMEM_LIMIT_BYTES
    from paddle_tpu.nn.functional.grouped_gemm import (_geometry,
                                                       grouped_gemm_banked)

    K, N = _BANKED[gemm]
    rows, held, layers = 256 * 10, 36, 10
    bm, bn = _geometry(K, N, 2)
    assert (bm, bn) == (128, N)        # each expert matrix: one block
    # x tile, weight and zero-bias blocks, f32 out tile, each in two
    # buffers
    blocks = 2 * (bm * K * 2 + K * bn * 2 + bn * 4 + bm * bn * 4)
    assert blocks < KERNEL_VMEM_LIMIT_BYTES // 2

    def fn(x, bank, offsets):
        return grouped_gemm_banked(x, bank, offsets, 5 * held,
                                   backend="pallas")

    bank = _sds((layers * held, K, N), jnp.bfloat16)
    args = (_sds((rows, K), jnp.bfloat16), bank,
            _sds((held + 1,), jnp.int32))
    compiled = jax.jit(fn).lower(*_placed(args, one_chip)).compile()
    text = compiled.as_text()
    assert _kernel_names(text) == {"pt_grouped_gemm_fwd"}
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2 * math.prod(bank.shape) // 100


def _uniform_stack():
    """A parameterless ``FusedMultiTransformer`` at the gpt3-1.3b cells'
    widths and depth (as ``_tp_view`` builds one: the raw methods read
    config attributes and the weights they are handed) and the shapes
    of its bf16 weight stacks."""
    from paddle_tpu.incubate.nn.fused_transformer import (
        FusedMultiTransformer)

    st = object.__new__(FusedMultiTransformer)
    for n, v in dict(embed_dim=D, head_dim=HEAD_DIM, dim_feedforward=DFF,
                     num_layers=L, num_heads=HEADS, num_kv_heads=HEADS,
                     activation="gelu", epsilon=1e-5, rope_theta=1e4,
                     max_position=4096, moe_num_experts=None,
                     moe_top_k=2).items():
        object.__setattr__(st, n, v)
    w = {"ln1_scale": (L, D), "ln1_bias": (L, D),
         "qkv_weight": (L, D, NQ), "qkv_bias": (L, NQ),
         "out_weight": (L, D, D), "out_bias": (L, D),
         "ln2_scale": (L, D), "ln2_bias": (L, D),
         "ffn1_weight": (L, D, DFF), "ffn1_bias": (L, DFF),
         "ffn2_weight": (L, DFF, D), "ffn2_bias": (L, D)}
    return st, {n: _sds(s, jnp.bfloat16) for n, s in w.items()}


def _prefill_chunk_program(chunk: int, pages: int):
    """``prefill_chunk_raw`` as the serving engine jits it: the cell's
    widths and depth, bf16 weight stacks, one row of ``chunk`` tokens, a
    pool of ``pages`` pages a layer, both pool sides donated."""
    from paddle_tpu.incubate.nn.fused_transformer import PagedKV

    st, w = _uniform_stack()
    bf = jnp.bfloat16

    def fn(w, x, ck, cv, tables, start, lens, cos, sin):
        h, cache = st.prefill_chunk_raw(w, x, PagedKV(ck, cv), tables,
                                        start, lens, cos, sin)
        return h, cache.k, cache.v

    pool = _sds((L * pages, HEADS, PAGE, HEAD_DIM), bf)
    rope = _sds((4096, HEAD_DIM // 2), jnp.float32)
    args = (w, _sds((1, chunk, D), bf), pool, pool,
            _sds((1, 160), jnp.int32), _sds((1,), jnp.int32),
            _sds((1,), jnp.int32), rope, rope)
    return jax.jit(fn, donate_argnums=(2, 3)), args, pool


def _uniform_decode_program():
    """``FusedMultiTransformer.decode_raw`` at gpt3-1.3b's serving cells:
    32 rows, tables of 160 pages, a pool of 1,664 pages a layer
    (``[39936, 16, 16, 128]``), both sides donated."""
    from paddle_tpu.incubate.nn.fused_transformer import PagedKV

    st, w = _uniform_stack()
    bf = jnp.bfloat16

    def fn(w, x, ck, cv, tables, lens, cos, sin):
        h, cache = st.decode_raw(w, x, PagedKV(ck, cv), tables, lens,
                                 cos, sin)
        return h, cache.k, cache.v

    pool = _sds((L * 1664, HEADS, PAGE, HEAD_DIM), bf)
    rope = _sds((4096, HEAD_DIM // 2), jnp.float32)
    args = (w, _sds((32, D), bf), pool, pool, _sds((32, 160), jnp.int32),
            _sds((32,), jnp.int32), rope, rope)
    return jax.jit(fn, donate_argnums=(2, 3)), args, pool


def _hybrid_decode_program():
    """``HybridStack.decode_raw`` at granite-4.0-h-small's cell: 64 rows,
    tables of 321 pages, the one attention layer's pool of 20,544 pages
    (``[20544, 8, 16, 128]``) and the recurrent state, all donated. The
    pattern keeps the published widths of mixer, attention and experts
    and is cut to ONE mamba layer before the attention layer and 4 held
    experts of the 72: the cell's ten layers take 144 s to compile, and
    neither the other mixers nor the other experts touch the pool."""
    from paddle_tpu.incubate.nn.hybrid_stack import (HybridStack,
                                                     RecurrentState)
    from paddle_tpu.incubate.nn.fused_transformer import PagedKV
    from paddle_tpu.incubate.nn.layer_pattern import (
        ATTENTION, MAMBA, AttentionSpec, LayerPattern, MambaSpec, MoESpec)

    d = 4096
    p = LayerPattern(
        d_model=d, period=(MAMBA, ATTENTION), n_periods=1,
        attention=AttentionSpec(32, 8, HEAD_DIM, scale=0.0078125,
                                rope_theta=None),
        mamba=MambaSpec(num_heads=128, head_dim=64, d_state=128),
        moe=MoESpec(72, 10, 768, shared_dim=1536, experts_held=(0, 4)),
        norm="rmsnorm", gated=True, bias=False, activation="silu",
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=16.0)
    st = object.__new__(HybridStack)
    object.__setattr__(st, "pattern", p)
    m, moe, bf, f32 = p.mamba, p.moe, jnp.bfloat16, jnp.float32
    w = {"m_norm": ((1, d), f32), "m_in": ((1, d, m.in_proj_dim), bf),
         "m_conv_w": ((1, m.d_conv, m.conv_dim), f32),
         "m_conv_b": ((1, m.conv_dim), f32),
         "m_dt_bias": ((1, m.num_heads), f32),
         "m_A_log": ((1, m.num_heads), f32), "m_D": ((1, m.num_heads), f32),
         "m_gnorm": ((1, m.d_inner), f32), "m_out": ((1, m.d_inner, d), bf),
         "a_norm": ((1, d), f32), "qkv_weight": ((1, d, 48 * HEAD_DIM), bf),
         "out_weight": ((1, 32 * HEAD_DIM, d), bf),
         "f_norm": ((2, d), f32), "f_router": ((2, d, 72), f32),
         "e_w1": ((2, 4, d, 2 * moe.expert_dim), bf),
         "e_w2": ((2, 4, moe.expert_dim, d), bf),
         "s_w1": ((2, d, 2 * moe.shared_dim), bf),
         "s_w2": ((2, moe.shared_dim, d), bf)}
    w = {n: _sds(*sd) for n, sd in w.items()}

    def fn(w, x, ck, cv, ssm, conv, tables, lens, active):
        h, cache, state, counts = st.decode_raw(
            w, x, PagedKV(ck, cv), RecurrentState(ssm, conv), tables,
            lens, active)
        return h, cache.k, cache.v, state.ssm, state.conv, counts

    pool = _sds((20544, 8, PAGE, HEAD_DIM), bf)
    args = (w, _sds((64, d), bf), pool, pool,
            _sds((1, 64, m.d_state, m.d_inner), f32),
            _sds((1, 64, m.d_conv - 1, m.conv_dim), bf),
            _sds((64, 321), jnp.int32), _sds((64,), jnp.int32),
            _sds((64,), jnp.bool_))
    return jax.jit(fn, donate_argnums=(2, 3, 4, 5)), args, pool


def _pool_copies(text, pool):
    """The optimised HLO's ``copy`` instructions of the pool's shape."""
    shape = "bf16[%s]" % ",".join(map(str, pool.shape))
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(r"= " + re.escape(shape) + r"\S* copy\(", line)]


@pytest.mark.parametrize("chunk", [64, 256])
def test_prefill_chunk_program_never_copies_the_pool(chunk, one_chip,
                                                     as_on_chip):
    """The layer loop carries the pool through two Pallas calls a layer
    (``pt_paged_kv_write`` aliases it, ``pt_flash_varlen_paged`` reads
    it) and nothing else, so layout assignment leaves it in the default
    layout from entry to exit: no ``copy`` of the pool's shape in the
    optimised HLO, and the program's temp stays far under one pool
    side. With the XLA scatter in the loop this read 6 pool-shaped
    copies (2 in the loop body, 4 at entry and exit) and a temp of two
    pool sides (PR 29's parent, same compile)."""
    jitted, args, pool = _prefill_chunk_program(chunk, pages=256)
    compiled = jitted.lower(*_placed(args, one_chip)).compile()
    text = compiled.as_text()
    assert not _pool_copies(text, pool)
    assert _kernel_names(text) == {"pt_paged_kv_write",
                                   "pt_flash_varlen_paged"}
    side = 2 * math.prod(pool.shape)                # bf16 bytes
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < side // 4, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == 2 * side      # both sides donated


@pytest.mark.parametrize("stack", ["uniform", "hybrid"])
def test_decode_program_never_copies_the_pool(stack, one_chip, as_on_chip):
    """The decode step of both stacks at their cells' shapes: the pool
    passes through ``pt_paged_attention_decode_inplace`` (aliased) and
    no other instruction, the step's page walk is a few small int32
    operands beside it, and nothing of the pool's shape is copied; both
    sides stay donated and the program's temp far under one side."""
    jitted, args, pool = (_uniform_decode_program() if stack == "uniform"
                          else _hybrid_decode_program())
    compiled = jitted.lower(*_placed(args, one_chip)).compile()
    text = compiled.as_text()
    assert not _pool_copies(text, pool)
    assert "pt_paged_attention_decode_inplace" in _kernel_names(text)
    side = 2 * math.prod(pool.shape)                # bf16 bytes
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < side // 4, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= 2 * side      # both sides donated


def _latent_program(phase: str):
    """``HybridStack`` over latent-attention layers at the published widths
    of Mistral-Small-4-119B-2603 and its cell's shapes (24 rows or a
    1,024-token chunk, tables of 2,096 pages, a latent pool of 50,368
    pages a layer, ``[100736, 16, 384]``, donated), cut to TWO layers and 4
    held experts of the 128: the cell's five layers only repeat these, and
    the other experts do not touch the pool."""
    from paddle_tpu.incubate.nn.hybrid_stack import HybridStack
    from paddle_tpu.incubate.nn.layer_pattern import (
        LATENT, LatentAttentionSpec, LayerPattern, MoESpec, YarnSpec)
    from paddle_tpu.nn.functional.mla_attention import LatentKV

    d, nl, held, pages, pp = 4096, 2, 4, 50368, 2096
    lt = LatentAttentionSpec(
        32, 1024, 256, 64, 64, 128,
        yarn=YarnSpec(128.0, 8192, 32.0, 1.0, 1.0, 1.0),
        temperature_beta=0.1, temperature_period=8192)
    p = LayerPattern(
        d_model=d, period=(LATENT,), n_periods=nl, latent=lt,
        moe=MoESpec(128, 4, 2048, shared_dim=2048,
                    experts_held=(0, held)),
        norm="rmsnorm", gated=True, bias=False, activation="silu",
        epsilon=1e-6, tie_embeddings=False)
    st = object.__new__(HybridStack)
    object.__setattr__(st, "pattern", p)
    bf, f32, W, R = jnp.bfloat16, jnp.float32, lt.row_width, 256
    w = {"l_norm": ((nl, d), f32), "l_dq": ((nl, d, 1024), bf),
         "l_qnorm": ((nl, 1024), f32), "l_uq": ((nl, 1024, 4096), bf),
         "l_dkv": ((nl, d, W), bf), "l_kvnorm": ((nl, R), f32),
         "l_uk": ((nl, 32, 64, R), bf), "l_uv": ((nl, 32, R, 128), bf),
         "l_o": ((nl, 4096, d), bf), "f_norm": ((nl, d), f32),
         "f_router": ((nl, d, 128), f32),
         "e_w1": ((nl, held, d, 4096), bf),
         "e_w2": ((nl, held, 2048, d), bf),
         "s_w1": ((nl, d, 4096), bf), "s_w2": ((nl, 2048, d), bf)}
    w = {n: _sds(*sd) for n, sd in w.items()}
    pool = _sds((nl * pages, PAGE, W), bf)
    rope = _sds((33537, 32), f32)
    if phase == "decode":
        def fn(w, x, rows, tables, lens, active, cos, sin):
            h, cache, _, counts = st.decode_raw(
                w, x, LatentKV(rows), None, tables, lens, active, cos, sin)
            return h, cache.rows, counts

        args = (w, _sds((24, d), bf), pool, _sds((24, pp), jnp.int32),
                _sds((24,), jnp.int32), _sds((24,), jnp.bool_), rope, rope)
    else:
        def fn(w, x, rows, tables, start, lens, cos, sin):
            h, cache, _, counts = st.prefill_chunk_raw(
                w, x, LatentKV(rows), None, tables, start, lens, cos, sin)
            return h, cache.rows, counts

        args = (w, _sds((1, 1024, d), bf), pool, _sds((1, pp), jnp.int32),
                _sds((1,), jnp.int32), _sds((1,), jnp.int32), rope, rope)
    return jax.jit(fn, donate_argnums=(2,)), args, pool


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_latent_programs_never_copy_the_pool(phase, one_chip, as_on_chip):
    """Both phases of a latent-attention stack at the cell's shapes and
    the published widths: the ONE-array pool passes through the phase's
    latent kernel (aliased) and no other instruction, nothing of its shape
    is copied, it stays donated and the program's temp stays far under
    it. A 320-wide row is refused by the chip's compiler (the minor
    dimension is stored in 128-lane tiles): the row is 384 wide."""
    jitted, args, pool = _latent_program(phase)
    compiled = jitted.lower(*_placed(args, one_chip)).compile()
    text = compiled.as_text()
    assert not _pool_copies(text, pool)
    names = _kernel_names(text)
    if phase == "decode":
        assert names == {"pt_mla_paged_decode", "pt_moe_stream_experts",
                         "pt_stream_linear_bf16"}
    else:
        assert names == {"pt_mla_paged_prefill", "pt_grouped_gemm_fwd"}
    size = 2 * math.prod(pool.shape)                # bf16 bytes
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < size // 4, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == size          # the pool, donated


def _avals(jaxpr):
    """(shape, dtype) of every value a jaxpr computes, sub-jaxprs
    (loops, calls) included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(v.aval.shape), v.aval.dtype
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


@pytest.mark.parametrize("stack", ["uniform", "pattern"])
def test_both_stacks_launch_the_same_decode_attention(stack, monkeypatch):
    """Both stacks reach the decode attention through the one call
    (``plan_decode_attention`` / ``decode_attend``): dry-traced as on
    the chip at their cells' shapes each launches
    ``pt_paged_attention_decode_inplace`` and none of its siblings, off
    the chip none of them; and neither way is a mask over the layer's
    whole region built (``build_pool_ownership``'s ``[pages * page]``
    owner list: the XLA gather never read it, the in-place kernel walks
    the tables). No topology, nothing compiles: a trace."""
    from paddle_tpu.analysis.audit import record_pallas_calls
    from paddle_tpu.analysis.sites import _force_tpu_routing
    from paddle_tpu.device import chip

    build = (_uniform_decode_program if stack == "uniform"
             else _hybrid_decode_program)
    region = build()[2].shape[0] // (L if stack == "uniform" else 1) * PAGE

    def trace():
        jitted, args, _ = build()       # a fresh function: no cached trace
        with record_pallas_calls() as records:
            closed = jax.make_jaxpr(jitted)(*args)
        attn = [r.name for r in records
                if r.name.startswith("pt_paged_attention")]
        masks = [a for a in _avals(closed.jaxpr)
                 if a == ((region,), jnp.int32)]
        return attn, masks

    with _force_tpu_routing():
        attn, masks = trace()
    assert set(attn) == {"pt_paged_attention_decode_inplace"} and not masks
    monkeypatch.setattr(chip, "on_tpu", lambda: False)
    attn, masks = trace()
    assert not attn and not masks
