"""Attention functionals: SDPA + flash attention.

TPU-native equivalent of the reference's attention surface (reference:
python/paddle/nn/functional/flash_attention.py:146 ``flash_attention``,
``scaled_dot_product_attention``; CUDA FA2 via phi/backends/dynload/flashattn.h
and the memory-efficient cutlass kernel). Here the hot path is the Pallas
TPU flash-attention kernel (tiled online-softmax over VMEM blocks feeding
the MXU); off-TPU we fall back to XLA's fused ``jax.nn.dot_product_attention``
so the same API runs everywhere (the fake-device test precedent, SURVEY §4).

Layout: paddle convention [batch, seqlen, num_heads, head_dim].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .paged_attention import _enable_x64

from ...core.generator import next_rng_key
from ...device import chip as _chip
from ...ops.dispatch import eager_apply, as_tensor_args

__all__ = [
    "scaled_dot_product_attention", "flash_attention",
    "flash_attn_unpadded", "sdp_kernel",
]


def _fa_mod():
    from jax.experimental.pallas.ops.tpu import flash_attention as m

    return m


_FA_BLOCKS = None  # optional (block_q, block_k) override


def set_flash_block_sizes(block_q=None, block_k=None):
    """Tune the Pallas flash-attention tile sizes (the reference's
    per-arch FA2 launch-config knob). None restores the kernel default
    (128/128); larger tiles amortize VMEM loads for long seqs."""
    global _FA_BLOCKS
    if block_q is None and block_k is not None:
        raise ValueError(
            "set_flash_block_sizes: block_q is required when block_k "
            "is given (block_q=None resets to defaults)")
    _FA_BLOCKS = None if block_q is None else (int(block_q),
                                               int(block_k or block_q))


def _fa_blocks(m, b, h, sq, sk, d):
    if _FA_BLOCKS is None:
        # measured on v5e (GPT-1.3B, d128, s1024): vs the 128 default,
        # 256x256 tiles lift train MFU 0.444 -> 0.504 and 256x512
        # -> 0.527; 512-wide q tiles exhaust VMEM at d=128. At d<=64
        # tile bytes halve, and 512x512 wins again (bert-base s512:
        # MFU 0.330 -> 0.361, tools/bert_profile fa512, r5). Gate on
        # shapes where the bigger tile is safe and divides the seq.
        if d <= 64 and sq % 512 == 0 and sk % 512 == 0:
            bq = bk = 512
        elif d <= 128 and sq % 256 == 0 and sk % 256 == 0:
            bq = 256
            bk = 512 if sk % 512 == 0 else 256
        else:
            return m.BlockSizes.get_default(b, h, sq, sk, d)
    else:
        bq = min(_FA_BLOCKS[0], sq)
        bk = min(_FA_BLOCKS[1], sk)
        # the kernel requires tiles to divide the sequence; snap down
        # rather than fail trace-time with an opaque Pallas error
        while bq > 128 and sq % bq:
            bq //= 2
        while bk > 128 and sk % bk:
            bk //= 2
        if sq % bq or sk % bk:
            return m.BlockSizes.get_default(b, h, sq, sk, d)
    return m.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk,
        block_q_dq=bq)


# Own custom_vjp shell around the pallas kernel: both rules trace the
# kernel under enable_x64(False) — paddle_tpu turns x64 on globally (for
# int64 tensor parity) and the kernel's block index maps mix int32/int64
# under that flag. Wrapping only the primal call is not enough because
# custom-vjp fwd/bwd re-enter python during outer vjp tracing.
# JAX names its two backward kernels itself (``flash_mha_bwd_dq_*`` /
# ``flash_mha_bwd_dkv_*``: a ``named_scope`` around each call) and its
# forward not at all, so the forward's scope is opened here, INSIDE the
# rules: around the whole ``_flash_core`` call it would also wrap the
# backward kernels' names, which the benchmark's readers match.
_FWD_SCOPE = "pt_flash_mha_fwd"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_core(q, k, v, causal, scale):
    m = _fa_mod()
    with _enable_x64(False), jax.named_scope(_FWD_SCOPE), \
            jax.default_matmul_precision("default"):
        return m._flash_attention(
            q, k, v, None, None, False, causal, scale,
            _fa_blocks(m, q.shape[0], q.shape[1], q.shape[2], q.shape[2], q.shape[3]), False)


def _flash_core_fwd(q, k, v, causal, scale):
    m = _fa_mod()
    with _enable_x64(False), jax.named_scope(_FWD_SCOPE), \
            jax.default_matmul_precision("default"):
        out, res = m._flash_attention_fwd(
            q, k, v, None, None, False, causal, scale,
            _fa_blocks(m, q.shape[0], q.shape[1], q.shape[2], q.shape[2], q.shape[3]), False)
    return out, res


def _flash_core_bwd(causal, scale, res, do):
    m = _fa_mod()
    q = res[0]
    with _enable_x64(False), \
            jax.default_matmul_precision("default"):
        dq, dk, dv, _ds, _dseg = m._flash_attention_bwd(
            False, causal, scale, _fa_blocks(m, q.shape[0], q.shape[1], q.shape[2], q.shape[2], q.shape[3]), False, res, do)
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _pallas_flash(q, k, v, causal: bool, scale: float):
    """[b, s, h, d] in/out; pallas kernel wants [b, h, s, d]."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash_core(qt, kt, vt, causal, scale)
    return jnp.swapaxes(out, 1, 2)


def _xla_attention(q, k, v, bias, causal: bool, scale: float):
    return jax.nn.dot_product_attention(
        q, k, v, bias=bias, is_causal=causal, scale=scale)


def _attention_raw(q, k, v, *maybe_mask, causal=False, scale=None,
                   dropout_p=0.0, dropout_key=None):
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    bias = maybe_mask[0] if maybe_mask else None
    if bias is not None and bias.dtype == jnp.bool_:
        bias = jnp.where(bias, 0.0, jnp.finfo(q.dtype).min).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        # dropout on attention weights → fall back to explicit softmax path
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if bias is not None:
            logits = logits + (bias if bias.ndim == 4 else bias[:, None])
        if causal:
            s_q, s_k = logits.shape[-2], logits.shape[-1]
            mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
            logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        w = jax.nn.softmax(logits, axis=-1)
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, w.shape)
        w = w * keep.astype(w.dtype) / (1.0 - dropout_p)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)
    if _use_pallas(head_dim, q.shape[1], k.shape[1], bias is not None):
        _record_backend("pallas_flash")
        return _pallas_flash(q, k, v, causal, scale)
    _record_backend("xla")
    return _xla_attention(q, k, v, bias, causal, scale)


def _use_pallas(head_dim: int, seq_q: int, seq_k: int,
                has_bias: bool) -> bool:
    """Gate for the Pallas flash kernel — its real constraints: lane-dim
    alignment (head_dim % 8; 64/96/128 all verified on v5e) and seq
    divisibility by the 128-wide q/k blocks. (Round-1 gate wrongly
    required head_dim % 128, so head_dim 64/96 models never hit flash.)"""
    return (_chip.on_tpu() and not has_bias and head_dim % 8 == 0
            and seq_q % 128 == 0 and seq_k % 128 == 0)


_LAST_BACKEND = [None]


def _record_backend(name: str):
    _LAST_BACKEND[0] = name


def last_attention_backend():
    """Which backend the most recent attention dispatch picked
    ('pallas_flash' | 'xla') — observability for tests and the bench."""
    return _LAST_BACKEND[0]


@functools.lru_cache(maxsize=64)
def _sdp_jitted(causal: bool, dropout_p: float, has_mask: bool,
                has_key: bool):
    """One cached jitted attention program per static config: a FRESH
    closure per eager call would give the pallas_call primitive a new
    cache key every time — a recompile per eager flash-attention call
    (~660ms each in an earlier round's chip run)."""

    def fn(*arrs):
        dkey = arrs[-1] if has_key else None
        arrs = arrs[:-1] if has_key else arrs
        return _attention_raw(*arrs, causal=causal, dropout_p=dropout_p,
                              dropout_key=dkey)

    return jax.jit(fn)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    tensors = as_tensor_args(*((query, key, value, attn_mask)
                               if attn_mask is not None
                               else (query, key, value)))
    p = dropout_p if training else 0.0
    dkey = next_rng_key() if p > 0.0 else None
    raw = _sdp_jitted(bool(is_causal), float(p),
                      attn_mask is not None, dkey is not None)
    if dkey is not None:
        # the key rides as a traced ARG so fresh masks don't recompile
        orig = raw

        def raw(*arrs):
            return orig(*arrs, dkey)

    return eager_apply("scaled_dot_product_attention", raw, tensors)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """Paddle flash_attention parity (flash_attention.py:146): returns
    (out, softmax) — softmax is None unless return_softmax (debug-only in the
    reference; unsupported here as flash never materialises it)."""
    if return_softmax:
        raise NotImplementedError(
            "return_softmax materialises the attention matrix — unsupported "
            "by the flash path (reference only supports it in debug mode)")
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return out, None


def _unpadded_dense_raw(q, k, v, cu_q, cu_k, *, scale, causal):
    """LEGACY dense varlen path: reconstructs the full segment mask and
    materializes [h, total_q, total_k] logits — O(T²) memory. Kept as
    the numerical reference for the block-skipping kernel (tests,
    bench) and behind FLAGS_attn_varlen_backend=dense; unusable at
    real packed batch sizes (a 16k-token pack needs a >=1 GiB
    intermediate per head)."""
    total_q, h, d = q.shape
    total_k = k.shape[0]
    pos_q = jnp.arange(total_q)
    pos_k = jnp.arange(total_k)
    seg_q = jnp.searchsorted(cu_q[1:], pos_q, side="right")
    seg_k = jnp.searchsorted(cu_k[1:], pos_k, side="right")
    mask = seg_q[:, None] == seg_k[None, :]
    logits = jnp.einsum("qhd,khd->hqk", q, k) * scale
    if causal:
        off_q = pos_q - cu_q[jnp.minimum(seg_q, cu_q.shape[0] - 1)]
        off_k = pos_k - cu_k[jnp.minimum(seg_k, cu_k.shape[0] - 1)]
        mask = mask & (off_q[:, None] >= off_k[None, :])
    logits = jnp.where(mask[None], logits, jnp.finfo(logits.dtype).min)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,khd->qhd", w, v)


def _unpadded_varlen_raw(q, k, v, cu_q, cu_k, *, scale, causal):
    """Varlen flash attention over a packed batch: the segment-aware
    block-skipping kernel family (nn/functional/flash_varlen.py).
    MODULE-LEVEL by design: a stable function identity plus cu_seqlens
    as TRACED operands is what lets the dispatch caches admit it — the
    old per-call closure baked cu_q/cu_k in as constants, so every
    distinct packing was a fresh function object that re-traced
    (the recompile storm; pinned by tests/test_flash_varlen.py)."""
    from ...core.flags import flag
    from .flash_varlen import flash_varlen_packed

    backend = flag("attn_varlen_backend")
    if backend == "dense":
        return _unpadded_dense_raw(q, k, v, cu_q, cu_k, scale=scale,
                                   causal=causal)
    return flash_varlen_packed(q, k, v, cu_q, cu_k, scale=scale,
                               causal=causal, backend=backend)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen flash attention (reference flash_attention.py:302).

    TPU-native treatment: the packed batch stays packed — a
    segment-aware block-skipping flash kernel visits only the tiles
    where seg_q ∩ seg_k ≠ ∅ (block map from cu_seqlens), with online
    softmax — memory O(T·d), work ∝ the sum of per-segment areas.
    cu_seqlens ride as traced operands so one compiled program serves
    every packing of the same shape.
    """
    tensors = as_tensor_args(query, key, value, cu_seqlens_q,
                             cu_seqlens_k)
    out = eager_apply(
        "flash_attn_unpadded", _unpadded_varlen_raw, tensors,
        static_kwargs={"scale": float(scale), "causal": bool(causal)})
    return out, None


class sdp_kernel:
    """Context selecting attention backends (paddle/torch-compat no-op:
    backend choice is automatic — pallas on TPU, XLA elsewhere)."""

    def __init__(self, enable_flash=True, enable_math=True,
                 enable_mem_efficient=True):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
