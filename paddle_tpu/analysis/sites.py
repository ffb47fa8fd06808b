"""The repo's Pallas kernel inventory + the interpret-free dry-trace
driver that exercises every site through the audit shim.

Each :class:`KernelSite` names one ``pallas_call`` site, builds a
representative serving-shaped launch (as ShapeDtypeStructs — no real
arrays), and dry-traces it with ``jax.eval_shape`` under
``record_pallas_calls``. Abstract evaluation captures the full launch
spec without lowering to Mosaic, so this runs on CPU in milliseconds
per kernel, with the exact BlockSpecs/grid/scratch the chip would get.

``expected_vmem`` is an INDEPENDENT hand-written block list per site
(kept in sync with the kernel by eye, not by code): the tier-1
regression test asserts the analyzer's footprint over the shim-recorded
spec equals this closed form, so either the analyzer drifting or a
kernel's geometry changing silently fails CI until both are
re-reconciled.

The TPU-only routing gate (``device.chip.on_tpu``) is patched for the
duration of a dry-trace so the Pallas path is taken off-chip; x64 is
disabled around each trace to mirror the on-TPU tracing regime (the
stock flash kernel's index maps require it).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, List, Optional, Tuple

from .audit import PallasCallRecord, record_pallas_calls
from .geometry import tile_padded_bytes as _B

__all__ = ["KernelSite", "KERNEL_SITES", "KERNEL_PREFIX", "trace_site",
           "trace_all_sites"]


#: prefix of every kernel name the device trace shows (``name=`` of the
#: ``pallas_call`` and the ``jax.named_scope`` around it)
KERNEL_PREFIX = "pt_"


@dataclasses.dataclass
class KernelSite:
    name: str                 # "stream_linear.bf16", ...
    module: str               # module that owns the pallas_call
    build: Callable           # () -> (fn, args) for jax.eval_shape
    expected_vmem: Optional[Callable[[], int]]  # closed-form footprint
    n_calls: int = 1          # pallas_calls the dry-trace must record
    # the trace names of the launches the dry-trace records, in order.
    # One name per ``pallas_call`` in the code, ``pt_`` + the site's
    # name with dots as underscores; spelled out only where a site's
    # trace crosses several launches (a backward pass)
    kernels: Tuple[str, ...] = ()

    def __post_init__(self):
        if not self.kernels:
            self.kernels = (KERNEL_PREFIX
                            + self.name.replace(".", "_"),) * self.n_calls


@contextlib.contextmanager
def _force_tpu_routing():
    """Patch the one platform probe (``device.chip.on_tpu`` — every
    kernel module calls it through that module) so dry-traces take the
    Pallas path off-chip, and trace under x64=False (the regime the
    kernels are written for — see paged_attention._enable_x64)."""
    import jax

    from ..device import chip

    orig = chip.on_tpu
    x64 = bool(jax.config.jax_enable_x64)
    try:
        chip.on_tpu = lambda: True
        jax.config.update("jax_enable_x64", False)
        yield
    finally:
        chip.on_tpu = orig
        jax.config.update("jax_enable_x64", x64)


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


# --------------------------------------------------------------- builders
# Representative serving shapes: GPT-1.3B-ish projections (d=2048,
# dff=8192), a b=8 GQA-free decode batch over a 16-token-page pool with
# 1024-token stream chunks, and a bert-ish s=512 flash block.

def _build_stream_linear():
    import jax.numpy as jnp

    import paddle_tpu.nn.functional.stream_linear as sl

    def fn(x, w):
        return sl.stream_linear(x, w)

    return fn, (_sds((32, 2048), jnp.bfloat16),
                _sds((2048, 8192), jnp.bfloat16))


def _expected_stream_linear():
    # bn = 2048 (8 MiB bf16 target / K=2048 rows), nb = 4, Mp = 32
    return (_B((32, 2048), "bfloat16")           # x, resident
            + 2 * _B((1, 2048, 2048), "bfloat16")  # w stream, dbl-buffered
            + 2 * _B((32, 2048), "bfloat16"))      # out blocks, streamed


def _build_stream_linear_a8w8():
    import jax.numpy as jnp

    import paddle_tpu.nn.functional.stream_linear as sl

    def fn(x, w, s):
        return sl.stream_linear(x, w, scale=s, act_quant=True)

    return fn, (_sds((32, 2048), jnp.bfloat16),
                _sds((2048, 8192), jnp.int8),
                _sds((8192,), jnp.float32))


def _expected_stream_linear_a8w8():
    # bn = 2048 (4 MiB int8 target), nb = 4, Mp = 32 (int8 sublane tile)
    return (_B((32, 2048), "int8")                 # x_q, resident
            + _B((32, 1), "float32")               # per-token scales
            + 2 * _B((1, 2048, 2048), "int8")      # w stream
            + 2 * _B((1, 1, 2048), "float32")      # dequant scales
            + 2 * _B((32, 2048), "bfloat16"))      # out blocks


def _build_stream_layer_tail():
    import jax.numpy as jnp

    import paddle_tpu.nn.functional.stream_linear as sl

    # the real depth: a one-row block of an [L, d] operand tiles at
    # L = 1 and small L only by accident of padding
    L, d, dff, nq = 24, 2048, 8192, 3 * 2048
    bf = jnp.bfloat16

    def fn(att, h, wo, w1, w2, bo, b1, b2, ln2s, ln2b, wq, bq, ln1s,
           ln1b):
        return sl.stream_layer_tail(
            att, h, wo, w1, w2, layer=0, bo=bo, b1=b1, b2=b2,
            ln2_scale=ln2s, ln2_bias=ln2b, epsilon=1e-5,
            activation="gelu",
            next_qkv={"w": wq, "b": bq, "ln_s": ln1s, "ln_b": ln1b,
                      "layer": 1})

    args = (_sds((32, d), bf), _sds((32, d), bf),
            _sds((L, d, d), bf), _sds((L, d, dff), bf),
            _sds((L, dff, d), bf),
            _sds((L, d), bf), _sds((L, dff), bf), _sds((L, d), bf),
            _sds((L, d), bf), _sds((L, d), bf),
            _sds((L, d, nq), bf), _sds((L, nq), bf),
            _sds((L, d), bf), _sds((L, d), bf))
    return fn, args


def _expected_stream_layer_tail():
    # bn_o = bn_f = bn_q = 512 (2 MiB grouped per-stream target);
    # grid = nb_o + nb_f + nb_q = 4 + 16 + 12
    d, dff, nq = 2048, 8192, 3 * 2048
    bf = "bfloat16"
    return (
        _B((32, d), bf) + _B((32, d), bf)          # att, h: resident
        + 2 * _B((1, d, 512), bf)                  # Wo stream
        + _B((1, 1, d), bf)                        # bo (whole row)
        + 2 * _B((1, d, 512), bf)                  # W1 stream
        + 2 * _B((1, 1, 512), bf)                  # b1 blocks
        + 2 * _B((1, 512, d), bf)                  # W2 stream
        + _B((1, 1, d), bf)                        # b2 (whole row)
        + _B((1, 1, d), bf) * 2                    # ln2 scale+bias
        + 2 * _B((1, d, 512), bf)                  # Wq prefetch stream
        + 2 * _B((1, 1, 512), bf)                  # bq blocks
        + _B((1, 1, d), bf) * 2                    # ln1 scale+bias
        + _B((32, d), bf)                          # out_h
        + 2 * _B((32, 512), bf)                    # out_q blocks
        + _B((32, d), "float32") * 2               # s_h2 + s_acc scratch
        + _B((32, d), bf))                         # s_hn scratch


_POOL = dict(b=8, n_kv=8, d=128, ps=16)


def _paged_args(P, pp, dtype_name="bfloat16"):
    import jax.numpy as jnp

    b, n_kv, d, ps = (_POOL[k] for k in ("b", "n_kv", "d", "ps"))
    dt = getattr(jnp, dtype_name)
    return (_sds((b, n_kv, d), dt),
            _sds((P, n_kv, ps, d), dt),
            _sds((P, n_kv, ps, d), dt),
            _sds((b,), jnp.int32),
            _sds((b, pp), jnp.int32))


def _build_decode_inplace():
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.paged_attention import (
        paged_decode_attention_inplace)

    q, kc, vc, lens, tables = _paged_args(P=128, pp=8)
    nk = _sds((_POOL["b"], _POOL["n_kv"], _POOL["d"]), jnp.bfloat16)

    def fn(q, nk, nv, kc, vc, lens, tables):
        return paged_decode_attention_inplace(
            q, nk, nv, kc, vc, lens, tables, pool_base=0)

    return fn, (q, nk, nk, kc, vc, lens, tables)


def _expected_decode_inplace():
    # the walk: b * pp = 64 entries = one chunk of 64 pages (1024 tokens)
    b, n_kv, d, ps = (_POOL[k] for k in ("b", "n_kv", "d", "ps"))
    bf = "bfloat16"
    return (_B((n_kv, b, d), bf)                   # qt
            + _B((1, 1024), "int32")               # the walk's token owners
            + 2 * _B((n_kv, b, d), bf)             # nk_t + nv_t operands
            + 2 * _B((b, n_kv, 1, d), "float32")   # nk_w + nv_w page patch
            + _B((b, 1, ps, 1), "float32")         # slot selector
            + _B((n_kv, b, d), "float32")          # out
            + 2 * 2 * _B((64, n_kv, ps, d), bf)    # kb + vb chunk scratch
            + 2 * _B((b, n_kv, ps, d), bf)         # pgk + pgv page RMW
            + 2 * _B((n_kv, b), "float32")         # m + l
            + _B((n_kv, b, d), "float32"))         # acc


def _build_decode_inplace_q():
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.paged_attention import (
        paged_decode_attention_inplace_q)

    b, n_kv, d, ps = (_POOL[k] for k in ("b", "n_kv", "d", "ps"))
    P = 128
    q = _sds((b, n_kv, d), jnp.bfloat16)
    nk = _sds((b, n_kv, d), jnp.bfloat16)
    pool = _sds((P, n_kv, ps, d), jnp.int8)
    plane = _sds((n_kv, P * ps), jnp.float32)
    lens = _sds((b,), jnp.int32)
    tables = _sds((b, 8), jnp.int32)

    def fn(q, nk, nv, kq, ks, vq, vs, lens, tables):
        return paged_decode_attention_inplace_q(
            q, nk, nv, kq, ks, vq, vs, lens, tables, pool_base=0,
            pool_pages=P)

    return fn, (q, nk, nk, pool, plane, pool, plane, lens, tables)


def _expected_decode_inplace_q():
    # rows_pp = n_kv*ps = 128 int8 rows/page; C = 1024, nchunks = 2
    b, n_kv, d, ps = (_POOL[k] for k in ("b", "n_kv", "d", "ps"))
    rp = n_kv * ps
    return (_B((n_kv, b, d), "int8")               # qq
            + _B((n_kv, b), "float32")             # qs
            + 2 * _B((1, b, 1024), "int32")        # ownership mask chunk
            + 2 * _B((n_kv, b, d), "bfloat16")     # nk_t + nv_t (exact)
            + 2 * _B((b, rp, d), "int8")           # quantized page patches
            + _B((b, rp, 1), "float32")            # flat slot selector
            + 2 * _B((1, 1024), "float32")         # plane patch column sel
            + 2 * 2 * _B((n_kv, 1024), "float32")  # kval+vval patch values
            + 2 * 2 * _B((n_kv, 1024), "float32")  # ks+vs plane blocks in
            + _B((n_kv, b, d), "float32")          # out
            + 2 * 2 * _B((n_kv, 1024), "float32")  # kso+vso plane blocks out
            + 2 * _B((2, 64, rp, d), "int8")       # kb + vb chunk scratch
            + 2 * _B((b, rp, d), "int8")           # pgq + pgv page RMW
            + 2 * _B((n_kv, b), "float32")         # m + l
            + _B((n_kv, b, d), "float32"))         # acc


def _build_flash():
    import jax.numpy as jnp

    import paddle_tpu.nn.functional.attention as att

    q = _sds((2, 512, 8, 128), jnp.float32)

    def fn(q, k, v):
        return att._attention_raw(q, k, v, causal=True)

    return fn, (q, q, q)


# varlen flash (ISSUE 13): a serving-shaped packed batch — 8 heads,
# d128, 1024 tokens in 4 segments, 128x128 tiles, bf16
_VARLEN = dict(h=8, T=1024, d=128, nseg=4, bq=128, bk=128)


def _varlen_args():
    import jax.numpy as jnp

    h, T, d, nseg = (_VARLEN[k] for k in ("h", "T", "d", "nseg"))
    return (_sds((T, h, d), jnp.bfloat16),
            _sds((T, h, d), jnp.bfloat16),
            _sds((T, h, d), jnp.bfloat16),
            _sds((nseg + 1,), jnp.int32),
            _sds((nseg + 1,), jnp.int32))


def _build_flash_varlen_fwd():
    from paddle_tpu.nn.functional.flash_varlen import flash_varlen_packed

    def fn(q, k, v, cu_q, cu_k):
        return flash_varlen_packed(q, k, v, cu_q, cu_k, causal=True,
                                   backend="pallas")

    return fn, _varlen_args()


def _expected_flash_varlen_fwd():
    h, d, bq, bk = (_VARLEN[k] for k in ("h", "d", "bq", "bk"))
    return (2 * _B((2, bq), "int32")               # qmeta tile stream
            + 2 * _B((h, bq, d), "bfloat16")       # q tile stream
            + 2 * _B((h, bq, d), "float32")        # out tile stream
            + 2 * _B((h, bq), "float32")           # lse tile stream
            + _B((2, h, bk, d), "bfloat16") * 2    # k + v DMA scratch
            + _B((2, 2, bk), "int32"))             # kmeta DMA scratch


def _build_flash_varlen_bwd():
    import jax

    from paddle_tpu.nn.functional.flash_varlen import flash_varlen_packed

    def fn(q, k, v, cu_q, cu_k):
        def loss(q, k, v):
            out = flash_varlen_packed(q, k, v, cu_q, cu_k, causal=True,
                                      backend="pallas")
            return jax.numpy.sum(out.astype(jax.numpy.float32))

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return fn, _varlen_args()


def _expected_flash_varlen_bwd():
    h, d, bq, bk = (_VARLEN[k] for k in ("h", "d", "bq", "bk"))
    bf = "bfloat16"
    fwd = _expected_flash_varlen_fwd()
    dq = (2 * _B((2, bq), "int32")                 # qmeta tile stream
          + 2 * _B((h, bq, d), bf)                 # q tile stream
          + 2 * _B((h, bq, d), bf)                 # dout tile stream
          + 2 * _B((2, h, bq), "float32")          # lse+delta stream
          + 2 * _B((h, bq, d), "float32")          # dq tile stream
          + _B((2, h, bk, d), bf) * 2              # k + v DMA scratch
          + _B((2, 2, bk), "int32"))               # kmeta DMA scratch
    dkv = (2 * _B((2, bk), "int32")                # kmeta tile stream
           + 2 * _B((h, bk, d), bf) * 2            # k + v tile streams
           + 2 * _B((h, bk, d), "float32") * 2     # dk + dv tile streams
           + _B((2, h, bq, d), bf) * 2             # q + dout DMA scratch
           + _B((2, 2, h, bq), "float32")          # lse+delta DMA scratch
           + _B((2, 2, bq), "int32"))              # qmeta DMA scratch
    return fwd + dq + dkv


def _build_flash_varlen_paged():
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.flash_varlen import (
        paged_prefill_attention)

    b, n_kv, d, ps = (_POOL[k] for k in ("b", "n_kv", "d", "ps"))
    c, pp, P = 64, 16, 256

    def fn(q, kc, vc, tables, start):
        return paged_prefill_attention(q, kc, vc, tables, start,
                                       n_kv=n_kv, backend="pallas")

    return fn, (_sds((b, c, n_kv, d), jnp.bfloat16),
                _sds((P, n_kv, ps, d), jnp.bfloat16),
                _sds((P, n_kv, ps, d), jnp.bfloat16),
                _sds((b, pp), jnp.int32),
                _sds((b,), jnp.int32))


def _expected_flash_varlen_paged():
    # bk = 8 pages x ps16 = 128 tokens; q/out blocks stream per row
    n_kv, d, ps = (_POOL[k] for k in ("n_kv", "d", "ps"))
    c, npp = 64, 8
    return (2 * _B((1, n_kv, c, d), "bfloat16")    # q row stream
            + 2 * _B((1, n_kv, c, d), "float32")   # out row stream
            + _B((2, npp, n_kv, ps, d), "bfloat16") * 2)  # k+v page DMA


def _build_paged_kv_write(c=64):
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.paged_attention import (
        write_prefill_kv_inplace)

    b, n_kv, d, ps = (_POOL[k] for k in ("b", "n_kv", "d", "ps"))
    pp, P = 16, 256

    def fn(kc, vc, k, v, tables, start, lens):
        return write_prefill_kv_inplace(kc, vc, k, v, tables, start,
                                        lens)

    return fn, (_sds((P, n_kv, ps, d), jnp.bfloat16),
                _sds((P, n_kv, ps, d), jnp.bfloat16),
                _sds((b, c, n_kv, d), jnp.bfloat16),
                _sds((b, c, n_kv, d), jnp.bfloat16),
                _sds((b, pp), jnp.int32),
                _sds((b,), jnp.int32),
                _sds((b,), jnp.int32))


def _expected_paged_kv_write():
    # a 64-row chunk at any offset touches 64/16 + 1 = 5 pages a row;
    # the pool itself stays in HBM (aliased, memory_space=ANY)
    n_kv, d, ps = (_POOL[k] for k in ("n_kv", "d", "ps"))
    npg = 5
    return (2 * _B((npg, n_kv, ps, d), "bfloat16") * 2   # shifted k + v
            + 2 * _B((npg, 1, ps, 1), "float32")         # row mask
            + _B((npg, n_kv, ps, d), "bfloat16") * 2)    # k + v page RMW


# ragged grouped-GEMM MoE kernel (ISSUE 15): a serving-shaped FFN1
# bank — 8 experts, d=2048 -> dff=8192, 1024 expert-sorted rows, bf16
# weights. bn = 2048 (8 MiB bf16 stream target / K=2048), bm = 128;
# nwu = 1024/128 + 2*8 + 1 = 25 work units.
_GROUPED = dict(T=1024, K=2048, N=8192, E=8, bm=128, bn=2048)


def _grouped_args():
    import jax.numpy as jnp

    T, K, N, E = (_GROUPED[k] for k in ("T", "K", "N", "E"))
    return (_sds((T, K), jnp.bfloat16),
            _sds((E, K, N), jnp.bfloat16),
            _sds((E, N), jnp.float32),
            _sds((E + 1,), jnp.int32))


def _build_grouped_gemm_fwd():
    from paddle_tpu.nn.functional.grouped_gemm import grouped_gemm

    def fn(x, w, b, offsets):
        return grouped_gemm(x, w, offsets, bias=b, activation="gelu",
                            backend="pallas")

    return fn, _grouped_args()


def _expected_grouped_gemm_fwd():
    K, N, bm, bn = (_GROUPED[k] for k in ("K", "N", "bm", "bn"))
    return (_B((bm, K), "bfloat16")            # x row tile (dynamic map)
            + 2 * _B((1, K, bn), "bfloat16")   # expert weight stream
            + 2 * _B((1, 1, bn), "float32")    # bias blocks
            + 2 * _B((bm, bn), "float32"))     # out tile stream


def _build_grouped_gemm_bwd():
    import jax

    from paddle_tpu.nn.functional.grouped_gemm import grouped_gemm

    def fn(x, w, b, offsets):
        def loss(x, w, b):
            y = grouped_gemm(x, w, offsets, bias=b, activation="gelu",
                             backend="pallas")
            return jax.numpy.sum(y.astype(jax.numpy.float32))

        return jax.grad(loss, argnums=(0, 1, 2))(x, w, b)

    return fn, _grouped_args()


def _expected_grouped_gemm_bwd():
    # grad trace records fwd + pre-activation recompute (same geometry
    # as fwd), the dx walk against the transposed bank (bn = 512: the
    # 8 MiB bf16 target over K = dff = 8192), and the dw segment
    # accumulation
    K, N, bm, bn = (_GROUPED[k] for k in ("K", "N", "bm", "bn"))
    bn_dx = 512
    fwd = _expected_grouped_gemm_fwd()
    dx = (_B((bm, N), "float32")               # dz row tile (dynamic)
          + 2 * _B((1, N, bn_dx), "bfloat16")  # transposed weight stream
          + 2 * _B((1, 1, bn_dx), "float32")   # zero-bias blocks
          + 2 * _B((bm, bn_dx), "float32"))    # dx tile stream
    dw = (_B((bm, K), "bfloat16")              # x row tile (dynamic)
          + 2 * _B((bm, bn), "float32")        # dz tile stream
          + 2 * _B((1, K, bn), "float32"))     # dw expert-block stream
    return 2 * fwd + dx + dw


# batched multi-LoRA delta kernel (ISSUE 18): a serving-shaped ffn1
# delta bank — 8 adapter slots, rank 8 padded to the bf16 sublane tile
# (R = 16), d=2048 -> dff=8192, 1024 adapter-sorted rows. The bank
# dtype drives the same bm=128 / bn=2048 stream geometry as the MoE
# bank above; each work unit chains TWO dots (down to the rank, back
# up) inside one launch.
_LORA = dict(T=1024, K=2048, N=8192, S=8, R=16, bm=128, bn=2048)


def _build_lora_delta():
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.lora import lora_delta

    T, K, N, S, R = (_LORA[k] for k in ("T", "K", "N", "S", "R"))

    def fn(x, a, b, offsets):
        return lora_delta(x, a, b, offsets, backend="pallas")

    return fn, (_sds((T, K), jnp.bfloat16),
                _sds((S, K, R), jnp.bfloat16),
                _sds((S, R, N), jnp.bfloat16),
                _sds((S + 1,), jnp.int32))


def _expected_lora_delta():
    # x and the A tile index only on the work unit (the slow grid
    # axis), so neither double-buffers against the bn walk; the A
    # tile's R=16 lane axis pads to the full 128-lane tile
    K, R, bm, bn = (_LORA[k] for k in ("K", "R", "bm", "bn"))
    return (_B((bm, K), "bfloat16")            # x row tile (dynamic map)
            + _B((1, K, R), "bfloat16")        # A down-proj tile
            + 2 * _B((1, R, bn), "bfloat16")   # B up-proj stream
            + 2 * _B((bm, bn), "float32"))     # delta tile stream


# state-space and routed-expert serving kernels (ISSUE 31) at
# granite-4.0-h-small's published widths: 128 Mamba heads of 64, state
# 128, one SSD chunk of 256 rows; 64 decode slots over 9 Mamba layers;
# 36 held experts of width 768 over hidden 4096, 10 layers.
_SSM = dict(H=128, P=64, N=128, T=256, L=9, S=64)
_MOE = dict(M=64, d=4096, f=768, E=36, L=10, tf=256)


def _build_ssd_chunk_scan():
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.ssm import ssd_chunk_scan

    H, P, N, T = (_SSM[k] for k in ("H", "P", "N", "T"))

    def fn(x, dt, A, B, C, D, s0):
        return ssd_chunk_scan(x, dt, A, B, C, D, s0, chunk_size=T)

    return fn, (_sds((T, H, P), jnp.bfloat16), _sds((T, H), jnp.float32),
                _sds((H,), jnp.float32), _sds((T, N), jnp.bfloat16),
                _sds((T, N), jnp.bfloat16), _sds((H,), jnp.float32),
                _sds((N, H * P), jnp.float32))


def _expected_ssd_chunk_scan():
    # grid (heads, chunks) = (128, 1): what is indexed by the head
    # streams, what is indexed by the chunk alone stays put
    H, P, N, Q = (_SSM[k] for k in ("H", "P", "N", "T"))
    return (2 * _B((1, Q, P), "bfloat16")       # x, one head
            + _B((H, Q), "float32")              # decay sums, row form
            + 2 * _B((Q, H), "float32")          # ... column form, dt
            + _B((N, Q), "bfloat16")             # B^T
            + _B((Q, N), "bfloat16")             # C
            + 2 * _B((1, N, P), "float32")       # the head's initial state
            + 2 * _B((1, Q, P), "float32")       # y
            + 2 * _B((1, N, P), "float32")       # final state
            + _B((N, P), "float32"))             # carried state (scratch)


def _build_ssm_decode_update():
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.ssm import ssm_decode_update

    H, P, N, L, S = (_SSM[k] for k in ("H", "P", "N", "L", "S"))

    def fn(state, decay, dtx, B, C):
        return ssm_decode_update(state, 4, decay, dtx, B, C)

    return fn, (_sds((L, S, N, H * P), jnp.float32),
                _sds((S, H * P), jnp.float32),
                _sds((S, H * P), jnp.float32),
                _sds((S, N), jnp.bfloat16), _sds((S, N), jnp.bfloat16))


def _expected_ssm_decode_update():
    # [N, 4096] float32 blocks of one slot's state, in and (aliased) out
    N, w = _SSM["N"], 4096
    return (2 * 2 * _B((1, 1, w), "float32")     # decay and dt*u rows
            + 2 * _B((1, N, 2), "float32")       # B | C columns
            + 2 * _B((1, 1, N, w), "float32")    # state block in
            + 2 * _B((1, 1, N, w), "float32")    # state block out
            + 2 * _B((1, 1, w), "float32"))      # y row


def _build_moe_stream_experts():
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.moe_gated import moe_gated_stream

    M, d, f, E, L = (_MOE[k] for k in ("M", "d", "f", "E", "L"))

    def fn(x, gates, idx, w1, w2):
        return moe_gated_stream(x, gates, idx, w1, w2, 3, (0, E))

    return fn, (_sds((M, d), jnp.bfloat16), _sds((M, 10), jnp.float32),
                _sds((M, 10), jnp.int32),
                _sds((L, E, d, 2 * f), jnp.bfloat16),
                _sds((L, E, f, d), jnp.bfloat16))


def _expected_moe_stream_experts():
    M, d, tf = (_MOE[k] for k in ("M", "d", "tf"))
    return (_B((M, d), "bfloat16")               # rows (resident)
            + 2 * _B((1, M, 1), "float32")       # the expert's gate column
            + 2 * 2 * _B((1, 1, d, tf), "bfloat16")  # a and b column tiles
            + 2 * _B((1, 1, tf, d), "bfloat16")  # W2 row tile
            + _B((M, d), "float32"))             # the accumulated output


# latent attention over the paged latent pool (ISSUE 37) at the published
# widths of the configuration that drives it: 32 heads, a 384-lane row
# (256 latent + 64 rope + 64 pad), page 16; a 256-token chunk (two tiles of
# 128 tokens x 32 heads) and 8 decode rows over tables of 64 pages
_MLA = dict(H=32, W=384, R=256, ps=16, P=512, pp=64, c=256, S=8)


def _build_mla_paged_prefill():
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.mla_attention import mla_prefill_attend

    H, W, R, ps, P, pp, c = (_MLA[k] for k in
                             ("H", "W", "R", "ps", "P", "pp", "c"))

    def fn(q, rows, pool, tables, start, lens):
        return mla_prefill_attend(q, rows, pool, tables, start, lens,
                                  v_width=R)

    return fn, (_sds((c, H, W), jnp.bfloat16), _sds((c, W), jnp.bfloat16),
                _sds((P, ps, W), jnp.bfloat16), _sds((1, pp), jnp.int32),
                _sds((1,), jnp.int32), _sds((1,), jnp.int32))


def _expected_mla_paged_prefill():
    # a tile is 128 query tokens x 32 heads = 4096 rows; a 256-row chunk
    # at any offset touches 256/16 + 1 = 17 pages; the walk gathers 16
    # pages (256 tokens) a step into one half of a double buffer; the
    # pool itself stays in HBM (aliased, memory_space=ANY)
    H, W, R, ps = (_MLA[k] for k in ("H", "W", "R", "ps"))
    M, npg, cpk = 128 * H, 17, 16
    return (2 * _B((M, W), "bfloat16")             # q tile stream
            + _B((npg, ps, W), "bfloat16")         # the chunk, page-shaped
            + _B((npg, ps, 1), "float32")          # row mask
            + 2 * _B((M, R), "float32")            # out tile stream
            + _B((npg, ps, W), "bfloat16")         # page RMW scratch
            + _B((2, cpk, ps, W), "bfloat16")      # walk double buffer
            + 2 * _B((M, 1), "float32")            # running max, sum
            + _B((M, R), "float32"))               # accumulator


def _build_mla_paged_decode():
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.mla_attention import mla_decode_attend

    H, W, R, ps, P, pp, S = (_MLA[k] for k in
                             ("H", "W", "R", "ps", "P", "pp", "S"))

    def fn(q, rows, pool, tables, lens):
        return mla_decode_attend(q, rows, pool, tables, lens, 256,
                                 v_width=R)

    return fn, (_sds((S, H, W), jnp.bfloat16), _sds((S, W), jnp.bfloat16),
                _sds((P, ps, W), jnp.bfloat16), _sds((S, pp), jnp.int32),
                _sds((S,), jnp.int32))


def _expected_mla_paged_decode():
    # a grid step a sequence: its 32 query rows, its new row, its slot
    # selector and its output stream; the walk gathers 64 pages (1024
    # tokens) a step into one half of a double buffer
    H, W, R, ps = (_MLA[k] for k in ("H", "W", "R", "ps"))
    cpk = 64
    return (2 * _B((1, H, W), "bfloat16")          # q rows
            + 2 * _B((1, 1, W), "float32")         # the new row
            + 2 * _B((1, ps, 1), "float32")        # slot selector
            + 2 * _B((1, H, R), "float32")         # out rows
            + _B((ps, W), "bfloat16")              # page RMW scratch
            + _B((2, cpk, ps, W), "bfloat16"))     # walk double buffer


KERNEL_SITES: List[KernelSite] = [
    KernelSite("stream_linear.bf16", "nn/functional/stream_linear.py",
               _build_stream_linear, _expected_stream_linear),
    KernelSite("stream_linear.a8w8", "nn/functional/stream_linear.py",
               _build_stream_linear_a8w8, _expected_stream_linear_a8w8),
    KernelSite("stream_linear.layer_tail",
               "nn/functional/stream_linear.py",
               _build_stream_layer_tail, _expected_stream_layer_tail),
    KernelSite("paged_attention.decode_inplace",
               "nn/functional/paged_attention.py",
               _build_decode_inplace, _expected_decode_inplace),
    KernelSite("paged_attention.decode_inplace_q",
               "nn/functional/paged_attention.py",
               _build_decode_inplace_q, _expected_decode_inplace_q),
    # the stock jax flash kernel: geometry-checked but no hand block
    # list (its internals are jax's, not ours)
    # its forward launch carries the scope opened around JAX's call
    KernelSite("attention.flash", "nn/functional/attention.py",
               _build_flash, None, kernels=("pt_flash_mha_fwd",)),
    KernelSite("flash_varlen.packed_fwd",
               "nn/functional/flash_varlen.py",
               _build_flash_varlen_fwd, _expected_flash_varlen_fwd),
    # grad trace records fwd (residuals) + dq + dk/dv kernels
    KernelSite("flash_varlen.packed_bwd",
               "nn/functional/flash_varlen.py",
               _build_flash_varlen_bwd, _expected_flash_varlen_bwd,
               n_calls=3,
               kernels=("pt_flash_varlen_packed_fwd",
                        "pt_flash_varlen_packed_dq",
                        "pt_flash_varlen_packed_dkv")),
    KernelSite("flash_varlen.paged", "nn/functional/flash_varlen.py",
               _build_flash_varlen_paged, _expected_flash_varlen_paged),
    # the chunked-prefill write into the pool, in place (ISSUE 29):
    # the layer loop's only other touch of the pool besides the attend
    KernelSite("paged_kv.write", "nn/functional/paged_attention.py",
               _build_paged_kv_write, _expected_paged_kv_write),
    # ragged grouped-GEMM MoE (ISSUE 15): fwd, and the grad trace's
    # fwd + pre-activation recompute + dx walk + dw segment kernel
    KernelSite("grouped_gemm.fwd", "nn/functional/grouped_gemm.py",
               _build_grouped_gemm_fwd, _expected_grouped_gemm_fwd),
    KernelSite("grouped_gemm.bwd", "nn/functional/grouped_gemm.py",
               _build_grouped_gemm_bwd, _expected_grouped_gemm_bwd,
               n_calls=4,
               kernels=("pt_grouped_gemm_fwd",) * 3
               + ("pt_grouped_gemm_dw",)),
    # batched multi-LoRA delta (ISSUE 18): one ragged launch carrying
    # every adapter's x·A·B for an adapter-sorted chunk
    KernelSite("lora.delta", "nn/functional/lora.py",
               _build_lora_delta, _expected_lora_delta),
    # state-space layers and routed gated experts of a pattern-built
    # stack (ISSUE 31): the chunked SSD scan of a prefill chunk, the
    # in-place one-token state update, the decode rows' expert stream
    KernelSite("ssd.chunk_scan", "nn/functional/ssm.py",
               _build_ssd_chunk_scan, _expected_ssd_chunk_scan),
    KernelSite("ssm.decode_update", "nn/functional/ssm.py",
               _build_ssm_decode_update, _expected_ssm_decode_update),
    KernelSite("moe.stream_experts", "nn/functional/moe_gated.py",
               _build_moe_stream_experts, _expected_moe_stream_experts),
    # latent attention over the paged latent pool (ISSUE 37): a prefill
    # chunk written in place and attended over its prefix, and the
    # one-token append + attend, both in the absorbed form
    KernelSite("mla.paged_prefill", "nn/functional/mla_attention.py",
               _build_mla_paged_prefill, _expected_mla_paged_prefill),
    KernelSite("mla.paged_decode", "nn/functional/mla_attention.py",
               _build_mla_paged_decode, _expected_mla_paged_decode),
]


def trace_site(site: KernelSite) -> List[PallasCallRecord]:
    """Dry-trace one site; returns its recorded launch specs."""
    import jax

    fn, args = site.build()
    with _force_tpu_routing(), record_pallas_calls() as records:
        jax.eval_shape(fn, *args)
    if len(records) != site.n_calls:
        raise AssertionError(
            f"{site.name}: expected {site.n_calls} pallas_call(s), "
            f"recorded {len(records)} — kernel routing changed; update "
            "analysis/sites.py")
    return records


def trace_all_sites():
    """name -> records for the full kernel inventory."""
    return {site.name: trace_site(site) for site in KERNEL_SITES}
