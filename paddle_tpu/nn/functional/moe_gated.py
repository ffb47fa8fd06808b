"""Gated (SiLU) bias-free routed experts for the serving path, with an
expert layer that is told which experts it holds.

Routing: ``l = x W_r`` in float32 over ALL experts, the ``top_k``
largest, gates = softmax over the chosen logits (``route_topk_softmax``);
or ``s = sigmoid(l)``, the ``top_k`` largest of ``s + b`` (a per-expert
selection bias that picks experts and never weights them), gates = the
chosen ``s`` over their sum times a scaling factor
(``route_topk_sigmoid``). An expert is
``e(x) = W2_e (silu(a) * b)`` with ``[a | b] = x W1_e``; the layer's
output is the gate-weighted sum over the picks that name an expert
held HERE (``held = (first, count)``: the contiguous slice of the bank
this chip stores). A pick of another expert adds nothing.

Two paths over the same weights ``w1 [E_held, d, 2f]`` /
``w2 [E_held, f, d]``:

``moe_gated_grouped``  prefill rows: the picks that landed here, sorted
    by expert, through the ragged grouped GEMM
    (``pt_grouped_gemm_fwd``, twice), then combined into their tokens'
    rows by ``pt_moe_combine``, which reads only those picks.
``moe_gated_stream``   decode rows: EVERY held expert's weights streamed
    once past all rows with the gates as a dense ``[rows, E_held]``
    matrix of mostly zeros (``pt_moe_stream_experts``). At 64 rows and
    top-10 of 72 every held expert is hit in 99.99% of steps, so
    skipping the unhit ones would save nothing and make the step time
    depend on the data; the MXU work over rows that did not pick the
    expert hides under the weight stream.

Off the chip the stream path is a plain XLA loop over experts, the
grouped GEMM takes its XLA tile walk and the combine scatters and sums
all ``T x k`` picks: tier-1 needs no interpreter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...device import chip as _chip
from ...device.vmem import KERNEL_VMEM_LIMIT_BYTES
from .grouped_gemm import grouped_gemm_banked
from .paged_attention import _enable_x64

__all__ = ["route_topk_softmax", "route_topk_sigmoid", "dense_gates", "pick_counts",
           "moe_gated_grouped", "moe_combine", "moe_gated_stream", "gated_mlp"]


def route_topk_softmax(x, router_w, top_k: int):
    """x ``[T, d]``, ``router_w [d, E]`` -> ``(gates [T, k] f32, idx
    [T, k] int32)``: float32 logits, the k largest, softmax over those k
    (NOT a softmax over all E followed by renormalisation: the two
    differ by nothing in exact arithmetic, but this is the order the
    model states and the one the reference takes)."""
    # tpu-lint: ok(X-PROMOTE) -- fp32 routing by design (top-k margins)
    logits = jax.lax.dot_general(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    val, idx = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(val, axis=-1), idx.astype(jnp.int32)


def route_topk_sigmoid(x, router_w, bias, top_k: int, scaling=1.0):
    """x ``[T, d]``, ``router_w [d, E]``, ``bias [E]`` -> ``(gates [T, k]
    f32, idx [T, k] int32)``: float32 sigmoid scores over all E, the k
    largest of scores + bias, the chosen SCORES (the bias left out) over
    their sum, times ``scaling``."""
    # tpu-lint: ok(X-PROMOTE) -- fp32 routing by design (top-k margins)
    logits = jax.lax.dot_general(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32)[None, :], top_k)
    val = jnp.take_along_axis(s, idx, axis=-1)
    gates = val / jnp.sum(val, axis=-1, keepdims=True) * scaling
    return gates, idx.astype(jnp.int32)


def dense_gates(gates, idx, held):
    """``[T, k]`` picks -> ``[T, E_held]`` float32 gate matrix, zero
    where an expert was not picked; picks outside ``held`` vanish."""
    first, count = held
    local = idx - first
    here = (local >= 0) & (local < count)
    onehot = jax.nn.one_hot(jnp.where(here, local, count), count + 1,
                            dtype=jnp.float32)[..., :count]
    return jnp.einsum("tk,tke->te", gates, onehot)


def pick_counts(idx, rows, held):
    """int32 ``[3]``: picks of the ``rows`` (bool ``[T]``) in all, those
    that name an expert held here, and held experts hit at least once."""
    first, count = held
    local = idx - first
    here = (local >= 0) & (local < count) & rows[:, None]
    hit = jnp.zeros((count + 1,), jnp.int32).at[
        jnp.where(here, local, count).reshape(-1)].max(
            jnp.ones((idx.size,), jnp.int32))[:count]
    return jnp.stack([jnp.sum(rows) * idx.shape[1], jnp.sum(here),
                      jnp.sum(hit)]).astype(jnp.int32)


def gated_mlp(x, w1, w2):
    """The shared MLP, every token: ``(silu(a) * b) W2``, ``[a|b] = x
    W1``; float32 accumulation, float32 result."""
    f = w2.shape[0]
    ab = jax.lax.dot_general(x, w1, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    mid = (jax.nn.silu(ab[:, :f]) * ab[:, f:]).astype(x.dtype)
    return jax.lax.dot_general(mid, w2, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def moe_gated_grouped(x, gates, idx, w1, w2, layer: int, held,
                      backend="auto"):
    """Prefill rows. x ``[T, d]``; ``w1 [L, E_held, d, 2f]`` / ``w2 [L,
    E_held, f, d]`` the layer-stacked banks, read in place
    (``grouped_gemm_banked``). Returns ``[T, d]`` float32 and int32
    ``[2]``: the grid steps the two GEMMs' schedules walked and those
    that owned a row."""
    T, d = x.shape
    k = idx.shape[1]
    first, count = held
    L, E, f, _ = w2.shape
    w1 = w1.reshape(L * E, d, 2 * f)
    w2 = w2.reshape(L * E, f, d)
    base = int(layer) * E
    local = idx - first
    here = (local >= 0) & (local < count)
    # absent picks sort behind the last held expert: rows past
    # offsets[E] come out of the grouped GEMM as zeros
    key = jnp.where(here, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # compares and a column sum, not a bincount: a scatter-add of T*k
    # keys runs serially on the chip (0.15 ms a layer at 2,560 keys)
    counts = jnp.sum(key[:, None] == jnp.arange(count, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    rows = jnp.take(x, order // k, axis=0)                     # [T*k, d]
    ab, units1 = grouped_gemm_banked(rows, w1, offsets, base,
                                     backend=backend)
    mid = (jax.nn.silu(ab[:, :f]) * ab[:, f:]).astype(x.dtype)
    out, units2 = grouped_gemm_banked(mid, w2, offsets, base,
                                      backend=backend)         # [T*k, d]
    g = jnp.where(here, gates, 0.0).reshape(-1)[order]
    # off the chip every pick is scattered and summed, the absent ones as
    # zeros; the kernel reads only the rows below offsets[E]
    if backend == "xla" or (backend == "auto" and not _chip.on_tpu()):
        y = jnp.zeros((T * k, d), jnp.float32).at[order].set(
            out * g[:, None])
        return jnp.sum(y.reshape(T, k, d), axis=1), units1 + units2
    y = moe_combine(out, g, order // k, offsets[count:], T,
                    interpret=backend == "interpret" or not _chip.on_tpu())
    return y, units1 + units2


#: sorted rows a grid step of ``pt_moe_combine``
_COMBINE_ROWS = 128


def _combine_bn(T: int, d: int) -> int:
    """The widest column block whose resident ``[T, bn]`` float32 result
    and ``[128, bn]`` row tile, both double-buffered, take at most half of
    ``KERNEL_VMEM_LIMIT_BYTES``: the whole width at the cells' shapes (36
    MB at T 1,024, d 4,096). A narrower block costs a walk of the rows a
    column block."""
    bn = d
    while bn % 256 == 0 and \
            8 * (T + _COMBINE_ROWS) * bn > KERNEL_VMEM_LIMIT_BYTES // 2:
        bn //= 2
    return bn


def moe_combine(out, g, tok, live, T: int, *, interpret=False):
    """The routed experts' combine over the picks held here
    (``pt_moe_combine``). ``out [R, d]`` float32: the second GEMM's rows,
    sorted by expert; ``g [R]`` float32 their gates; ``tok [R]`` int32 the
    token each row belongs to; ``live`` int32 ``[1]``: rows ``[0, live)``
    name a held expert (``offsets[E]``). Returns ``[T, d]`` float32, each
    live row's ``g[r] * out[r]`` added into row ``tok[r]`` in sorted
    order.

    The grid walks 128-row tiles of ``out`` with the ``[T, bn]`` result
    resident in VMEM (zeroed at the first tile, written back once) and
    adds one row at a time through a dynamic row store: the work follows
    the live rows, not ``T x k``. A row at or past ``live`` is never read;
    a tile past the last live row is never fetched (its block index
    repeats the last live tile's, as the grouped GEMM's ``x`` tiles do)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, d = out.shape
    bm = _COMBINE_ROWS
    rp = -(-R // bm) * bm
    if rp != R:
        out = jnp.pad(out, ((0, rp - R), (0, 0)))
    bn = _combine_bn(T, d)

    def kernel(live_r, tok_r, g_r, out_ref, y_ref):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _():
            y_ref[...] = jnp.zeros_like(y_ref)

        def add_row(r, carry):
            s = i * bm + r
            y_ref[pl.ds(tok_r[s], 1), :] += out_ref[pl.ds(r, 1), :] * g_r[s]
            return carry

        jax.lax.fori_loop(0, jnp.clip(live_r[0] - i * bm, 0, bm), add_row,
                          0)

    def tile(j, i, live_r, tok_r, g_r):
        return jnp.minimum(i, jnp.maximum(live_r[0] - 1, 0) // bm), j

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d // bn, rp // bm),
        in_specs=[pl.BlockSpec((bm, bn), tile)],
        out_specs=pl.BlockSpec((T, bn), lambda j, i, *_: (0, j)))
    with _enable_x64(False), jax.named_scope("pt_moe_combine"):
        return pl.pallas_call(
            kernel,
            name="pt_moe_combine",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(live, tok, g, out)


def _f_tile(f: int) -> int:
    for t in (256, 128):
        if f % t == 0:
            return t
    return f


def moe_gated_stream(x, gates, idx, w1, w2, layer: int, held,
                     backend="auto"):
    """Decode rows. x ``[M, d]``; ``w1 [L, E_held, d, 2f]`` / ``w2 [L,
    E_held, f, d]`` the layer-stacked banks (read in place through the
    block index: no per-layer slice is ever materialised); ``layer`` a
    Python int. Returns ``[M, d]`` float32."""
    if backend not in ("auto", "interpret", "xla"):
        raise ValueError(f"moe_gated_stream backend={backend!r}")
    M, d = x.shape
    _, E, _, f2 = w1.shape
    f = f2 // 2
    dg = dense_gates(gates, idx, held)                         # [M, E]
    if backend == "xla" or (backend == "auto" and not _chip.on_tpu()):
        with jax.named_scope("pt_moe_stream_experts"):
            def one(acc, e):
                ab = jax.lax.dot_general(
                    x, w1[layer, e], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                mid = jax.nn.silu(ab[:, :f]) * ab[:, f:] \
                    * jax.lax.dynamic_slice_in_dim(dg, e, 1, 1)
                return acc + jax.lax.dot_general(
                    mid.astype(x.dtype), w2[layer, e],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32), None

            out, _ = jax.lax.scan(one, jnp.zeros((M, d), jnp.float32),
                                  jnp.arange(E))
            return out
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tf = _f_tile(f)
    nf = f // tf
    layer = int(layer)
    sub = 16 if x.dtype == jnp.bfloat16 else 8
    Mp = -(-M // sub) * sub
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
        dg = jnp.pad(dg, ((0, Mp - M), (0, 0)))
    gcol = jnp.transpose(dg)[:, :, None]                       # [E, Mp, 1]

    def dot(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    def kernel(x_ref, g_ref, wa_ref, wb_ref, w2_ref, o_ref):
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        xv = x_ref[...]
        a = dot(xv, wa_ref[0, 0])
        b = dot(xv, wb_ref[0, 0])
        mid = (jax.nn.silu(a) * b * g_ref[0]).astype(xv.dtype)
        o_ref[...] += dot(mid, w2_ref[0, 0])

    with _enable_x64(False), jax.named_scope("pt_moe_stream_experts"):
        out = pl.pallas_call(
            kernel,
            name="pt_moe_stream_experts",
            grid=(E, nf),
            in_specs=[
                pl.BlockSpec((Mp, d), lambda e, j: (0, 0)),
                pl.BlockSpec((1, Mp, 1), lambda e, j: (e, 0, 0)),
                pl.BlockSpec((1, 1, d, tf),
                             lambda e, j: (layer, e, 0, j)),
                pl.BlockSpec((1, 1, d, tf),
                             lambda e, j: (layer, e, 0, nf + j)),
                pl.BlockSpec((1, 1, tf, d),
                             lambda e, j: (layer, e, j, 0)),
            ],
            out_specs=pl.BlockSpec((Mp, d), lambda e, j: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((Mp, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(x, gcol, w1, w1, w2)
    return out[:M]
