"""State-space (Mamba-2 / SSD) layer pieces for the serving path.

Per head ``h`` (state ``S`` in R^{P x N}, ``P`` = head_dim, ``N`` =
d_state, one B/C group shared by every head)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * u_t B_t^T
    y_t = S_t C_t + D * u_t

Three entry points, each named in the device trace (``name=`` and a
``jax.named_scope`` of the same string; rows in ``analysis/sites.py``):

``ssd_chunk_scan``     prefill: the chunked SSD form (``pt_ssd_chunk_scan``)
    — inside a chunk of ``Q`` tokens the recurrence is three MXU
    products, between chunks one [N, P] state is passed; an initial
    state is taken and the final one returned, so a prompt may be
    prefilled in pieces. Rows with ``dt == 0`` neither move the state
    nor feed it: that is how the padding of a bucketed chunk is kept
    out.
``ssm_decode_update``  decode: one token a sequence, the whole
    slot-indexed state array updated IN PLACE (``pt_ssm_decode_update``,
    ``input_output_aliases`` as ``pt_paged_kv_write`` aliases the pool):
    8.4 MB read + written a sequence and layer, nothing else of weight.
``causal_conv1d_chunk`` / ``causal_conv1d_step``: the depthwise causal
    conv in front of the scan, plain XLA under the scope
    ``pt_causal_conv1d`` (kilobytes a row; its carried tail is the last
    ``k - 1`` VALID rows).

State layout: ``[N, H * P]`` float32 a sequence and layer (lanes are
``(head, p)``): the decode kernel then works on dense 2-D tiles — the
per-lane decay and ``dt * u`` rows broadcast down the sublanes, B and C
columns across the lanes, ``y`` is a sublane reduction. A ``[H, P, N]``
layout would leave half of every 128-lane tile empty at P = 64.

Off the chip both kernels fall back to plain XLA (``backend="auto"``),
so the tier-1 tests need no interpreter; ``backend="interpret"`` runs the
kernels through the Pallas interpreter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...device import chip as _chip
from ...device.vmem import KERNEL_VMEM_LIMIT_BYTES
from .paged_attention import _enable_x64

__all__ = ["causal_conv1d_chunk", "causal_conv1d_step", "ssd_chunk_scan",
           "ssm_decode_update", "ssm_scan_reference", "expand_heads"]

_BACKENDS = ("auto", "interpret", "xla")


def _use_kernel(backend: str, what: str) -> bool:
    if backend not in _BACKENDS:
        raise ValueError(f"{what} backend={backend!r}: expected one of "
                         f"{_BACKENDS}")
    return backend == "interpret" or (backend == "auto"
                                      and _chip.on_tpu())


def expand_heads(v, head_dim: int):
    """[..., H] per-head values -> [..., H * head_dim] per-lane values."""
    return jnp.repeat(v, head_dim, axis=-1)


# ---------------------------------------------------------------------
# causal depthwise conv (XLA, named)
# ---------------------------------------------------------------------

def causal_conv1d_chunk(x, tail, w, b, valid_len=None):
    """``silu(conv1d_causal(x))`` over a chunk that continues a sequence.

    x ``[b, c, C]`` rows, ``tail [b, k-1, C]`` the last rows before the
    chunk (zeros at a sequence's start), ``w [k, C]`` / ``b [C]`` the
    depthwise taps (tap ``j`` multiplies the row ``k-1-j`` back).
    Returns ``(y [b, c, C] in x.dtype, new_tail [b, k-1, C])``; the new
    tail holds the ``k-1`` rows that precede position ``valid_len[b]``
    of the chunk, i.e. the last VALID rows — padding never enters it.
    """
    bsz, c, C = x.shape
    km1 = w.shape[0] - 1
    with jax.named_scope("pt_causal_conv1d"):
        padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        acc = b.astype(jnp.float32)[None, None, :]
        for j in range(km1 + 1):
            acc = acc + w[j].astype(jnp.float32)[None, None, :] \
                * padded[:, j: j + c].astype(jnp.float32)
        y = jax.nn.silu(acc).astype(x.dtype)
        if valid_len is None:
            new_tail = padded[:, c:]
        else:
            n = jnp.clip(valid_len.astype(jnp.int32), 0, c)
            new_tail = jax.vmap(
                lambda p, s: jax.lax.dynamic_slice_in_dim(p, s, km1, 0))(
                    padded, n)
    return y, new_tail.astype(tail.dtype)


def causal_conv1d_step(x, tail, w, b, active=None):
    """One token a sequence: x ``[b, C]``, ``tail [b, k-1, C]``. Rows
    where ``active`` is False keep their tail. Returns ``(y, tail')``."""
    with jax.named_scope("pt_causal_conv1d"):
        window = jnp.concatenate([tail.astype(x.dtype), x[:, None]], 1)
        acc = b.astype(jnp.float32)[None, :] + jnp.einsum(
            "bkc,kc->bc", window.astype(jnp.float32),
            w.astype(jnp.float32))
        y = jax.nn.silu(acc).astype(x.dtype)
        new_tail = window[:, 1:].astype(tail.dtype)
        if active is not None:
            new_tail = jnp.where(active[:, None, None], new_tail, tail)
    return y, new_tail


# ---------------------------------------------------------------------
# reference: the recurrence token by token
# ---------------------------------------------------------------------

def ssm_scan_reference(x, dt, A, B, C, D, init_state=None):
    """The recurrence as a plain ``lax.scan`` over tokens, float32.

    x ``[T, H, P]``, dt ``[T, H]`` (after softplus), A ``[H]``
    (negative), B / C ``[T, N]``, D ``[H]``, ``init_state [N, H*P]``.
    Returns ``(y [T, H*P] f32, final_state [N, H*P] f32)``."""
    T, H, P = x.shape
    N = B.shape[-1]
    f32 = jnp.float32
    s0 = jnp.zeros((N, H * P), f32) if init_state is None \
        else init_state.astype(f32)

    def step(s, inp):
        xt, dtt, bt, ct = inp
        decay = expand_heads(jnp.exp(dtt * A), P)              # [HP]
        dtx = expand_heads(dtt, P) * xt.reshape(-1)
        s = s * decay[None, :] + bt[:, None] * dtx[None, :]
        y = jnp.sum(s * ct[:, None], axis=0) \
            + expand_heads(D, P) * xt.reshape(-1)
        return s, y

    s, y = jax.lax.scan(step, s0, (x.astype(f32), dt.astype(f32),
                                   B.astype(f32), C.astype(f32)))
    return y, s


# ---------------------------------------------------------------------
# prefill: chunked SSD scan
# ---------------------------------------------------------------------


def _ssd_xla(x, dt, cs, B, C, D, init, Q):
    """The chunked form in plain float32 XLA: scan over chunks, every
    head at once. Same products as the kernel, no bf16 casts."""
    T, H, P = x.shape
    N = B.shape[-1]
    nq = T // Q
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    xs = x.astype(f32).reshape(nq, Q, H, P)
    dts = dt.reshape(nq, Q, H)
    css = cs.reshape(nq, Q, H)
    Bs = B.astype(f32).reshape(nq, Q, N)
    Cs = C.astype(f32).reshape(nq, Q, N)
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def chunk(s, inp):                       # s [H, N, P]
        xq, dq, cq, bq, cq_ = inp
        seg = cq[:, None, :] - cq[None, :, :]                  # [i, j, H]
        L = jnp.where(tri[:, :, None], jnp.exp(jnp.minimum(seg, 0.0)),
                      0.0)
        G = jnp.einsum("in,jn->ij", cq_, bq, precision=hi)
        xdt = xq * dq[:, :, None]
        y = jnp.einsum("ij,ijh,jhp->ihp", G, L, xdt, precision=hi)
        y = y + jnp.exp(cq)[:, :, None] * jnp.einsum(
            "in,hnp->ihp", cq_, s, precision=hi)
        last = cq[-1]                                          # [H]
        w = jnp.exp(last[None, :] - cq) * dq                   # [Q, H]
        s = jnp.exp(last)[:, None, None] * s + jnp.einsum(
            "jn,jh,jhp->hnp", bq, w, xq, precision=hi)
        return s, y + D[None, :, None] * xq

    s0 = jnp.transpose(init.reshape(N, H, P), (1, 0, 2))
    s, ys = jax.lax.scan(chunk, s0, (xs, dts, css, Bs, Cs))
    return ys.reshape(T, H * P), \
        jnp.transpose(s, (1, 0, 2)).reshape(N, H * P)


def _ssd_pallas(x, dt, cs, B, C, D, init, Q, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, P = x.shape
    N = B.shape[-1]
    nq = T // Q
    f32 = jnp.float32
    mxu = x.dtype if x.dtype == jnp.bfloat16 else f32
    # head-major operands: the head is a LEADING block index, never a
    # lane offset (Mosaic has no dynamic lane slicing)
    x_h = jnp.transpose(x, (1, 0, 2))                          # [H, T, P]
    cs_r = jnp.transpose(cs)                                   # [H, T]
    bt = jnp.transpose(B).astype(mxu)                          # [N, T]
    s0 = jnp.transpose(init.reshape(N, H, P), (1, 0, 2))       # [H, N, P]

    def dot(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=f32)

    # each chunk's total decay a head, as SMEM scalars (a [1, 1] slice at
    # lane Q-1 of the row form has no layout Mosaic can broadcast)
    last = cs.reshape(nq, Q, H)[:, -1, :].reshape(-1)          # [nq * H]

    def kernel(d_ref, last_ref, elast_ref, x_ref, csr_ref, csc_ref,
               dtc_ref, bt_ref, c_ref, s0_ref, y_ref, fin_ref, s_scr):
        h = pl.program_id(0)
        ci = pl.program_id(1)

        @pl.when(ci == 0)
        def _():
            s_scr[...] = s0_ref[0]

        xq = x_ref[0]                                          # [Q, P]
        lane = jax.lax.broadcasted_iota(jnp.int32, (Q, H), 1)
        pick = lane == h
        cs_col = jnp.sum(jnp.where(pick, csc_ref[...], 0.0), axis=1,
                         keepdims=True)                        # [Q, 1]
        dt_col = jnp.sum(jnp.where(pick, dtc_ref[...], 0.0), axis=1,
                         keepdims=True)
        cs_row = csr_ref[pl.ds(h, 1), :]                       # [1, Q]
        ri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        ji = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        L = jnp.where(ri >= ji,
                      jnp.exp(jnp.minimum(cs_col - cs_row, 0.0)), 0.0)
        cq = c_ref[...]                                        # [Q, N]
        G = dot(cq, bt_ref[...])                               # [Q, Q]
        xf = xq.astype(f32)
        xdt = (xf * dt_col).astype(mxu)
        y = dot((G * L).astype(mxu), xdt)                      # [Q, P]
        s_prev = s_scr[...]                                    # [N, P]
        y = y + jnp.exp(cs_col) * dot(cq, s_prev.astype(mxu))
        y = y + d_ref[h] * xf
        w_col = jnp.exp(last_ref[ci * H + h] - cs_col) * dt_col  # [Q, 1]
        s_new = elast_ref[ci * H + h] * s_prev \
            + dot(bt_ref[...], (xf * w_col).astype(mxu))       # [N, P]
        s_scr[...] = s_new
        y_ref[0] = y.astype(y_ref.dtype)

        @pl.when(ci == nq - 1)
        def _():
            fin_ref[0] = s_new

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(H, nq),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda h, c, *_: (h, c, 0)),
            pl.BlockSpec((H, Q), lambda h, c, *_: (0, c)),
            pl.BlockSpec((Q, H), lambda h, c, *_: (c, 0)),
            pl.BlockSpec((Q, H), lambda h, c, *_: (c, 0)),
            pl.BlockSpec((N, Q), lambda h, c, *_: (0, c)),
            pl.BlockSpec((Q, N), lambda h, c, *_: (c, 0)),
            pl.BlockSpec((1, N, P), lambda h, c, *_: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda h, c, *_: (h, c, 0)),
            pl.BlockSpec((1, N, P), lambda h, c, *_: (h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), f32)])
    with _enable_x64(False), jax.named_scope("pt_ssd_chunk_scan"):
        y_h, fin = pl.pallas_call(
            kernel,
            name="pt_ssd_chunk_scan",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((H, T, P), f32),
                       jax.ShapeDtypeStruct((H, N, P), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(D.astype(f32), last, jnp.exp(last), x_h, cs_r, cs, dt, bt,
          C.astype(mxu), s0)
    y = jnp.transpose(y_h, (1, 0, 2)).reshape(T, H * P)
    return y, jnp.transpose(fin, (1, 0, 2)).reshape(N, H * P)


def ssd_chunk_scan(x, dt, A, B, C, D, init_state=None, *,
                   chunk_size: int = 256, backend: str = "auto"):
    """Chunked SSD scan of ONE sequence piece.

    x ``[T, H, P]``; dt ``[T, H]`` float32, already softplus'd, ZERO on
    rows that must not advance the state; A ``[H]`` float32 (negative);
    B / C ``[T, N]``; D ``[H]``; ``init_state [N, H*P]`` float32 (None
    = zeros). A piece longer than ``chunk_size`` that is no multiple of
    it is padded with rows of ``dt = 0``, which change nothing. Returns ``(y [T, H*P] float32, final_state [N, H*P] float32)``.

    The in-chunk decay sums run in float32 XLA (a cumulative sum over at
    most ``chunk_size`` rows, kilobytes); the kernel sees them as ``cs``
    and forms ``exp(cs_i - cs_j)`` itself, so no product of growing and
    shrinking exponentials can overflow.
    """
    T0, H, P = x.shape
    N = B.shape[-1]
    Q = min(T0, chunk_size)
    pad = -T0 % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, dt, B, C))
    T = T0 + pad
    f32 = jnp.float32
    dt = dt.astype(f32)
    a = dt * A.astype(f32)[None, :]
    cs = jnp.cumsum(a.reshape(T // Q, Q, H), axis=1).reshape(T, H)
    init = jnp.zeros((N, H * P), f32) if init_state is None \
        else init_state.astype(f32)
    if _use_kernel(backend, "ssd_chunk_scan"):
        y, s = _ssd_pallas(x, dt, cs, B, C, D.astype(f32), init, Q,
                           interpret=not _chip.on_tpu())
    else:
        y, s = _ssd_xla(x, dt, cs, B, C, D.astype(f32), init, Q)
    return y[:T0], s


# ---------------------------------------------------------------------
# decode: one token a sequence, the state array updated in place
# ---------------------------------------------------------------------

def _lane_block(hp: int) -> int:
    """Lanes of state a grid step moves: [N, w] float32 blocks of about
    2 MB (in and out, double-buffered: 8 MB of VMEM)."""
    w = min(hp, 4096)
    while hp % w:
        w //= 2
    return w


def ssm_decode_update(state, layer: int, decay, dtx, B, C, *,
                      backend: str = "auto"):
    """One recurrence step for every slot of one layer, in place.

    ``state [L, slots, N, H*P]`` float32, the whole slot-indexed array
    (ALIASED: only layer ``layer``'s blocks are read and written);
    ``decay`` / ``dtx`` ``[slots, H*P]`` float32 per-lane ``exp(dt*A)``
    and ``dt*u``; B / C ``[slots, N]``. A slot whose row has ``decay ==
    1`` and ``dtx == 0`` keeps its state bit for bit (idle slots and
    slots still prefilling pass through the decode batch that way).
    Returns ``(state', y [slots, H*P] float32)`` with ``y = S' C``.
    """
    L, S, N, HP = state.shape
    f32 = jnp.float32
    if not _use_kernel(backend, "ssm_decode_update"):
        with jax.named_scope("pt_ssm_decode_update"):
            s = state[layer]
            s = s * decay.astype(f32)[:, None, :] \
                + B.astype(f32)[:, :, None] * dtx.astype(f32)[:, None, :]
            y = jnp.sum(s * C.astype(f32)[:, :, None], axis=1)
            return state.at[layer].set(s), y
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w = _lane_block(HP)
    nw = HP // w
    layer = int(layer)
    bc = jnp.stack([B.astype(f32), C.astype(f32)], axis=-1)     # [S, N, 2]

    def kernel(dec_ref, dtx_ref, bc_ref, s_ref, o_ref, y_ref):
        bcv = bc_ref[0]                                        # [N, 2]
        s = s_ref[0, 0] * dec_ref[0] + bcv[:, 0:1] * dtx_ref[0]
        o_ref[0, 0] = s
        y_ref[0] = jnp.sum(s * bcv[:, 1:2], axis=0, keepdims=True)

    row = pl.BlockSpec((1, 1, w), lambda b, j: (b, 0, j))
    blk = pl.BlockSpec((1, 1, N, w), lambda b, j: (layer, b, 0, j))
    with _enable_x64(False), jax.named_scope("pt_ssm_decode_update"):
        new_state, y = pl.pallas_call(
            kernel,
            name="pt_ssm_decode_update",
            grid=(S, nw),
            in_specs=[row, row,
                      pl.BlockSpec((1, N, 2), lambda b, j: (b, 0, 0)),
                      blk],
            out_specs=[blk, row],
            out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                       jax.ShapeDtypeStruct((S, 1, HP), f32)],
            input_output_aliases={3: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(decay.astype(f32)[:, None, :], dtx.astype(f32)[:, None, :], bc,
          state)
    return new_state, y[:, 0, :]
