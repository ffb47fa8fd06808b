"""SPMD pipeline-parallel engines over a ``pp`` mesh axis.

TPU-native replacement for the reference's NCCL point-to-point pipeline
runtime (reference: fleet/meta_parallel/pipeline_parallel.py —
``forward_backward_pipeline:440`` 1F1B, ``:906`` interleave;
``pp_utils/p2p_communication.py:313`` batch_isend_irecv). On TPU there
are no streams or eager P2P sends: stage-to-device placement is a mesh
axis, and every stage handoff is a ``jax.lax.ppermute``
(collective-permute riding ICI) inside ONE compiled XLA program.

Two engines:

``pipeline_1f1b_grads``
    True 1F1B. Each macro-tick runs one stage forward and one stage
    backward per device (uniform SPMD clock):

      F of microbatch m at stage s   @ tick  m + s
      B of microbatch m at stage s   @ tick  m + 2P - 1 - s

    Warmup (ticks with invalid B), steady 1F1B (both valid), cooldown
    (invalid F) fall out of the validity masks. Residuals (the vjp
    closures, flattened to arrays) live in a ring buffer of depth 2P —
    the 1F1B O(pp_depth) live-activation bound, independent of the
    number of micro-batches. Backward consumes the ring at a
    stage-dependent lag (2(P-s)-1 ticks) via a dynamic gather on
    ``lax.axis_index``.

``circular_pipeline_fwd``
    Differentiable circular rotation, ``v`` chunks per device
    (num_virtual_pipeline_stages) — the interleave topology: virtual
    stage k lives on device k mod P, activations ppermute around the
    ring and wrap from the last device back to the first for the next
    chunk round. F-then-B schedule via plain ``jax.grad`` through the
    rotation (the reverse pipeline is generated by autodiff);
    ``jax.checkpoint`` on the stage body keeps stored residuals to the
    stage boundaries.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_1f1b_grads", "pipeline_interleaved_1f1b_grads",
           "circular_pipeline_fwd", "ring_perm"]


def _stable_vjp(stage_fn):
    """``jax.vjp(stage_fn, ...)`` traced ONCE: the residual ring stores
    the vjp closure's leaves tick after tick, so every tick must yield
    the same leaf list. Separate ``jax.vjp`` traces do not promise that
    (constants may or may not be hoisted into residuals); one jitted
    wrapper is traced on its first call and reused from jit's cache."""
    return jax.jit(lambda p, x: jax.vjp(stage_fn, p, x))


def ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


def _tree_zeros(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _tree_where(pred, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(pred, x, y), a, b)


def pipeline_1f1b_grads(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    head_loss_fn: Callable[[Any, jnp.ndarray, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    head_params: Any,
    h_all: jnp.ndarray,
    labels_all: jnp.ndarray,
    *,
    mesh,
    num_stages: int,
    pp_axis: str = "pp",
) -> Tuple[jnp.ndarray, Any, Any, jnp.ndarray]:
    """One 1F1B forward+backward sweep, fully manual, one XLA program.

    Args:
      stage_fn:   (stage_params, x) -> y. Pure; structurally identical
                  across stages. stage_params is any pytree whose leaves
                  are this stage's slice of ``stacked_params``.
      head_loss_fn: (head_params, y, label) -> scalar mean loss of ONE
                  micro-batch. Applied (and differentiated) inline at the
                  last stage's forward ticks.
      stacked_params: pytree, leaves [P, ...] sharded over ``pp_axis``.
      head_params: pytree, replicated over pp.
      h_all:      [M, ...mb...] stage-0 inputs for all micro-batches.
      labels_all: [M, ...] labels.

    Returns (mean_loss, d_stacked [P,...], d_head, dh_all [M,...]) where
    dh_all is the cotangent of ``h_all`` (for the caller to backprop
    through whatever produced the stage-0 inputs, e.g. the embedding).
    """
    M = int(h_all.shape[0])
    Pn = int(num_stages)
    R = 2 * Pn  # residual ring depth — the O(pp) 1F1B bound
    T = M + 2 * Pn - 1  # macro-ticks

    stage_vjp = _stable_vjp(stage_fn)

    def body(stacked, head_p, h_all_, labels_):
        stage_p = jax.tree_util.tree_map(lambda a: a[0], stacked)
        s = jax.lax.axis_index(pp_axis)
        is_first = s == 0
        is_last = s == Pn - 1
        # backward lag per stage: B of m at tick (m + 2P-1-s), F at (m+s)
        lag = 2 * Pn - 1 - 2 * s

        d_stage = _tree_zeros(stage_p)
        d_head = _tree_zeros(head_p)
        loss_acc = jnp.zeros((), jnp.float32)
        dh_all = jnp.zeros_like(h_all_)

        ring = None            # list of leaves, each [R, ...]
        vjp_treedef = None
        x_recv = jnp.zeros_like(h_all_[0])
        cot_recv = jnp.zeros_like(h_all_[0])
        dy_prev = jnp.zeros_like(h_all_[0])
        fperm = ring_perm(Pn, 1)
        bperm = ring_perm(Pn, -1)

        for t in range(T):
            # ---------------- forward tick ----------------
            mf = t - s                       # traced microbatch index
            f_valid = (mf >= 0) & (mf < M)
            mf_c = jnp.clip(mf, 0, M - 1)
            x_in = jnp.where(is_first, h_all_[min(t, M - 1)], x_recv)
            y, vjp_f = stage_vjp(stage_p, x_in)
            leaves, td = jax.tree_util.tree_flatten(vjp_f)
            if ring is None:
                vjp_treedef = td
                ring = [jnp.zeros((R,) + l.shape, l.dtype) for l in leaves]
            ring = [r.at[t % R].set(l) for r, l in zip(ring, leaves)]

            # last stage: head + loss + its vjp, inline at the F tick
            lbl = labels_[mf_c]
            (loss_m, (dhp_m, dy_m)) = _head_value_and_grad(
                head_loss_fn, head_p, y, lbl)
            use_head = f_valid & is_last
            loss_acc = loss_acc + jnp.where(use_head, loss_m, 0.0)
            d_head = _tree_add(
                d_head, _tree_where(use_head, dhp_m, _tree_zeros(dhp_m)))
            dy_scaled = jax.tree_util.tree_map(
                lambda a: a / M, dy_m)

            # ---------------- backward tick ----------------
            mb = t - (2 * Pn - 1) + s
            b_valid = (mb >= 0) & (mb < M)
            cot_in = jnp.where(is_last, dy_prev, cot_recv)
            read = jnp.mod(t - lag, R)
            sel = [jax.lax.dynamic_index_in_dim(r, read, keepdims=False)
                   for r in ring]
            vjp_sel = jax.tree_util.tree_unflatten(vjp_treedef, sel)
            dstage_t, dx_t = vjp_sel(cot_in)
            d_stage = _tree_add(
                d_stage,
                _tree_where(b_valid, dstage_t, _tree_zeros(dstage_t)))
            # stage-0 backward emits the input cotangent for microbatch mb
            mb_c = jnp.clip(mb, 0, M - 1)
            upd = jnp.where(b_valid & is_first, dx_t,
                            jax.lax.dynamic_index_in_dim(
                                dh_all, mb_c, keepdims=False))
            dh_all = jax.lax.dynamic_update_index_in_dim(
                dh_all, upd, mb_c, axis=0)

            # ---------------- rotate ----------------
            x_recv = jax.lax.ppermute(y, pp_axis, fperm)
            cot_recv = jax.lax.ppermute(dx_t, pp_axis, bperm)
            dy_prev = dy_scaled

        return (loss_acc[None], jax.tree_util.tree_map(
            lambda a: a[None], d_stage), jax.tree_util.tree_map(
            lambda a: a[None], d_head), dh_all[None])

    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(pp_axis),
                                         stacked_params), P(), P(), P()),
        out_specs=(P(pp_axis),
                   jax.tree_util.tree_map(lambda _: P(pp_axis),
                                          stacked_params),
                   jax.tree_util.tree_map(lambda _: P(pp_axis),
                                          head_params),
                   P(pp_axis)),
        axis_names=frozenset({pp_axis}), check_vma=False)

    loss_st, d_stacked, d_head_st, dh_all_st = shard(
        stacked_params, head_params, h_all, labels_all)
    mean_loss = loss_st[Pn - 1] / M
    d_head = jax.tree_util.tree_map(lambda a: a[Pn - 1], d_head_st)
    dh_all = dh_all_st[0]
    d_head = jax.tree_util.tree_map(lambda a: a / M, d_head)
    return mean_loss, d_stacked, d_head, dh_all


def pipeline_interleaved_1f1b_grads(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    head_loss_fn: Callable[[Any, jnp.ndarray, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    head_params: Any,
    h_all: jnp.ndarray,
    labels_all: jnp.ndarray,
    *,
    mesh,
    num_stages: int,
    num_virtual: int,
    pp_axis: str = "pp",
) -> Tuple[jnp.ndarray, Any, Any, jnp.ndarray]:
    """Interleaved (VPP) 1F1B: v virtual chunks per device, ONE chunk-F
    and ONE chunk-B per macro-tick, in one compiled XLA program.

    The reference's ``PipelineParallelWithInterleave``
    (fleet/meta_parallel/pipeline_parallel.py:906) runs 1F1B over virtual
    stages to cut the pipeline bubble ~v×. Here the schedule is the
    uniform-clock generalization of ``pipeline_1f1b_grads``: with
    m = g*P + r and virtual stage k = j*P + p (chunk j of device p, the
    reference's chunk-c-on-device-c-mod-P placement),

      F of (m, k)  @ tick  g*v*P + j*P + r + p
      B of (m, k)  @ tick  v*P + g*v*P + j'*P + r + p'   (mirror:
                    j' = v-1-j, p' = P-1-p)

    For v=1 these reduce exactly to the plain engine's ``m + s`` and
    ``m + 2P-1-s``. Each device executes exactly one F and one B slot
    per tick (the (g, j, r) mixed-radix decomposition of ``t - p`` is a
    bijection), residuals live in a ring of depth R = 2*v*P (the VPP
    live-set bound), and total ticks are

      T = v*P + g_max*v*P + (v-1)*P + (M-1 - g_max*P) + P
        ≈ M*v + v*P + P - 1   chunk-slots,

    strictly fewer compute-units than plain 1F1B over v-chunk stages
    (v*(M + 2P - 1)) for every v > 1 — the bubble shrink that defines
    VPP.

    Args/returns match ``pipeline_1f1b_grads`` except ``stacked_params``
    leaves are [v*P, ...] with row p*v + j holding chunk k = j*P + p
    (device-major layout so P(pp_axis) sharding puts each device's v
    chunks local), and ``num_virtual`` is v.
    """
    M = int(h_all.shape[0])
    Pn = int(num_stages)
    v = int(num_virtual)
    L = v * Pn
    R = 2 * L                     # residual ring depth — the VPP bound
    delta = L                     # B-schedule offset (mirror alignment)
    g_max = (M - 1) // Pn
    T = (delta + g_max * L + (v - 1) * Pn
         + (M - 1 - g_max * Pn) + (Pn - 1) + 1)

    stage_vjp = _stable_vjp(stage_fn)

    def body(stacked, head_p, h_all_, labels_):
        # local chunk params: [v, ...]; slot j = virtual stage j*P + s
        s = jax.lax.axis_index(pp_axis)
        is_first = s == 0
        is_last = s == Pn - 1
        p_mirror = Pn - 1 - s

        d_stacked = _tree_zeros(stacked)      # [v, ...] accumulators
        d_head = _tree_zeros(head_p)
        loss_acc = jnp.zeros((), jnp.float32)
        dh_all = jnp.zeros_like(h_all_)

        ring = None
        vjp_treedef = None
        x_recv = jnp.zeros_like(h_all_[0])
        cot_recv = jnp.zeros_like(h_all_[0])
        dy_prev = jnp.zeros_like(h_all_[0])
        fperm = ring_perm(Pn, 1)
        bperm = ring_perm(Pn, -1)

        def _decomp(u):
            """mixed-radix (g, j, r) of u = g*v*P + j*P + r."""
            r = jnp.mod(u, Pn)
            q = jnp.floor_divide(u, Pn)
            j = jnp.mod(q, v)
            g = jnp.floor_divide(q, v)
            return g, j, r

        for t in range(T):
            # ---------------- forward tick ----------------
            uf = t - s
            gf, jf, rf = _decomp(uf)
            mf = gf * Pn + rf
            f_valid = (uf >= 0) & (mf >= 0) & (mf < M)
            mf_c = jnp.clip(mf, 0, M - 1)
            jf_c = jnp.clip(jf, 0, v - 1)
            chunk_p = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, jf_c, keepdims=False), stacked)
            x_src = jax.lax.dynamic_index_in_dim(h_all_, mf_c,
                                                 keepdims=False)
            x_in = jnp.where(is_first & (jf_c == 0), x_src, x_recv)
            y, vjp_f = stage_vjp(chunk_p, x_in)
            leaves, td = jax.tree_util.tree_flatten(vjp_f)
            if ring is None:
                vjp_treedef = td
                ring = [jnp.zeros((R,) + l.shape, l.dtype) for l in leaves]
            ring = [r_.at[t % R].set(l) for r_, l in zip(ring, leaves)]

            # last virtual stage (j = v-1 on the last device): head+loss
            lbl = jax.lax.dynamic_index_in_dim(labels_, mf_c,
                                               keepdims=False)
            (loss_m, (dhp_m, dy_m)) = _head_value_and_grad(
                head_loss_fn, head_p, y, lbl)
            use_head = f_valid & is_last & (jf_c == v - 1)
            loss_acc = loss_acc + jnp.where(use_head, loss_m, 0.0)
            d_head = _tree_add(
                d_head, _tree_where(use_head, dhp_m, _tree_zeros(dhp_m)))
            dy_scaled = jax.tree_util.tree_map(lambda a: a / M, dy_m)

            # ---------------- backward tick ----------------
            ub = t - delta - p_mirror
            gb, jpb, rb = _decomp(ub)
            jb = v - 1 - jpb
            mb = gb * Pn + rb
            b_valid = (ub >= 0) & (mb >= 0) & (mb < M)
            mb_c = jnp.clip(mb, 0, M - 1)
            jb_c = jnp.clip(jb, 0, v - 1)
            # residual written at this (m, k)'s F tick
            tau_f = gb * L + jb_c * Pn + rb + s
            read = jnp.mod(jnp.clip(tau_f, 0, None), R)
            sel = [jax.lax.dynamic_index_in_dim(r_, read, keepdims=False)
                   for r_ in ring]
            vjp_sel = jax.tree_util.tree_unflatten(vjp_treedef, sel)
            cot_in = jnp.where(is_last & (jb_c == v - 1), dy_prev,
                               cot_recv)
            dchunk_t, dx_t = vjp_sel(cot_in)
            d_stacked = jax.tree_util.tree_map(
                lambda acc, g_: acc.at[jb_c].add(
                    jnp.where(b_valid, g_, jnp.zeros_like(g_))),
                d_stacked, dchunk_t)
            # virtual stage 0 backward emits the h_all cotangent
            upd = jnp.where(b_valid & is_first & (jb_c == 0), dx_t,
                            jax.lax.dynamic_index_in_dim(
                                dh_all, mb_c, keepdims=False))
            dh_all = jax.lax.dynamic_update_index_in_dim(
                dh_all, upd, mb_c, axis=0)

            # ---------------- rotate ----------------
            x_recv = jax.lax.ppermute(y, pp_axis, fperm)
            cot_recv = jax.lax.ppermute(dx_t, pp_axis, bperm)
            dy_prev = dy_scaled

        # d_stacked is local [v, ...] — concatenation over pp rebuilds
        # the global [v*P, ...] device-major layout directly
        return (loss_acc[None], d_stacked, jax.tree_util.tree_map(
            lambda a: a[None], d_head), dh_all[None])

    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(pp_axis),
                                         stacked_params), P(), P(), P()),
        out_specs=(P(pp_axis),
                   jax.tree_util.tree_map(lambda _: P(pp_axis),
                                          stacked_params),
                   jax.tree_util.tree_map(lambda _: P(pp_axis),
                                          head_params),
                   P(pp_axis)),
        axis_names=frozenset({pp_axis}), check_vma=False)

    loss_st, d_stacked, d_head_st, dh_all_st = shard(
        stacked_params, head_params, h_all, labels_all)
    mean_loss = loss_st[Pn - 1] / M
    d_head = jax.tree_util.tree_map(lambda a: a[Pn - 1], d_head_st)
    dh_all = dh_all_st[0]
    d_head = jax.tree_util.tree_map(lambda a: a / M, d_head)
    return mean_loss, d_stacked, d_head, dh_all


def interleaved_tick_count(M: int, num_stages: int, num_virtual: int) -> int:
    """Macro-tick count of the interleaved engine (1 chunk-F + 1 chunk-B
    per tick). For the bubble comparison: plain 1F1B over v-chunk stages
    costs (M + 2P - 1) ticks of v chunk-units each."""
    Pn, v = int(num_stages), int(num_virtual)
    L = v * Pn
    g_max = (M - 1) // Pn
    return (L + g_max * L + (v - 1) * Pn
            + (M - 1 - g_max * Pn) + (Pn - 1) + 1)


def _head_value_and_grad(head_loss_fn, head_p, y, lbl):
    loss_m, vjp_h = jax.vjp(
        lambda hp, yy: head_loss_fn(hp, yy, lbl), head_p, y)
    dhp_m, dy_m = vjp_h(jnp.ones_like(loss_m))
    # head grad contributes 1/M of the mean-over-microbatches loss;
    # the caller divides the accumulated d_head once at the end
    return loss_m, (dhp_m, dy_m)


def circular_pipeline_fwd(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    h_all: jnp.ndarray,
    *,
    mesh,
    num_stages: int,
    num_virtual: int = 1,
    pp_axis: str = "pp",
    remat: bool = True,
) -> jnp.ndarray:
    """Differentiable circular pipeline forward (interleave topology).

    ``stacked_params`` leaves are [v*P, ...] — chunk c lives on device
    c mod P (reference interleave placement, pipeline_parallel.py:906);
    locally each device holds its v chunks stacked on axis 0.
    Returns y_all [M, ...] — the last virtual stage's outputs.
    Backward (via jax.grad) generates the mirrored reverse pipeline.
    """
    M = int(h_all.shape[0])
    Pn = int(num_stages)
    v = int(num_virtual)
    L = v * Pn
    T = M + L - 1
    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    fperm = ring_perm(Pn, 1)

    def body(stacked, h_all_):
        # local chunk params: [v, ...] (global [v*P] split: chunk c on
        # device c mod P ⇒ local slot j holds global chunk j*P + p)
        s = jax.lax.axis_index(pp_axis)
        is_first = s == 0
        is_last = s == Pn - 1

        recv = [jnp.zeros_like(h_all_[0]) for _ in range(v)]
        y_all = jnp.zeros((M,) + h_all_.shape[1:], h_all_.dtype)

        for t in range(T):
            outs = []
            for j in range(v):
                chunk_p = jax.tree_util.tree_map(lambda a, j=j: a[j],
                                                 stacked)
                if j == 0:
                    x_in = jnp.where(is_first,
                                     h_all_[jnp.clip(t, 0, M - 1)],
                                     recv[0])
                else:
                    # wrap: device 0 slot j consumes device P-1 slot j-1
                    x_in = jnp.where(is_first, recv[j - 1], recv[j])
                    # non-first devices consume same-slot neighbor output
                y = fn(chunk_p, x_in)
                outs.append(y)
            # collect finished microbatches at the last device, last slot:
            # chunk L-1 finishes m at t = m + L - 1
            m_out = t - (L - 1)
            m_c = jnp.clip(m_out, 0, M - 1)
            valid = (m_out >= 0) & (m_out < M)
            upd = jnp.where(valid & is_last, outs[v - 1],
                            jax.lax.dynamic_index_in_dim(
                                y_all, m_c, keepdims=False))
            y_all = jax.lax.dynamic_update_index_in_dim(
                y_all, upd, m_c, axis=0)
            recv = [jax.lax.ppermute(o, pp_axis, fperm) for o in outs]

        return y_all[None]

    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(
            lambda _: P(pp_axis), stacked_params), P()),
        out_specs=P(pp_axis),
        axis_names=frozenset({pp_axis}), check_vma=False)
    y_st = shard(stacked_params, h_all)
    return y_st[Pn - 1]
