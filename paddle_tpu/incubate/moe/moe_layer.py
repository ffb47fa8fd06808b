"""Mixture-of-Experts layer.

TPU-native equivalent of the reference's MoE (reference:
python/paddle/incubate/distributed/models/moe/moe_layer.py:263 MoELayer,
gates gshard_gate.py/switch_gate.py/naive_gate.py; expert-parallel
dispatch via global_scatter/global_gather all-to-all
fluid/operators/collective/global_scatter_op.cu; cutlass grouped-GEMM
moe_kernel.cu). Two formulations live here:

- **capacity-factor (GShard einsum)**: top-k gate → capacity-bounded
  one-hot dispatch/combine tensors → einsum dispatch → per-expert FFN
  (stacked weights; one batched matmul on the MXU) → einsum combine.
  Over-capacity assignments DROP (counted in ``moe.dropped_tokens``).
- **no-drop ragged (``capacity_factor=None``, ISSUE 15)**: the stacked
  path routes through ``nn.functional.grouped_gemm.moe_ffn_nodrop`` —
  fp32 router → tokens stable-sorted by expert → two ragged grouped
  GEMMs → scatter-combine. ZERO capacity padding, ZERO dropped tokens,
  and no ``[T, E, capacity]`` intermediate anywhere in the trace.

Gate routing (softmax, top-k, top-k renormalization) runs in fp32 on
EVERY path regardless of AMP dtype: bf16 router probs make top-k ties
and the combine normalization unstable (pinned by the bf16-vs-fp32
routing-parity test). Expert parallelism = shard the expert dim of the
stacked weights over the mesh's ep/mp axis; GSPMD emits the all-to-all
the reference launches by hand.
"""
from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer_base import Layer, LayerList
from ...ops.dispatch import as_tensor_args, eager_apply

__all__ = ["MoELayer", "NaiveGate", "GShardGate", "SwitchGate", "ExpertFFN"]


def _count_dropped(drop):
    """Surface capacity-overflow drops on the EAGER path: bump the
    ``moe.dropped_tokens`` stats counter with this forward's dropped
    token->expert assignment count. The count is data-dependent (it
    comes off the device), so it is only fetched while the registry is
    enabled; inside a fully jit-compiled step the counter is not
    updated (the traced body runs once per compile) — silent-drop
    debugging is an eager/profiling activity."""
    from ...profiler import stats as _stats

    if not _stats.is_enabled():
        return
    arr = drop._data if isinstance(drop, Tensor) else drop
    if isinstance(arr, jax.core.Tracer):
        return  # under trace (TrainStep/jit): no per-execution count
    _stats.inc("moe.dropped_tokens", int(float(np.asarray(arr))))


def _stamp_moe_stats(counts):
    """Per-forward routing telemetry on the EAGER path: observe each
    expert's assignment count into the ``moe.tokens_per_expert``
    histogram and stamp the ``moe.imbalance`` gauge (max/mean expert
    load; 1.0 = perfectly balanced). Like ``_count_dropped``, this is
    data-dependent and therefore eager/profiling-only — inside a
    jit-compiled step the traced body runs once per compile."""
    from ...profiler import stats as _stats

    if not _stats.is_enabled():
        return
    arr = counts._data if isinstance(counts, Tensor) else counts
    if isinstance(arr, jax.core.Tracer):
        return
    c = np.asarray(arr, np.float64).reshape(-1)
    if not c.size:
        return
    for v in c:
        _stats.observe("moe.tokens_per_expert", float(v))
    mean = float(c.mean())
    _stats.set_gauge("moe.imbalance",
                     float(c.max()) / mean if mean > 0 else 0.0)


class BaseGate(Layer):
    def __init__(self, d_model: int, num_experts: int, top_k: int):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.top_k = top_k
        self.weight = self.create_parameter(
            shape=[d_model, num_experts],
            default_initializer=I.XavierUniform())


class NaiveGate(BaseGate):
    """top-k softmax gate, no auxiliary loss (naive_gate.py)."""

    aux_loss_weight = 0.0


class GShardGate(BaseGate):
    """GShard gate: top-2 + load-balancing aux loss (gshard_gate.py)."""

    def __init__(self, d_model, num_experts, top_k=2, capacity_factor=1.25):
        super().__init__(d_model, num_experts, top_k)
        self.capacity_factor = capacity_factor
        self.aux_loss_weight = 1e-2


class SwitchGate(BaseGate):
    """Switch Transformer gate: top-1 (switch_gate.py)."""

    def __init__(self, d_model, num_experts, top_k=1, capacity_factor=1.25):
        super().__init__(d_model, num_experts, top_k)
        self.capacity_factor = capacity_factor
        self.aux_loss_weight = 1e-2


class ExpertFFN(Layer):
    """Stacked-expert FFN: weights [E, d, d_ff] / [E, d_ff, d] so the whole
    expert bank is two batched matmuls (the grouped-GEMM form)."""

    def __init__(self, num_experts, d_model, d_hidden, activation="gelu"):
        super().__init__()
        self.w1 = self.create_parameter(
            shape=[num_experts, d_model, d_hidden],
            default_initializer=I.XavierUniform())
        self.b1 = self.create_parameter(
            shape=[num_experts, 1, d_hidden], is_bias=True)
        self.w2 = self.create_parameter(
            shape=[num_experts, d_hidden, d_model],
            default_initializer=I.XavierUniform())
        self.b2 = self.create_parameter(
            shape=[num_experts, 1, d_model], is_bias=True)
        self.activation = activation


class MoELayer(Layer):
    """(moe_layer.py:263 parity, GShard algebra)

    Args follow the reference loosely: ``experts`` may be an ExpertFFN
    (fast stacked path) or a list of per-expert Layers (generic path).
    """

    def __init__(self, d_model: int, experts=None, gate="gshard",
                 num_experts: Optional[int] = None, top_k: int = 2,
                 d_hidden: Optional[int] = None, capacity_factor=1.25,
                 moe_group=None, mp_group=None, recompute_interval=0,
                 ep_mesh=None, name=None):
        super().__init__()
        # ep_mesh=(mesh, axis_name): explicit expert parallelism via the
        # all-to-all dispatch the reference's MoE stack uses (reference:
        # incubate/distributed/models/moe/global_scatter → all-to-all;
        # moe/gate communication in moe_layer.py). Tokens stay sharded on
        # `axis`, experts live sharded on `axis`, and the dispatch /
        # combine are two lax.all_to_all inside a shard_map — O(tokens)
        # comm instead of the dense one-hot partial-sum reduce that the
        # GSPMD lowering of the einsum form produces.
        self._ep_mesh = ep_mesh
        if isinstance(experts, (list, LayerList)):
            if ep_mesh is not None:
                raise ValueError(
                    "ep_mesh expert parallelism needs the stacked "
                    "ExpertFFN form (pass num_experts/d_hidden or an "
                    "ExpertFFN, not a list of per-expert Layers)")
            self.experts = LayerList(list(experts))
            num_experts = len(self.experts)
            self.stacked = None
        else:
            assert num_experts is not None
            self.stacked = experts if isinstance(experts, ExpertFFN) else \
                ExpertFFN(num_experts, d_model,
                          d_hidden or 4 * d_model)
            self.experts = None
        self.num_experts = num_experts
        self.d_model = d_model

        if isinstance(gate, str):
            gate_cls = {"naive": NaiveGate, "gshard": GShardGate,
                        "switch": SwitchGate}[gate]
            if gate_cls is SwitchGate:
                top_k = 1
            self.gate = gate_cls(d_model, num_experts, top_k) \
                if gate_cls is NaiveGate else \
                gate_cls(d_model, num_experts, top_k=top_k,
                         capacity_factor=capacity_factor)
        else:
            self.gate = gate
        self.top_k = self.gate.top_k
        self.capacity_factor = getattr(self.gate, "capacity_factor",
                                       capacity_factor)
        self.aux_loss: Optional[Tensor] = None

    def _ep_forward(self, x):
        """Expert-parallel stacked path: shard_map over the ep axis with
        all-to-all dispatch/combine (see __init__ ep_mesh note)."""
        from functools import partial

        from jax.sharding import PartitionSpec as P

        mesh, axis = self._ep_mesh
        jmesh = mesh.jax_mesh() if hasattr(mesh, "jax_mesh") else mesh
        ep = jmesh.shape[axis]
        E, K, d = self.num_experts, self.top_k, self.d_model
        if E % ep:
            raise ValueError(f"num_experts {E} not divisible by "
                             f"ep degree {ep}")
        orig_shape = x.shape
        # shard_map shards the LEADING dim — that is the divisibility
        # that matters, not the flattened token count
        if orig_shape[0] % ep:
            raise ValueError(f"batch dim {orig_shape[0]} not divisible "
                             f"by ep degree {ep}")
        tokens = int(np.prod(orig_shape[:-1]))
        # capacity is per (expert, shard): receive buffers CONCAT across
        # shards (no cross-shard sum), which is what makes the exchange
        # an all-to-all instead of a reduce. No-drop mode
        # (capacity_factor=None) sizes the buffers for the worst case
        # (every local assignment to one expert) so nothing can drop.
        if self.capacity_factor is None:
            capacity = max((tokens // ep) * K, 1)
        else:
            capacity = max(int(math.ceil((tokens // ep) * K *
                                         self.capacity_factor / E)), 1)
        st = self.stacked
        act = jax.nn.gelu if st.activation == "gelu" else jax.nn.relu
        aux_w = getattr(self.gate, "aux_loss_weight", 0.0)
        nd = len(orig_shape)
        x_spec = P(*([axis] + [None] * (nd - 1)))
        w_spec = P(axis)

        def raw(xa, wg, w1, b1, w2, b2):
            def body(x_loc, wg_, w1_loc, b1_loc, w2_loc, b2_loc):
                xt = x_loc.reshape(-1, d)
                # tpu-lint: ok(X-PROMOTE) -- fp32 gate routing by design
                probs = jax.nn.softmax(
                    xt.astype(jnp.float32) @ wg_.astype(jnp.float32),
                    -1)
                combine, dispatch, aux, drop, cnt = _gshard_dispatch(
                    probs, E, K, capacity)
                combine = combine.astype(xt.dtype)
                dispatch = dispatch.astype(xt.dtype)
                exp_in = jnp.einsum("tec,td->ecd", dispatch, xt)
                # [E, c, d] -> [E/ep, ep*c, d]: rows for MY experts from
                # every shard land here, capacities concatenated
                recv = jax.lax.all_to_all(exp_in, axis, split_axis=0,
                                          concat_axis=1, tiled=True)
                h = act(jnp.einsum("ecd,edf->ecf", recv, w1_loc) + b1_loc)
                out = jnp.einsum("ecf,efd->ecd", h, w2_loc) + b2_loc
                # reverse exchange: [E/ep, ep*c, d] -> [E, c, d]
                back = jax.lax.all_to_all(out, axis, split_axis=1,
                                          concat_axis=0, tiled=True)
                y = jnp.einsum("tec,ecd->td", combine, back)
                return (y.reshape(x_loc.shape),
                        jax.lax.pmean(aux, axis),
                        jax.lax.psum(drop, axis),
                        jax.lax.psum(cnt, axis))

            y, aux, drop, cnt = jax.shard_map(
                body, mesh=jmesh,
                in_specs=(x_spec, P(), w_spec, w_spec, w_spec, w_spec),
                out_specs=(x_spec, P(), P(), P()))(xa, wg, w1, b1, w2,
                                                   b2)
            # zero-weight edge tying aux into the differentiated
            # output: when a whole-step AD (TrainStep) never consumes
            # aux, shard_map's transpose would otherwise receive a
            # symbolic-Zero cotangent for it and psum can't transpose
            # that (drop is int32 — non-differentiable by dtype — so
            # it needs no edge); XLA folds the multiply away
            y = y + (jnp.zeros((), y.dtype) * aux.astype(y.dtype))
            return y, aux, drop, cnt

        tensors = as_tensor_args(x, self.gate.weight, st.w1, st.b1,
                                 st.w2, st.b2)
        out, aux, drop, cnt = eager_apply("moe_layer_ep", raw, tensors,
                                          n_outputs=4)
        self.aux_loss = aux * aux_w if aux_w else aux
        _count_dropped(drop)
        _stamp_moe_stats(cnt)
        return out

    def _nodrop_forward(self, x):
        """No-drop stacked path (``capacity_factor=None``): fp32 router
        → stable sort by expert → ragged grouped-GEMM FFN →
        scatter-combine. Zero capacity padding, zero drops, no
        ``[T, E, C]`` intermediate in the traced program."""
        from ...core.flags import flag
        from ...nn.functional.grouped_gemm import moe_ffn_nodrop

        orig_shape = x.shape
        d = self.d_model
        tokens = int(np.prod(orig_shape[:-1]))
        E, K = self.num_experts, self.top_k
        aux_w = getattr(self.gate, "aux_loss_weight", 0.0)
        st = self.stacked
        act = st.activation
        backend = flag("moe_grouped_backend")
        tensors = as_tensor_args(x, self.gate.weight, st.w1, st.b1,
                                 st.w2, st.b2)

        def raw(xa, wg, w1, b1, w2, b2):
            xt = xa.reshape(tokens, d)
            y, probs, topk_idx, counts = moe_ffn_nodrop(
                xt, wg, w1, b1.reshape(E, -1), w2, b2.reshape(E, -1),
                top_k=K, activation=act, backend=backend)
            # load-balance aux loss: the same GShard formula as the
            # capacity path (fp32 probs)
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(jax.nn.one_hot(topk_idx[:, 0], E,
                                         dtype=probs.dtype), axis=0)
            aux = jnp.sum(me * ce) * E
            return y.reshape(xa.shape), aux, counts

        out, aux, cnt = eager_apply("moe_layer_nodrop", raw, tensors,
                                    n_outputs=3)
        self.aux_loss = aux * aux_w if aux_w else aux
        # no-drop by construction — the counter moves by exactly 0, so
        # drop-rate dashboards see the mode switch, not a gap
        _count_dropped(jnp.zeros((), jnp.int32))
        _stamp_moe_stats(cnt)
        return out

    def forward(self, x):
        orig_shape = x.shape
        d = self.d_model
        tokens = int(np.prod(orig_shape[:-1]))
        E, K = self.num_experts, self.top_k
        if self.capacity_factor is None and self._ep_mesh is None:
            if self.stacked is None:
                raise ValueError(
                    "no-drop MoE (capacity_factor=None) needs the "
                    "stacked ExpertFFN form — heterogeneous per-expert "
                    "Layers still route through the capacity-bounded "
                    "dispatch")
            return self._nodrop_forward(x)
        capacity = None if self.capacity_factor is None else max(
            int(math.ceil(tokens * K * self.capacity_factor / E)), 1)
        aux_w = getattr(self.gate, "aux_loss_weight", 0.0)

        if self._ep_mesh is not None and self.stacked is not None:
            return self._ep_forward(x)

        if self.stacked is not None:
            st = self.stacked
            act = st.activation
            tensors = as_tensor_args(x, self.gate.weight, st.w1, st.b1,
                                     st.w2, st.b2)

            def raw(xa, wg, w1, b1, w2, b2):
                xt = xa.reshape(tokens, d)
                # tpu-lint: ok(X-PROMOTE) -- fp32 gate routing by design
                logits = xt.astype(jnp.float32) \
                    @ wg.astype(jnp.float32)                   # [T, E]
                probs = jax.nn.softmax(logits, -1)
                combine, dispatch, aux, drop, cnt = _gshard_dispatch(
                    probs, E, K, capacity)
                combine = combine.astype(xt.dtype)
                dispatch = dispatch.astype(xt.dtype)
                # dispatch: [T, E, C] → expert inputs [E, C, d]
                exp_in = jnp.einsum("tec,td->ecd", dispatch, xt)
                h = exp_in @ w1 + b1                           # [E, C, ff]
                h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
                exp_out = h @ w2 + b2                          # [E, C, d]
                out = jnp.einsum("tec,ecd->td", combine, exp_out)
                return out.reshape(xa.shape), aux, drop, cnt

            out, aux, drop, cnt = eager_apply("moe_layer", raw, tensors,
                                              n_outputs=4)
            self.aux_loss = aux * aux_w if aux_w else aux
            _count_dropped(drop)
            _stamp_moe_stats(cnt)
            return out

        # generic per-expert path (heterogeneous experts); gate grads flow
        # through the combine weights produced by the dispatch op
        xt = x.reshape([tokens, d])

        def raw_dispatch(xa, wg):
            # tpu-lint: ok(X-PROMOTE) -- fp32 gate routing by design
            logits = xa.astype(jnp.float32) @ wg.astype(jnp.float32)
            probs = jax.nn.softmax(logits, -1)
            combine, dispatch, aux, drop, cnt = _gshard_dispatch(
                probs, E, K, capacity)
            combine = combine.astype(xa.dtype)
            dispatch = dispatch.astype(xa.dtype)
            exp_in = jnp.einsum("tec,td->ecd", dispatch, xa)
            return exp_in, combine, aux, drop, cnt

        exp_in_all, combine_t, aux, drop, cnt = eager_apply(
            "moe_dispatch", raw_dispatch,
            as_tensor_args(xt, self.gate.weight), n_outputs=5)
        _count_dropped(drop)
        _stamp_moe_stats(cnt)
        outs = []
        for e, expert in enumerate(self.experts):
            outs.append(expert(exp_in_all[e]))
        import paddle_tpu as paddle

        exp_out = paddle.stack(outs, axis=0)
        out = eager_apply(
            "moe_combine",
            lambda c, eo: jnp.einsum("tec,ecd->td", c, eo),
            as_tensor_args(combine_t, exp_out))
        self.aux_loss = aux * aux_w if aux_w else aux
        return out.reshape(orig_shape)


def _gshard_dispatch(probs, E, K, capacity):
    """GShard top-K dispatch with capacity (pure jnp; differentiable
    through the combine weights).

    Returns (combine, dispatch, aux, dropped, counts): ``dropped``
    (int32 scalar) is the number of token->expert assignments discarded
    by the capacity bound this batch, counted exactly per top-k pass —
    the eager MoELayer forward surfaces it as the
    ``moe.dropped_tokens`` stats counter so capacity-overflow drops
    are observable instead of silent. ``counts`` (int32 [E]) is the
    per-expert ROUTED assignment count (before the capacity bound) —
    the ``moe.tokens_per_expert`` / ``moe.imbalance`` telemetry."""
    T = probs.shape[0]
    topk_val, topk_idx = jax.lax.top_k(probs, K)              # [T, K]
    # normalize selected probabilities
    topk_val = topk_val / jnp.sum(topk_val, -1, keepdims=True)

    combine = jnp.zeros((T, E, capacity), probs.dtype)
    dispatch = jnp.zeros((T, E, capacity), probs.dtype)
    # running per-expert slot base across the K passes: k=0 assignments
    # claim the leading slots, k=1 continues after them (GShard's
    # priority ordering) — WITHOUT this, pass k's counts restart at 0
    # and two different tokens share a slot, so the expert sees the SUM
    # of their activations (r5 fix; pinned by the identity-property test)
    # slot bookkeeping runs in fp32 regardless of probs.dtype: under AMP
    # O2 probs are bf16, which represents integers exactly only up to
    # 256 — a bf16 cumsum over more tokens rounds increments away and
    # two tokens silently share a slot (the exact corruption the `base`
    # fix prevents)
    base = jnp.zeros((E,), jnp.float32)
    dropped = jnp.zeros((), jnp.int32)
    for k in range(K):
        idx = topk_idx[:, k]                                  # [T]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)    # [T, E]
        # position within expert buffer (running count per expert)
        pos_in_e = (jnp.cumsum(onehot, axis=0) - 1
                    + base[None, :]) * onehot                 # [T, E]
        pos = jnp.sum(pos_in_e, axis=-1).astype(jnp.int32)    # [T]
        keep = pos < capacity
        dropped = dropped + (T - jnp.sum(keep.astype(jnp.int32)))
        pos_cap = jnp.clip(pos, 0, capacity - 1)
        cap_onehot = jax.nn.one_hot(pos_cap, capacity,
                                    dtype=probs.dtype)        # [T, C]
        mask = (onehot.astype(probs.dtype)
                * keep[:, None].astype(probs.dtype))
        disp_k = mask[:, :, None] * cap_onehot[:, None, :]    # [T, E, C]
        dispatch = dispatch + disp_k
        combine = combine + disp_k * topk_val[:, k][:, None, None]
        base = base + jnp.sum(onehot, axis=0)

    # load-balance aux loss (gshard): E * sum_e(frac_tokens_e * mean_prob_e)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(topk_idx[:, 0], E, dtype=probs.dtype), axis=0)
    aux = jnp.sum(me * ce) * E
    # int32 on purpose: exact under AMP (a bf16 dispatch.sum() rounds
    # past 256), and non-differentiable by dtype so the ep path's
    # shard_map psum never sees a symbolic-zero cotangent for it
    return combine, dispatch, aux, dropped, base.astype(jnp.int32)
