"""Eager op dispatch.

TPU-native equivalent of the reference's generated eager AD functions +
PHI dispatch (reference: the per-op ``*_ad_func`` emitted by
paddle/fluid/eager/auto_code_generator/generator/eager_gen.py and kernel
selection in paddle/phi/api/lib/kernel_dispatch.h:100).

Where the reference's codegen emits, per op, (forward call + GradNode
creation + saved TensorWrappers), we get the same artifact generically:
``eager_apply`` runs the op's functional jnp implementation under
``jax.vjp`` when any input requires grad, records a GradNode with the vjp
closure (JAX traces the backward — the GradNode *is* the saved-tensor
wrapper, closed over immutable arrays), and wires edges to producers.

Ops never hand-write gradients; XLA differentiates the same code that runs
forward, which is the single-source-of-truth property the reference gets
from ops.yaml + backward.yaml.
"""
from __future__ import annotations

import sys
import warnings
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core import engine
from ..core.flags import flag
from ..core.tensor import Tensor
from ..profiler import stats as _stats
from ..profiler.profiler import _SPANS, RecordEvent

__all__ = ["eager_apply", "as_tensor_args", "defun", "inplace_apply"]

# The compiled-forward fast path donates in-place op buffers; CPU jaxlib
# has no donation support and warns per compiled function — silence it
# (donation there is simply a no-op, results are unaffected).
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

# per-op call counters, cached so the hot dispatch path pays one dict
# lookup (not a registry lock) per call; cache outcome counters are
# module-bound for the same reason
_OP_COUNTERS: Dict[str, Any] = {}
_C_HIT = _stats.counter("vjp_cache.hit")
_C_MISS = _stats.counter("vjp_cache.miss")
_C_ADMIT = _stats.counter("vjp_cache.admit")
_C_BLOCKLISTED = _stats.counter("vjp_cache.blocklisted")
_C_BLOCKED = _stats.counter("vjp_cache.blocked")
_C_UNCACHEABLE = _stats.counter("vjp_cache.uncacheable")
_F_HIT = _stats.counter("fwd_cache.hit")
_F_MISS = _stats.counter("fwd_cache.miss")
_F_ADMIT = _stats.counter("fwd_cache.admit")
_F_BLOCKLISTED = _stats.counter("fwd_cache.blocklisted")
_F_BLOCKED = _stats.counter("fwd_cache.blocked")
_F_UNCACHEABLE = _stats.counter("fwd_cache.uncacheable")

#: trace-time errors that mean "this op's python body needs concrete
#: values" — such signatures are blocklisted once and permanently fall
#: back to the plain eager path
_TRACE_ERRS = (jax.errors.JAXTypeError, jax.errors.UnexpectedTracerError)


def _op_counter(op_name: str):
    c = _OP_COUNTERS.get(op_name)
    if c is None:
        c = _OP_COUNTERS[op_name] = _stats.counter("op." + op_name)
    return c


# ---------------------------------------------------------------------------
# Taped-backward vjp cache.
#
# ``jax.vjp`` retraces the op on every tape-recorded call (~750µs/op in an
# earlier round's chip run), which eager ``backward()`` training pays per
# op per step. The reference amortizes this with codegen'd GradNodes
# (eager_gen.py); we amortize it by jitting the (primals, residuals) forward
# and the residual->cotangent backward once per (op, static kwargs, input
# avals) — the same aval-keyed trick that fixed eager flash-attention
# forwards in r4. Residuals cross the jit boundary as flattened leaves (the
# VJP pytree's treedef is cached host-side; hashing it per call is what made
# the naive "return the VJP object" scheme slow).
#
# Admission: an entry is built only for a ``raw_fn`` OBJECT seen at least
# twice (weakref sighting). Per-call closures — dropout's fresh mask,
# gumbel's noise — get a fresh function object every call, so they are never
# admitted, which is also what makes caching them SAFE to skip: their closed-
# over randomness must not be baked into a compiled trace. Ops whose trace
# needs concrete values (TracerBool/Concretization errors under jit) are
# blocklisted on first failure and permanently fall back to plain jax.vjp.
# ---------------------------------------------------------------------------

class _CachedVJP:
    __slots__ = ("fwd", "bwd", "box", "raw_fn")

    def __init__(self, op_name, raw_fn, static_kwargs, n_args, diff_idx):
        self.raw_fn = raw_fn  # strong ref: pins id() while entry lives
        self.box = box = {}
        const_idx = [i for i in range(n_args) if i not in set(diff_idx)]
        from jax import tree_util as jtu

        def fwd(*arrays):
            cmap = {i: arrays[i] for i in const_idx}

            def f(*diff):
                full = _interleave(cmap, n_args, diff)
                out = raw_fn(*full, **static_kwargs)
                box["was_tuple"] = isinstance(out, tuple)
                return out if isinstance(out, tuple) else (out,)

            primals, vf = jax.vjp(f, *(arrays[i] for i in diff_idx))
            leaves, td = jtu.tree_flatten(vf)
            box["td"], box["n_out"] = td, len(primals)
            box["n_res"] = len(leaves)
            return tuple(primals) + tuple(leaves)

        def bwd(*args):
            vf = jtu.tree_unflatten(box["td"], list(args[:box["n_res"]]))
            return tuple(vf(tuple(args[box["n_res"]:])))

        self.fwd = jax.jit(fwd)
        self.bwd = jax.jit(bwd)


_VJP_CACHE: "OrderedDict[tuple, _CachedVJP]" = OrderedDict()
_VJP_CACHE_MAX = 1024
_VJP_BLOCK: set = set()          # keys whose trace needs concrete values


class _AdmissionTracker:
    """Seen-twice admission discipline, shared by the VJP and the
    compiled-forward caches.

    A cache entry is only built for a signature key whose ``raw_fn``
    OBJECT has been sighted before under the same key. Per-call closures
    (dropout's fresh mask, gumbel's noise) get a fresh function object
    every call, so they are never admitted — which is also what makes
    skipping them SAFE: their closed-over randomness must never be baked
    into a compiled trace. Keying sightings by the FULL signature (not
    just the function) additionally means an op called with a per-step
    varying static scalar never triggers a compile storm: each distinct
    value must be seen twice before anything is traced.

    The value stored is a weakref to ``raw_fn`` whose callback purges the
    entry when the referent dies. This fixes the latent id-reuse bug of
    the old id-keyed dict: without the purge, a recycled ``id()`` could
    inherit a stale sighting and falsely admit a per-call closure.
    """

    __slots__ = ("_seen", "_max")

    def __init__(self, max_entries: int = 8192):
        self._seen: Dict[Any, Any] = {}
        self._max = max_entries

    def admit(self, key, raw_fn) -> bool:
        """True when (key, raw_fn) was already sighted — build the entry
        now. False records the sighting (first time, or a different
        object under the same key)."""
        ref = self._seen.get(key)
        if ref is not None and ref() is raw_fn:
            return True
        if len(self._seen) >= self._max:
            # drop dead refs first; if genuinely full, evict oldest
            dead = [k for k, r in self._seen.items() if r() is None]
            for k in dead:
                self._seen.pop(k, None)
            while len(self._seen) >= self._max:
                self._seen.pop(next(iter(self._seen)), None)
        seen = self._seen

        def _purge(r, _seen=seen, _key=key):
            if _seen.get(_key) is r:
                _seen.pop(_key, None)

        self._seen[key] = weakref.ref(raw_fn, _purge)
        return False

    def clear(self) -> None:
        self._seen.clear()

    def __len__(self) -> int:
        return len(self._seen)


_VJP_SEEN = _AdmissionTracker()   # taped-path sightings
_FWD_SEEN = _AdmissionTracker()   # no-grad-path sightings

_STATIC_OK_TYPES = (str, bytes, int, float, bool, type(None), np.dtype,
                    np.generic)

try:  # slice objects are only hashable from python 3.12
    hash(slice(None))
    _SLICE_HASHABLE = True
except TypeError:
    _SLICE_HASHABLE = False


def _static_ok(v) -> bool:
    """Is a static-kwarg value safe to bake into a compiled trace?
    Conservative allowlist: plain immutable scalars/strings, dtypes,
    (nested) tuples and slices thereof. Tensors/arrays are rejected even
    though they hash by identity — baking their VALUES into a jitted
    executable would silently freeze them."""
    if isinstance(v, _STATIC_OK_TYPES) or isinstance(v, type):
        return True
    if isinstance(v, tuple):
        return all(_static_ok(x) for x in v)
    if isinstance(v, slice):
        return (_SLICE_HASHABLE and _static_ok(v.start)
                and _static_ok(v.stop) and _static_ok(v.step))
    return False


def _sig_key(raw_fn, static_kwargs, arrays, extra):
    """Hashable signature key ``(raw_fn identity, static kwargs, input
    avals incl. weak_type, extra)``, or None when a static kwarg is not
    safely bakeable (arrays, lists, Tensors) — those calls use the plain
    path. ``extra`` discriminates cache flavors (diff_idx for the VJP
    cache, the donation mask for the forward cache)."""
    for v in static_kwargs.values():
        if not _static_ok(v):
            return None
    skey = tuple(sorted(static_kwargs.items()))
    avals = tuple(
        (a.shape, str(a.dtype), bool(getattr(a, "weak_type", False)))
        for a in arrays)
    return (id(raw_fn), skey, avals, extra)


def _vjp_cache_key(raw_fn, static_kwargs, arrays, diff_idx):
    return _sig_key(raw_fn, static_kwargs, arrays, tuple(diff_idx))


def _vjp_cache_admit(key, op_name, raw_fn, static_kwargs, n_args,
                     diff_idx):
    """After a successful uncached call: build an entry on the second
    sighting of the same (key, raw_fn object) pair."""
    if not _VJP_SEEN.admit(key, raw_fn):
        return
    _C_ADMIT.inc()
    with _stats.timed("compile.vjp_build_us"):
        _VJP_CACHE[key] = _CachedVJP(op_name, raw_fn, static_kwargs,
                                     n_args, diff_idx)
    while len(_VJP_CACHE) > _VJP_CACHE_MAX:
        _VJP_CACHE.popitem(last=False)


# ---------------------------------------------------------------------------
# Compiled-forward fast path (no-grad dispatch).
#
# Inference mode, the ContinuousBatchingEngine host loop, and every
# ``no_grad`` region used to pay primitive-by-primitive dispatch for
# composite ops: OPBENCH r05 measured eager ``gelu`` at 378µs vs 24.8µs
# jitted, ``cross_entropy`` 1378.9µs vs 25.5µs. The reference amortizes
# this with codegen'd PHI kernels per op (eager_gen.py +
# kernel_dispatch.h); we amortize it the same way the taped path does —
# a jit-compiled executable per (raw_fn identity, static kwargs, input
# avals), admitted under the shared seen-twice discipline and LRU
# bounded. In-place ops (``*_`` family) additionally DONATE the target
# buffer so steady-state eager inference stops double-buffering; a
# refcount guard skips donation whenever anything else aliases the
# buffer, so the aliasing is never visible to callers.
# ---------------------------------------------------------------------------

_FWD_CACHE: "OrderedDict[tuple, _CachedFwd]" = OrderedDict()
_FWD_CACHE_MAX = 1024
_FWD_BLOCK: set = set()          # keys whose trace needs concrete values


class _CachedFwd:
    __slots__ = ("fn", "box", "raw_fn")

    def __init__(self, raw_fn, static_kwargs, donate):
        self.raw_fn = raw_fn  # strong ref: pins id() while entry lives
        self.box = box = {}

        def call(*arrays):
            out = raw_fn(*arrays, **static_kwargs)
            box["was_tuple"] = isinstance(out, tuple)
            return out if isinstance(out, tuple) else (out,)

        self.fn = jax.jit(call, donate_argnums=donate) if donate \
            else jax.jit(call)


def _donation_safe(arrays, i) -> bool:
    """May ``arrays[i]``'s buffer be donated? Refs visible at this point
    are: the ``arrays`` list, getrefcount's own argument, and — unless
    AMP cast produced a fresh temp — the owning ``Tensor._data``. Any
    count above that is an external alias (``t.detach()``, a saved vjp
    residual, a user variable) whose buffer donation would invalidate."""
    return sys.getrefcount(arrays[i]) <= 3


def _poison_donated(op_name, arrays, eff_donate):
    """FLAGS_check_donation: after a donated dispatch the donated input
    buffers are dead on TPU — register them so any alias that slipped
    the refcount guard fails its next read loudly (CPU jaxlib ignores
    donation, so without this the bug is invisible off-chip)."""
    from ..analysis import donation as _don

    for i in eff_donate:
        _don.poison(arrays[i], op_name)


def _check_poisoned(arrays, reader):
    from ..analysis import donation as _don

    _don.assert_not_poisoned(arrays, reader)


def _forward_fast_path(raw_fn, arrays, static_kwargs, donate_idx,
                       op_name="<op>"):
    """Try the compiled-forward cache for a no-grad dispatch. Returns
    ``(outs, was_tuple)`` when a compiled executable served the call,
    None to fall back to the plain eager path."""
    if not arrays or not flag("eager_fwd_cache"):
        # zero-input programs bake their outputs as constants (measured
        # to degrade dispatch in an earlier round's chip run) — never
        # cache those
        return None
    eff_donate = ()
    if donate_idx:
        eff_donate = tuple(i for i in donate_idx if _donation_safe(arrays, i))
    key = _sig_key(raw_fn, static_kwargs, arrays, eff_donate)
    if key is None:
        _F_UNCACHEABLE.inc()
        _F_MISS.inc()
        return None
    if key in _FWD_BLOCK:
        _F_BLOCKED.inc()
        _F_MISS.inc()
        return None
    entry = _FWD_CACHE.get(key)
    if entry is not None:
        try:
            outs = entry.fn(*arrays)
        except _TRACE_ERRS:
            _F_BLOCKLISTED.inc()
            _F_MISS.inc()
            _FWD_BLOCK.add(key)
            del _FWD_CACHE[key]
            return None
        _F_HIT.inc()
        _FWD_CACHE.move_to_end(key)
        if eff_donate and flag("check_donation"):
            _poison_donated(op_name, arrays, eff_donate)
        return outs, entry.box.get("was_tuple", False)
    if not _FWD_SEEN.admit(key, raw_fn):
        _F_MISS.inc()
        return None
    entry = _CachedFwd(raw_fn, static_kwargs, eff_donate)
    try:
        with _stats.timed("compile.fwd_trace_us"):
            outs = entry.fn(*arrays)
    except _TRACE_ERRS:
        _F_BLOCKLISTED.inc()
        _F_MISS.inc()
        _FWD_BLOCK.add(key)
        return None
    _F_ADMIT.inc()
    _FWD_CACHE[key] = entry
    while len(_FWD_CACHE) > _FWD_CACHE_MAX:
        _FWD_CACHE.popitem(last=False)
    if eff_donate and flag("check_donation"):
        _poison_donated(op_name, arrays, eff_donate)
    return outs, entry.box.get("was_tuple", False)


def _is_diff_dtype(arr) -> bool:
    return jnp.issubdtype(arr.dtype, jnp.inexact)


def _interleave(const_map, n, diff_arrays):
    """Rebuild the full positional array list from constants + the
    differentiable subset (shared by the forward vjp closure and the
    double-grad replay in engine._apply_node)."""
    full, it = [], iter(diff_arrays)
    for i in range(n):
        full.append(const_map[i] if i in const_map else next(it))
    return full


def as_tensor_args(*args) -> List[Tensor]:
    out = []
    for a in args:
        if isinstance(a, Tensor):
            out.append(a)
        else:
            out.append(Tensor(jnp.asarray(a)))
    return out


def _check_finite(op_name: str, arrays) -> None:
    for a in arrays:
        if jnp.issubdtype(a.dtype, jnp.inexact):
            bad = bool(jnp.any(~jnp.isfinite(a)))
            if bad:
                msg = f"NaN/Inf detected in output of op `{op_name}`"
                if flag("check_nan_inf_level") == 0:
                    raise FloatingPointError(msg)
                print("[check_nan_inf]", msg)


def eager_apply(
    op_name: str,
    raw_fn: Callable,
    tensor_inputs: Sequence[Tensor],
    static_kwargs: Optional[Dict[str, Any]] = None,
    n_outputs: Optional[int] = 1,
    donate_idx: Sequence[int] = (),
):
    """Run one eager op.

    ``raw_fn(*arrays, **static_kwargs)`` is the functional implementation
    over raw jax arrays; ``tensor_inputs`` are the Tensor operands in
    positional order. Returns Tensor or tuple of Tensors (``n_outputs``).
    ``donate_idx`` marks inputs whose buffers MAY be donated to the
    compiled no-grad fast path (the in-place op family — the caller
    rebinds the target afterwards, see ``inplace_apply``); donation is
    skipped whenever the buffer is aliased elsewhere.

    Telemetry: every call bumps the ``op.<name>`` counter
    (profiler.stats); when a profiler window is recording, the whole
    dispatch additionally runs under an auto ``op::<name>`` RecordEvent
    span, so ``Profiler.summary()`` sees per-op count/total/avg/max
    without manual annotation.
    """
    _op_counter(op_name).inc()
    if not _SPANS.enabled:
        return _eager_apply_impl(op_name, raw_fn, tensor_inputs,
                                 static_kwargs, n_outputs, donate_idx)
    ev = RecordEvent("op::" + op_name)
    ev.begin()
    try:
        return _eager_apply_impl(op_name, raw_fn, tensor_inputs,
                                 static_kwargs, n_outputs, donate_idx)
    finally:
        ev.end()


def _eager_apply_impl(
    op_name: str,
    raw_fn: Callable,
    tensor_inputs: Sequence[Tensor],
    static_kwargs: Optional[Dict[str, Any]] = None,
    n_outputs: Optional[int] = 1,
    donate_idx: Sequence[int] = (),
):
    static_kwargs = static_kwargs or {}
    arrays = [t._data for t in tensor_inputs]

    if flag("check_donation"):
        _check_poisoned(arrays, f"op `{op_name}`")

    # AMP O1 autocast (reference: eager_gen.py:515 AMP logic in generated
    # ad_funcs + python/paddle/amp/auto_cast.py lists): white-list ops run in
    # the low-precision dtype, black-list ops in float32.
    from ..amp.auto_cast import _amp_cast_arrays

    arrays = _amp_cast_arrays(op_name, arrays)

    from ..amp.debugging import _op_stats, _record_op

    if _op_stats["enabled"]:
        for a in arrays:
            _record_op(op_name, a.dtype)

    grad_wanted = engine.is_grad_enabled() and any(
        (not t.stop_gradient) and _is_diff_dtype(t._data)
        for t in tensor_inputs
    )

    if not grad_wanted:
        fast = _forward_fast_path(raw_fn, arrays, static_kwargs,
                                  donate_idx, op_name=op_name)
        if fast is not None:
            outs, was_tuple = fast
        else:
            out = raw_fn(*arrays, **static_kwargs)
            was_tuple = isinstance(out, tuple)
            outs = out if was_tuple else (out,)
        if n_outputs is None:  # auto: single unless raw returned a tuple
            n_outputs = len(outs) if was_tuple else 1
        if flag("check_nan_inf"):
            _check_finite(op_name, outs)
        tensors = tuple(Tensor(o) for o in outs)
        _maybe_record(op_name, raw_fn, static_kwargs, tensor_inputs,
                      tensors)
        return tensors if n_outputs != 1 else tensors[0]

    diff_idx = [
        i for i, t in enumerate(tensor_inputs)
        if (not t.stop_gradient) and _is_diff_dtype(t._data)
    ]
    diff_set = set(diff_idx)

    cache_key = _vjp_cache_key(raw_fn, static_kwargs, arrays, diff_idx)
    if cache_key is None:
        _C_UNCACHEABLE.inc()
    elif cache_key in _VJP_BLOCK:
        _C_BLOCKED.inc()
        cache_key = None
    entry = _VJP_CACHE.get(cache_key) if cache_key is not None else None

    primals_out = vjp_fn = None
    if entry is not None:
        try:
            out_flat = entry.fwd(*arrays)
        except (jax.errors.JAXTypeError, jax.errors.UnexpectedTracerError):
            # trace needs concrete values — permanent plain-vjp fallback
            # (cache_key cleared so the fallback below can't re-admit a
            # zombie entry under the blocked key)
            _C_BLOCKLISTED.inc()
            _VJP_BLOCK.add(cache_key)
            del _VJP_CACHE[cache_key]
            cache_key = None
        else:
            _C_HIT.inc()
            box = entry.box
            primals_out = out_flat[:box["n_out"]]
            res_leaves = out_flat[box["n_out"]:]
            bwd = entry.bwd
            vjp_fn = lambda cots, _b=bwd, _r=res_leaves: _b(*_r, *cots)
            if n_outputs is None:
                n_outputs = box["n_out"] if box["was_tuple"] else 1

    if primals_out is None:
        const_arrays = {i: a for i, a in enumerate(arrays)
                        if i not in diff_set}
        was_tuple = [False]

        def f(*diff_arrays):
            full = _interleave(const_arrays, len(arrays), diff_arrays)
            out = raw_fn(*full, **static_kwargs)
            was_tuple[0] = isinstance(out, tuple)
            return out if isinstance(out, tuple) else (out,)

        _C_MISS.inc()
        with _stats.timed("compile.vjp_trace_us"):
            primals_out, vjp_fn = jax.vjp(f, *[arrays[i] for i in diff_idx])
        if n_outputs is None:  # auto: single unless raw returned a tuple
            n_outputs = len(primals_out) if was_tuple[0] else 1
        if cache_key is not None:
            _vjp_cache_admit(cache_key, op_name, raw_fn, static_kwargs,
                             len(arrays), diff_idx)

    if flag("check_nan_inf"):
        _check_finite(op_name, primals_out)

    edges = []
    for i in diff_idx:
        t = tensor_inputs[i]
        if t._grad_node is not None:
            edges.append(("node", t._grad_node, t._out_idx))
        else:
            edges.append(("leaf", t))

    out_avals = [(o.shape, o.dtype) for o in primals_out]
    node = engine.GradNode(op_name, vjp_fn, edges, out_avals)
    # double-grad support: keep the primal recipe so create_graph can
    # re-express this backward as a differentiable op (engine._apply_node).
    # The recipe bakes in the dtypes the forward actually ran with (AMP
    # may have cast them, and may be OFF at backward time), so the replay
    # reproduces the same out_avals. Recording holds refs to ALL primal
    # inputs (the vjp residuals usually hold most of them anyway);
    # memory-critical first-order-only runs can turn it off via
    # FLAGS_record_double_grad (create_graph then raises).
    if flag("record_double_grad"):
        cast_dtypes = [a.dtype for a in arrays]

        def recipe_fn(*full):
            full = [x.astype(dt) if x.dtype != dt else x
                    for x, dt in zip(full, cast_dtypes)]
            out = raw_fn(*full, **static_kwargs)
            return out if isinstance(out, tuple) else (out,)

        node.second = (recipe_fn, list(tensor_inputs), diff_idx)

    tensors = []
    for idx, o in enumerate(primals_out):
        t = Tensor(o, stop_gradient=not _is_diff_dtype(o))
        t._grad_node = node
        t._out_idx = idx
        tensors.append(t)
    tensors = tuple(tensors)
    _maybe_record(op_name, raw_fn, static_kwargs, tensor_inputs, tensors)
    return tensors if n_outputs != 1 else tensors[0]


_STATIC_STATE = None


def _maybe_record(op_name, raw_fn, static_kwargs, tensor_inputs, tensors):
    """Static-graph recording hook: under static.program_guard every
    dispatched op is appended to the active Program (the ProgramDesc
    build step of the reference's static mode — base/framework.py
    append_op); eager execution proceeds unchanged. The thread-local is
    cached after first use so the common no-guard case costs one
    attribute check per dispatch."""
    global _STATIC_STATE
    if _STATIC_STATE is None:
        from ..static.program import _STATE as _STATIC_STATE_MOD

        _STATIC_STATE = _STATIC_STATE_MOD
    prog = _STATIC_STATE.main
    if prog is not None:
        prog.record(op_name, raw_fn, static_kwargs, tensor_inputs, tensors)


def inplace_apply(
    op_name: str,
    raw_fn: Callable,
    tensor_inputs: Sequence[Tensor],
    static_kwargs: Optional[Dict[str, Any]] = None,
):
    """Dispatch one in-place op: functional ``raw_fn`` + Tensor rebind.

    The target (``tensor_inputs[0]``) is offered for buffer DONATION to
    the compiled-forward fast path: in no-grad steady state the update
    happens in place in HBM instead of double-buffering. Donation is
    skipped (automatically, per call) when the buffer is aliased by
    anything else — ``detach()`` views, saved residuals, a user-held
    array — so the aliasing contract of the ``*_`` family is preserved:
    the caller-visible result is always bit-identical to the undonated
    out-of-place op. Under grad, tapes exactly like the functional op.
    """
    target = tensor_inputs[0]
    out = eager_apply(op_name, raw_fn, tensor_inputs, static_kwargs, 1,
                      donate_idx=(0,))
    target._rebind(out._data, out._grad_node, out._out_idx)
    return target


def defun(op_name: str, n_tensor_args: int = 1, n_outputs: int = 1):
    """Turn a raw-array function into an eager op.

    The first ``n_tensor_args`` positional args are Tensors (scalars are
    promoted); everything keyword is static. ``n_tensor_args=-1`` means all
    positional args are tensors. The raw function stays reachable as
    ``op.raw_fn`` (in-place wrappers re-dispatch it with donation).
    """

    def deco(raw_fn):
        import functools

        @functools.wraps(raw_fn)
        def op(*args, **kwargs):
            nt = len(args) if n_tensor_args < 0 else n_tensor_args
            tensors = as_tensor_args(*args[:nt])
            static = dict(kwargs)
            if nt < len(args):
                raise TypeError(
                    f"{op_name}: extra positional args beyond tensor slots; "
                    "pass them as keywords")
            return eager_apply(op_name, raw_fn, tensors, static, n_outputs)

        op.__name__ = op_name
        op.raw_fn = raw_fn
        return op

    return deco
