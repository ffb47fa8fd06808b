"""Whole-step compilation: forward + backward + optimizer in ONE XLA program.

This is the TPU-idiomatic performance path (SURVEY.md §7.1 step 5 "whole
step compile (fwd+bwd+opt)"). The reference runs a step as thousands of
individually-launched kernels coordinated by the interpreter
(new_executor/program_interpreter.cc); on TPU the entire step compiles to
a single executable — XLA fuses elementwise chains into the matmuls, the
optimizer update aliases parameter buffers in HBM (donation), and the only
per-step host work is pushing the batch and pulling the scalar loss.

Used by hapi.Model.fit, bench.py, and the distributed data-parallel step
(where the same pure function is pjit'd over a mesh).
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from ..core import engine
from ..core.generator import default_generator, use_trace_key
from ..core.tensor import Tensor
from ..profiler import RecordEvent
from ..profiler import roofline as _roofline
from ..profiler import stats as _stats
from .static_function import _SwappedState, _flatten_tensors

__all__ = ["TrainStep"]


class TrainStep:
    """Compile ``loss = loss_fn(model(*inputs), *labels)`` + optimizer step.

    ``step(inputs, labels)`` returns the loss Tensor; parameters, optimizer
    state and buffers are updated in place (rebound to the donated outputs).
    """

    def __init__(self, model, loss_fn: Callable, optimizer,
                 in_sharding=None, donate: bool = True,
                 amp_level: Optional[str] = None,
                 amp_dtype: str = "bfloat16"):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # autocast applied around the traced forward+loss: O1 = per-op
        # white/black lists; O2 = cast-everything-except-blacklist (the
        # decorate() param cast alone is not enough — fp32 activations
        # would re-promote bf16 params at every op)
        self._amp_level = amp_level if amp_level in ("O1", "O2") else None
        self._amp_dtype = amp_dtype
        self._params = [p for _, p in model.named_parameters()]
        self._buffers = [b for _, b in model.named_buffers()]
        self._trainable_idx = [i for i, p in enumerate(self._params)
                               if not p.stop_gradient]
        donate_args = (0, 1) if donate else ()
        # explicit-AOT dispatch (profiler/roofline.py): the whole-step
        # executable's XLA cost model (flops, bytes accessed) lands in
        # compile.{flops,bytes} at compile time, so bench.py and
        # tools/*_profile.py derive MFU / bandwidth utilization from the
        # compiler's own accounting via self.roofline() instead of a
        # hand-derived flops-per-token formula
        self._program_name = f"TrainStep[{type(model).__name__}]"
        self._compiled = _roofline.AotProgram(
            self._program_name, jax.jit(self._pure_step,
                                        donate_argnums=donate_args))
        _stats.inc("jit.train_step_build")

    # ---- functional grad-clip mirror of nn.ClipGradByGlobalNorm ----
    def _clip_grads(self, grads):
        clip = self.optimizer._grad_clip
        if clip is None:
            return grads
        from ..nn.clip import ClipGradByGlobalNorm, ClipGradByNorm, \
            ClipGradByValue

        if isinstance(clip, ClipGradByValue):
            return [jnp.clip(g, clip.min, clip.max) for g in grads]
        if isinstance(clip, ClipGradByNorm):
            out = []
            for g in grads:
                n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                s = jnp.minimum(clip.clip_norm / jnp.maximum(n, 1e-12), 1.0)
                out.append((g * s).astype(g.dtype))
            return out
        if isinstance(clip, ClipGradByGlobalNorm):
            gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in grads)
            gnorm = jnp.sqrt(gsq)
            s = jnp.minimum(clip.clip_norm / jnp.maximum(gnorm, 1e-12), 1.0)
            return [(g * s).astype(g.dtype) for g in grads]
        raise NotImplementedError(f"grad clip {type(clip)} in TrainStep")

    def _pure_step(self, param_arrays, opt_states, buffer_arrays,
                   input_arrays, label_arrays, key, hyper, per_param):
        model, loss_fn = self.model, self.loss_fn
        params, buffers = self._params, self._buffers
        t_idx = self._trainable_idx

        def loss_of(trainable_arrays):
            full = list(param_arrays)
            for i, a in zip(t_idx, trainable_arrays):
                full[i] = a
            from ..amp import auto_cast

            amp_ctx = auto_cast(enable=self._amp_level is not None,
                                level=self._amp_level or "O1",
                                dtype=self._amp_dtype)
            with _SwappedState(params + buffers,
                               full + list(buffer_arrays)), \
                    use_trace_key(key), engine.no_grad(), amp_ctx:
                inputs = [Tensor(a) for a in input_arrays]
                labels = [Tensor(a, stop_gradient=True)
                          for a in label_arrays]
                out = model(*inputs)
                outs = out if isinstance(out, (list, tuple)) else (out,)
                loss = loss_fn(*outs, *labels)
                # mutated buffers surfaced via has_aux (no tracer leak)
                new_bufs = [b._data for b in buffers]
            return (loss._data if isinstance(loss, Tensor) else loss,
                    new_bufs)

        trainable = [param_arrays[i] for i in t_idx]
        (loss, new_bufs), grads = jax.value_and_grad(
            loss_of, has_aux=True)(trainable)
        grads = self._shard_grads(grads)
        grads = self._apply_regularizers(trainable, grads)
        grads = self._clip_grads(grads)

        sts = [opt_states[i] for i in range(len(t_idx))]
        new_trainable, new_sts = self.optimizer._update_arrays(
            trainable, grads, sts, hyper, per_param)
        new_params = list(param_arrays)
        for i, a in zip(t_idx, new_trainable):
            new_params[i] = a
        return loss, new_params, new_sts, new_bufs

    def _shard_grads(self, grads):
        """ZeRO stage-2 (os_g): when the optimizer carries a grad-shard
        annotation (set by GroupShardedStage2/DygraphShardingOptimizerV2),
        constrain each gradient to Shard over the sharding axis — GSPMD
        then fuses the dp grad all-reduce with the shard into a
        reduce-scatter (reference: dygraph_sharding_optimizer.py:470)."""
        gs = getattr(self.optimizer, "_grad_shard", None)
        if gs is None:
            return grads
        mesh, axis = gs
        from ..distributed.fleet.meta_parallel.sharding.sharding_optimizer \
            import _axis_sharding, _find_shard_dim

        degree = mesh.get_dim_size(axis)
        out = []
        for g in grads:
            d = _find_shard_dim(g.shape, degree)
            if d is None:
                out.append(g)
            else:
                out.append(jax.lax.with_sharding_constraint(
                    g, _axis_sharding(mesh, axis, g.ndim, dim=d)))
        return out

    def _apply_regularizers(self, p_arrays, grads):
        opt = self.optimizer
        from ..regularizer import WeightDecayRegularizer

        wd = opt._weight_decay
        if wd is None or opt._decoupled_wd():
            regs = [self._params[i].regularizer for i in self._trainable_idx]
            if not any(regs):
                return grads
            return [r(p, g) if r is not None else g
                    for r, p, g in zip(regs, p_arrays, grads)]
        if isinstance(wd, WeightDecayRegularizer):
            return [wd(p, g) for p, g in zip(p_arrays, grads)]
        return grads

    def _build_args(self, inputs, labels):
        """Assemble the positional args of ``_pure_step`` exactly as
        ``__call__`` passes them (single source for call + lowering)."""
        opt = self.optimizer
        trainable = [self._params[i] for i in self._trainable_idx]
        fun = getattr(opt, "_apply_decay_param_fun", None)
        if fun is not None:
            opt._no_decay_ids = {id(p) for p in trainable if not fun(p.name)}
        opt_states = [opt._state_for(p) for p in trainable]
        hyper = opt._hyper()
        per_param = [opt._per_param_hyper(p) for p in trainable]
        key = default_generator().next_key()

        p_arrays = [p._data for p in self._params]
        b_arrays = [b._data for b in self._buffers]
        in_arrays = [t._data if isinstance(t, Tensor) else jnp.asarray(t)
                     for t in inputs]
        lb_arrays = [t._data if isinstance(t, Tensor) else jnp.asarray(t)
                     for t in labels]
        return (p_arrays, opt_states, b_arrays, in_arrays, lb_arrays, key,
                hyper, per_param)

    def lower_hlo(self, inputs, labels=()) -> str:
        """Lower the whole-step program for these inputs and return the
        optimized HLO text (used by HLO-assertion tests and the
        multichip dryrun; does NOT execute the step)."""
        return self._compiled.jitted \
            .lower(*self._build_args(inputs, labels)).compile().as_text()

    def roofline(self, wall_s_per_step: float):
        """Roofline for the compiled step from the XLA cost model and an
        honestly measured per-step wall time: returns a RooflineResult
        (achieved FLOP/s, achieved bytes/s, MFU, %-of-bandwidth-roofline
        vs the device peak table) and refreshes the roofline.* gauges.
        None until the step has compiled."""
        return _roofline.analyze(self._program_name, wall_s_per_step)

    def __call__(self, inputs, labels=()):
        # the profiler's own step marker (StepTraceAnnotation): the
        # device trace groups this step's work under its step_num
        with RecordEvent("train.step",
                         step_num=self.optimizer._global_step):
            return self._call(inputs, labels)

    def _call(self, inputs, labels):
        if isinstance(inputs, Tensor):
            inputs = [inputs]
        if isinstance(labels, Tensor):
            labels = [labels]
        opt = self.optimizer
        trainable = [self._params[i] for i in self._trainable_idx]

        # first call = trace + XLA compile (+ run): record its wall
        # seconds so bench telemetry carries cold-vs-warm compile time
        # — with the persistent cache warm (device.setup_compile_cache)
        # a process's first call drops to executable-load time, and
        # the histogram shows it
        first = not getattr(self, "_first_call_done", False)
        if first:
            import time as _time

            t0 = _time.perf_counter()

        # where the host's time inside one call goes: the argument
        # lists, the compiled program's call (signature + enqueue) and
        # the write-back; each phase is a span AND, from the span's own
        # stamps, a ``jit.train_step.*_ms`` histogram
        with RecordEvent("train.args") as ev_args:
            args = self._build_args(inputs, labels)
        try:
            with RecordEvent("train.dispatch",
                             program=self._program_name) as ev_call:
                loss, new_params, new_sts, new_bufs = self._compiled(
                    *args)
        except Exception as e:  # graph-break diagnostics (VERDICT r3 #7)
            from .graph_break import reraise_graph_break

            if not reraise_graph_break(
                    f"TrainStep[{type(self.model).__name__}]", e):
                raise

        if first:
            self._first_call_done = True
            self.first_call_seconds = _time.perf_counter() - t0
            _stats.observe("compile.train_step_first_call_s",
                           self.first_call_seconds)
        with RecordEvent("train.rebind") as ev_rebind:
            for p, a in zip(self._params, new_params):
                p._rebind(a)
            for p, st in zip(trainable, new_sts):
                opt._accumulators[id(p)] = st
            for b, a in zip(self._buffers, new_bufs):
                b._rebind(a)
            opt._global_step += 1
        _stats.observe("jit.train_step.args_ms", ev_args.dur_ms)
        _stats.observe("jit.train_step.dispatch_ms", ev_call.dur_ms)
        _stats.observe("jit.train_step.rebind_ms", ev_rebind.dur_ms)
        return Tensor(loss)
