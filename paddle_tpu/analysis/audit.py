"""Audit shim around ``pl.pallas_call``: records every kernel launch
spec — BlockSpecs, grid, scratch shapes, operand avals, compiler
params — at trace time, without perturbing the call.

This is how the geometry pass sees kernels exactly as Mosaic will: the
sites driver (``analysis.sites``) dry-traces each kernel under
``jax.eval_shape`` with this shim installed, so the whole launch spec is
captured on CPU with zero device work (abstract evaluation never lowers
to Mosaic, so it works off-TPU regardless of ``interpret``).

The shim patches the ``pallas_call`` attribute of
``jax.experimental.pallas``; both the repo's kernels and the stock jax
kernels (flash attention, jax paged_attention) resolve it through the
module at call time, so all of them are captured.
"""
from __future__ import annotations

import contextlib
import dataclasses
import traceback
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["BlockSpecInfo", "ScratchInfo", "PallasCallRecord",
           "record_pallas_calls"]


@dataclasses.dataclass
class BlockSpecInfo:
    """One (possibly None) BlockSpec, normalized."""

    block_shape: Optional[Tuple[int, ...]]
    index_map: Optional[Any]          # the original callable, if any
    memory_space: Optional[str]       # e.g. "any", "vmem", None
    # filled by the analyzer from call-time operands / out_shape:
    aval_shape: Optional[Tuple[int, ...]] = None
    aval_dtype: Optional[str] = None

    @property
    def is_blocked(self) -> bool:
        return self.block_shape is not None


@dataclasses.dataclass
class ScratchInfo:
    shape: Tuple[int, ...]
    dtype: str
    memory_space: str                 # "vmem" | "smem" | "semaphore"


@dataclasses.dataclass
class PallasCallRecord:
    kernel_name: str
    path: str                         # call-site file
    line: int                         # call-site line
    grid: Tuple[int, ...]
    num_scalar_prefetch: int
    in_specs: List[BlockSpecInfo]
    out_specs: List[BlockSpecInfo]
    scratch: List[ScratchInfo]
    out_shapes: List[Optional[Tuple[Tuple[int, ...], str]]]
    vmem_limit_bytes: Optional[int]
    input_output_aliases: Dict[int, int]
    interpret: bool
    # the names the device trace can show the launch under: the
    # ``name=`` it was built with, and the innermost ``jax.named_scope``
    # open when it was called (the compiled instruction takes the
    # innermost of the two); None where the site gives none
    name: Optional[str] = None
    scope: Optional[str] = None
    # call-time avals, one per operand INCLUDING scalar-prefetch args;
    # None for operands passed as literal None (optional flash inputs)
    operands: Optional[List[Optional[Tuple[Tuple[int, ...], str]]]] = None

    @property
    def site(self) -> str:
        return f"{self.path}:{self.line}:{self.kernel_name}"

    def scalar_operands(self):
        """Call-time avals of the scalar-prefetch operands."""
        ops = self.operands or []
        return ops[:self.num_scalar_prefetch]

    def blocked_operands(self):
        """(BlockSpecInfo, aval) pairs for the non-scalar inputs, spec
        order; aval is None when the operand was passed as None."""
        ops = (self.operands or [])[self.num_scalar_prefetch:]
        return list(zip(self.in_specs, list(ops) + [None] * (
            len(self.in_specs) - len(ops))))


def _space_name(space) -> Optional[str]:
    if space is None:
        return None
    name = getattr(space, "name", None) or str(space)
    return str(name).lower()


def _norm_spec(spec) -> BlockSpecInfo:
    if spec is None:
        return BlockSpecInfo(None, None, None)
    shape = getattr(spec, "block_shape", None)
    if shape is not None:
        shape = tuple(int(d) for d in shape)
    return BlockSpecInfo(
        block_shape=shape,
        index_map=getattr(spec, "index_map", None),
        memory_space=_space_name(getattr(spec, "memory_space", None)))


def _norm_scratch(ref) -> ScratchInfo:
    space = _space_name(getattr(ref, "memory_space", None)) or "vmem"
    dtype = getattr(ref, "dtype", None)
    dstr = str(getattr(dtype, "name", None)
               or getattr(dtype, "__name__", None) or dtype)
    if "sem" in dstr or "semaphore" in space:
        kind = "semaphore"
    elif "smem" in space:
        kind = "smem"
    else:
        kind = "vmem"
    shape = tuple(int(d) for d in getattr(ref, "shape", ()) or ())
    return ScratchInfo(shape=shape, dtype=dstr, memory_space=kind)


def _as_list(x) -> list:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _norm_out_shape(s):
    if s is None:
        return None
    return (tuple(int(d) for d in s.shape), str(s.dtype))


def _aval(x):
    if x is None:
        return None
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return None
    return (tuple(int(d) for d in shape), str(dtype))


def _call_site() -> Tuple[str, int]:
    """First stack frame outside this module and outside functools —
    the code that invoked pallas_call."""
    for frame in reversed(traceback.extract_stack()):
        if frame.filename != __file__ and "functools" not in frame.filename:
            return frame.filename, frame.lineno or 0
    return "<unknown>", 0


def _capture(kernel, args, kwargs) -> PallasCallRecord:
    grid_spec = kwargs.get("grid_spec")
    if grid_spec is not None:
        grid = getattr(grid_spec, "grid", ()) or ()
        nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
        in_specs = _as_list(getattr(grid_spec, "in_specs", None))
        out_specs = _as_list(getattr(grid_spec, "out_specs", None))
        scratch = _as_list(getattr(grid_spec, "scratch_shapes", None))
    else:
        grid = kwargs.get("grid", ()) or ()
        nsp = 0
        in_specs = _as_list(kwargs.get("in_specs"))
        out_specs = _as_list(kwargs.get("out_specs"))
        scratch = _as_list(kwargs.get("scratch_shapes"))
    if isinstance(grid, int):
        grid = (grid,)
    cp = kwargs.get("compiler_params")
    vmem = getattr(cp, "vmem_limit_bytes", None) if cp is not None else None
    if isinstance(cp, dict):  # pallas also accepts a plain dict
        vmem = (cp.get("mosaic") or {}).get("vmem_limit_bytes",
                                            cp.get("vmem_limit_bytes"))
    path, line = _call_site()
    name = getattr(kernel, "__name__", None)
    if not name or name == "<lambda>":
        fn = getattr(kernel, "func", None)  # functools.partial
        name = getattr(fn, "__name__", name or "<kernel>")
    return PallasCallRecord(
        kernel_name=name,
        path=path,
        line=line,
        grid=tuple(int(g) for g in grid),
        num_scalar_prefetch=nsp,
        in_specs=[_norm_spec(s) for s in in_specs],
        out_specs=[_norm_spec(s) for s in out_specs],
        scratch=[_norm_scratch(r) for r in scratch],
        out_shapes=[_norm_out_shape(s)
                    for s in _as_list(kwargs.get("out_shape"))],
        vmem_limit_bytes=int(vmem) if vmem is not None else None,
        input_output_aliases=dict(
            kwargs.get("input_output_aliases") or {}),
        interpret=bool(kwargs.get("interpret", False)),
        name=kwargs.get("name"),
    )


def _innermost_scope() -> Optional[str]:
    from jax._src import source_info_util

    scopes = [e.name for e in source_info_util.current_name_stack().stack
              if isinstance(e, source_info_util.Scope)]
    return scopes[-1] if scopes else None


@contextlib.contextmanager
def record_pallas_calls():
    """Patch ``pl.pallas_call`` to record every launch spec; yields the
    (live) list of :class:`PallasCallRecord`. The real pallas_call runs
    unchanged underneath, so this can wrap real executions as well as
    ``jax.eval_shape`` dry-traces."""
    from jax.experimental import pallas as pl

    records: List[PallasCallRecord] = []
    orig = pl.pallas_call

    def shim(kernel, *args, **kwargs):
        rec = _capture(kernel, args, kwargs)
        records.append(rec)
        inner = orig(kernel, *args, **kwargs)

        def invoke(*operands):
            rec.operands = [_aval(o) for o in operands]
            rec.scope = _innermost_scope()
            return inner(*operands)

        return invoke

    pl.pallas_call = shim
    try:
        yield records
    finally:
        pl.pallas_call = orig
