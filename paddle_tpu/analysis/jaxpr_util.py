"""Shared jaxpr plumbing for the program-level passes: sub-jaxpr
enumeration, aval byte sizing, and source anchoring of equations (so
findings land on the repo line that built the op and inline waivers
apply there).
"""
from __future__ import annotations

import os
from typing import Iterator, List, Optional, Tuple

import numpy as np

__all__ = ["sub_jaxprs", "walk_eqns", "aval_bytes", "eqn_anchor",
           "repo_root"]


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _jaxpr_types():
    from jax.extend.core import ClosedJaxpr, Jaxpr

    return ClosedJaxpr, Jaxpr


def sub_jaxprs(eqn) -> List[object]:
    """Inner jaxprs of one equation (scan/while/cond/pjit/shard_map/
    custom_* all carry theirs under different param keys — enumerate by
    type instead of by name)."""
    ClosedJaxpr, Jaxpr = _jaxpr_types()
    out = []
    for v in eqn.params.values():
        if isinstance(v, ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, Jaxpr):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            for b in v:
                if isinstance(b, ClosedJaxpr):
                    out.append(b.jaxpr)
                elif isinstance(b, Jaxpr):
                    out.append(b)
    return out


#: primitives whose sub-jaxpr is a LOOP body (runs per iteration)
LOOP_PRIMS = ("scan", "while")


def walk_eqns(jaxpr, in_loop: bool = False) -> Iterator[Tuple[object, bool]]:
    """Yield ``(eqn, in_loop)`` over a jaxpr and all sub-jaxprs, where
    ``in_loop`` is True for equations inside a scan/while body."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        inner_loop = in_loop or eqn.primitive.name in LOOP_PRIMS
        for sj in sub_jaxprs(eqn):
            yield from walk_eqns(sj, inner_loop)


def aval_bytes(aval) -> int:
    """HBM bytes of one abstract value (bf16 counts 2; non-array avals
    count 0)."""
    try:
        size = int(aval.size)
        dt = str(aval.dtype).replace("bfloat16", "uint16")
        return size * int(np.dtype(dt).itemsize)
    except Exception:
        return 0


def eqn_anchor(eqn) -> Tuple[Optional[str], Optional[int]]:
    """(path, line) of the user frame that built this equation —
    repo-relative when inside the repo — or (None, None)."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None, None
    path, line = frame.file_name, int(frame.start_line)
    root = repo_root()
    if path.startswith(root + os.sep):
        path = os.path.relpath(path, root)
    return path, line
