"""What the ``.rag`` readers share (``family: granite_hybrid`` behind the
pattern-built programs): the programs' declared module names, the pick
counters' gain in the window, and the work of the traced decode chunks."""
from __future__ import annotations

from benchmark.kernels import granite_hybrid as gh
from benchmark.readers import decode_chunks

#: ``inference/hybrid.py`` declares these names; jit prefixes ``jit_``
DECODE_PROGRAM = r"^jit_+pt_hybrid_decode_chunk(?!\w)"
PREFILL_PROGRAM = r"^jit_+pt_hybrid_prefill_chunk(?!\w)"


def counter_delta(ctx, name):
    """What the program's counter ``name`` gained in the window (0 where the
    program has no such counter)."""
    f = ctx["facts"]
    c0 = (f.get("stats0") or ({}, {}, {}))[0]
    c1 = (f.get("stats1") or ({}, {}, {}))[0]
    return c1.get(name, 0) - c0.get(name, 0)


def picks_share(ctx):
    """Picks that landed on a held expert over all picks, in the window
    (``serving.moe.*``); None without them."""
    picks = counter_delta(ctx, "serving.moe.picks")
    if picks <= 0:
        return None
    return counter_delta(ctx, "serving.moe.picks_here") / picks


def decode_work(ctx):
    """(operations, bytes, device steps, tokens) of the traced decode
    chunks, or None where the program counted no picks."""
    cfg, ps = ctx["config"], picks_share(ctx)
    if ps is None:
        return None
    k = int(cfg["serving"]["engine"]["decode_chunk"])
    flops = nbytes = steps = tokens = 0
    for seqs in decode_chunks(ctx):
        for j in range(k):
            live = [c + j for c, m in seqs if j < m]
            flops += gh.decode_step_flops(cfg, live, ps)
            nbytes += gh.decode_step_bytes(cfg, live)
            tokens += len(live)
        steps += k
    return flops, nbytes, steps, tokens
