"""What the per-layer readers share. A reader is a file of its own under
``benchmark/metrics/`` with ``read(ctx)``; it returns a number, or None
where it finds nothing to read (the harness then leaves the metric out:
a share of a roofline or of a peak is never reported as 0).

``ctx``: ``cell``, ``config``, ``traffic``, ``peaks`` (this chip's row of
peaks.json), ``trace`` (a ``trace_reduce.TraceSummary`` or None),
``facts`` (what the driver measured) and ``traced`` (the traced part of the
window on the host's clock).
"""
from __future__ import annotations

import math

from benchmark.kernels import gpt


def percentile(values, q):
    v = sorted(values)
    if not v:
        return None
    return v[min(max(int(math.ceil(q / 100.0 * len(v))) - 1, 0), len(v) - 1)]


def hist_delta(facts, name):
    """(count, total) that the program's histogram ``name`` gained in the
    window."""
    h0 = (facts.get("stats0") or ({}, {}, {}))[2].get(name, (0, 0.0))
    h1 = (facts.get("stats1") or ({}, {}, {}))[2].get(name)
    if h1 is None:
        return None
    return h1[0] - h0[0], h1[1] - h0[1]


def in_traced(ctx, t):
    t0, t1 = ctx["traced"]
    return t0 is not None and t1 is not None and t0 <= t < t1


def seen_in_traced(ctx, t):
    """True where the stepping loop began a step between ``t`` and the end
    of the traced part. What is submitted during the loop's last step before
    the profiler stops (``Tracer.stop()`` holds the loop for tens of
    seconds) is first seen after it, and waited for the profiler."""
    t1 = ctx["traced"][1]
    return in_traced(ctx, t) and any(
        t <= s[0] < t1 for s in ctx["facts"].get("steps", ()))


def module_time(ctx, pattern):
    """(seconds, runs) of the programs whose name matches, in the trace."""
    tr = ctx["trace"]
    if tr is None:
        return None
    s, n = tr.modules_matching(pattern)
    return (s, n) if n else None


def share(needed_s, measured_s):
    """A share in percent, or None where nothing was measured."""
    if not measured_s or needed_s is None:
        return None
    return 100.0 * needed_s / measured_s


def device_idle(ctx, minus_span=None):
    """1 - busy / window, in percent; ``minus_span`` leaves out the gaps
    that fell into that harness span (no request in the engine)."""
    tr = ctx["trace"]
    if tr is None or not tr.window_s:
        return None
    idle = tr.window_s - tr.busy_s
    if minus_span is not None:
        idle -= tr.gap_s.get(minus_span, 0.0)
    return 100.0 * idle / tr.window_s


# The programs of the serving engine. Each pattern takes two spellings:
# the name today's trace gives (the program declares none yet; every
# prefill-chunk size shares the first, and the decode program is a
# ``functools.partial`` and so is called ``_unknown``), and ONE declared
# name in the form ``readers_granite.py`` matches, which a later program PR
# may give the uniform stack's programs without silencing eight readers.
PREFILL_PROGRAM = (r"^(?:jit__chunk_prefill_fn\("
                   r"|jit_+pt_fused_prefill_chunk(?!\w))")
DECODE_PROGRAM = r"^(?:jit__unknown\(|jit_+pt_fused_decode_chunk(?!\w))"


def prefill_chunks(ctx):
    """The prefill chunks that ended inside the traced part of the window,
    from the program's flight recorder: (pos_before, n_tokens, final)."""
    by_rid = {r.rid: r for r in ctx["facts"]["requests"] if r.rid is not None}
    out = []
    for e in ctx["facts"]["journal"]:
        if e.get("ev") != "prefill_chunk" or not in_traced(ctx, e["ts"]):
            continue
        rq = by_rid.get(e.get("rid"))
        n, pos = int(e["n"]), int(e["pos"])
        final = rq is not None and pos >= len(rq.prompt)
        out.append((pos - n, n, final))
    return out


def decode_chunks(ctx):
    """The decode chunks that ended inside the traced part of the window,
    from the harness's own token stamps: for each, the list of
    (context_before_first_token, tokens_taken) of the sequences it moved."""
    f = ctx["facts"]
    steps = f["steps"]
    chunks = {}
    for r in f["requests"]:
        p = len(r.prompt)
        for i, s in enumerate(r.token_step):
            if i == 0 or s >= len(steps) or steps[s][2] != "decode" \
                    or not in_traced(ctx, steps[s][1]):
                continue
            seqs = chunks.setdefault(s, {})
            if r.idx not in seqs:
                seqs[r.idx] = [p + i - 1, 0]
            seqs[r.idx][1] += 1
    return [list(map(tuple, seqs.values())) for seqs in chunks.values()]


def decode_work(ctx):
    """(model operations, bytes, device steps) of the traced decode chunks:
    operations for the tokens taken; bytes for every device step of a chunk
    (the weights and the head once a step, and the K+V rows held)."""
    cfg = ctx["config"]
    k = int(cfg["serving"]["engine"]["decode_chunk"])
    flops = nbytes = steps = 0
    for seqs in decode_chunks(ctx):
        for j in range(k):
            live = [c + j for c, m in seqs if j < m]
            flops += gpt.decode_step_flops(cfg, live)
            nbytes += gpt.decode_step_bytes(cfg, live)
        steps += k
    return flops, nbytes, steps

#: the compiled train step: ``TrainStep._pure_step`` under jit today, or
#: the one name a later program PR may declare for it
TRAIN_PROGRAM = r"^(?:jit__pure_step\(|jit_+pt_train_step(?!\w))"
