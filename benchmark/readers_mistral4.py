"""What the ``.longdoc`` readers share (``family: mistral4_mla`` behind the
pattern-built programs): the work of the traced decode chunks by this
family's own counts, and the two latent-attention kernels' names."""
from __future__ import annotations

from benchmark.kernels import mistral4_mla as mk
from benchmark.readers import decode_chunks
from benchmark.readers_granite import picks_share

PREFILL_KERNEL = r"^pt_mla_paged_prefill(?!\w)"
DECODE_KERNEL = r"^pt_mla_paged_decode(?!\w)"


def kernel_time(ctx, pattern):
    """(self seconds, calls) of a kernel in the trace, or None."""
    tr = ctx["trace"]
    if tr is None:
        return None
    seconds, calls = tr.ops_matching(pattern)
    return (seconds, calls) if calls else None


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
            flops += mk.decode_step_flops(cfg, live, ps)
            nbytes += mk.decode_step_bytes(cfg, live)
            tokens += len(live)
        steps += k
    return flops, nbytes, steps, tokens
