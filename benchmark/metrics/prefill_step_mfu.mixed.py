"""Model step: model operations of the prefill chunks that ran (their real
tokens, causal pairs in the full layers and at most 128 a query in the
window layers, routed experts for the picks that landed here, the head once
a request) over their device time times the chip's bf16 peak."""
from benchmark.kernels import mimo_v2_flash as mk
from benchmark.readers import module_time, prefill_chunks, share
from benchmark.readers_granite import PREFILL_PROGRAM, picks_share


def read(ctx):
    t = module_time(ctx, PREFILL_PROGRAM)
    chunks = prefill_chunks(ctx)
    ps = picks_share(ctx)
    if t is None or not chunks or ps is None:
        return None
    flops = sum(mk.prefill_chunk_flops(ctx["config"], pos, n, final, ps)
                for pos, n, final in chunks)
    return share(flops / ctx["peaks"]["bf16_flops_per_s"], t[0])
