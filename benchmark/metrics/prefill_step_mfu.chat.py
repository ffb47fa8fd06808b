"""Model step: model operations of the prefill chunks that ran (their real
tokens, causal attention over what is cached, the head once a request) over
their device time times the chip's bf16 peak."""
from benchmark.kernels import gpt
from benchmark.readers import PREFILL_PROGRAM, module_time, prefill_chunks, \
    share


def read(ctx):
    t = module_time(ctx, PREFILL_PROGRAM)
    chunks = prefill_chunks(ctx)
    if t is None or not chunks:
        return None
    flops = sum(gpt.prefill_chunk_flops(ctx["config"], pos, n, final)
                for pos, n, final in chunks)
    return share(flops / ctx["peaks"]["bf16_flops_per_s"], t[0])
