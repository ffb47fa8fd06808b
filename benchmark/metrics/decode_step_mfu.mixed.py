"""Model step: model operations of the decode steps that ran (attention
over the whole context in the full layers and at most 128 positions in the
window layers, routed experts for the picks that landed here) over their
device time times the chip's bf16 peak."""
from benchmark.readers import module_time, share
from benchmark.readers_granite import DECODE_PROGRAM
from benchmark.readers_mimo import decode_work


def read(ctx):
    t = module_time(ctx, DECODE_PROGRAM)
    work = decode_work(ctx)
    if t is None or work is None or not work[2]:
        return None
    return share(work[0] / ctx["peaks"]["bf16_flops_per_s"], t[0])
