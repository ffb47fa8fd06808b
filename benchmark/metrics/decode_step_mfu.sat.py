"""Model step: model operations of the decode steps that ran over their
device time times the chip's bf16 peak."""
from benchmark.readers import DECODE_PROGRAM, decode_work, module_time, share


def read(ctx):
    t = module_time(ctx, DECODE_PROGRAM)
    flops, _nbytes, steps = decode_work(ctx)
    if t is None or not steps:
        return None
    return share(flops / ctx["peaks"]["bf16_flops_per_s"], t[0])
