"""Model step: the least time the chip could take for the decode steps that
ran (the larger of operations over the bf16 peak and bytes over the HBM
peak; bytes are the weights and the head once a step and the K+V rows the
step's sequences hold) over the decode programs' device time."""
from benchmark.readers import DECODE_PROGRAM, decode_work, module_time, share


def read(ctx):
    t = module_time(ctx, DECODE_PROGRAM)
    flops, nbytes, steps = decode_work(ctx)
    if t is None or not steps:
        return None
    least = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return share(least, t[0])
