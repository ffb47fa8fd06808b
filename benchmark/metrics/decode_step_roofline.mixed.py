"""Model step: the least time the chip could take for the decode steps that
ran (the larger of operations over the bf16 peak and bytes over the HBM
peak; bytes are every weight once a step, all held experts among them, the
full layers' K and V of every cached token and the window layers' of at
most 128, in content bytes) over the decode program's device time."""
from benchmark.readers import module_time, share
from benchmark.readers_granite import DECODE_PROGRAM
from benchmark.readers_mimo import decode_work


def read(ctx):
    t = module_time(ctx, DECODE_PROGRAM)
    work = decode_work(ctx)
    if t is None or work is None or not work[2]:
        return None
    least = max(work[0] / ctx["peaks"]["bf16_flops_per_s"],
                work[1] / ctx["peaks"]["hbm_bytes_per_s"])
    return share(least, t[0])
