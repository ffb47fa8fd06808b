"""Kernels: the decode rows' expert stream (``pt_moe_stream_experts``, self
time in the trace) against the least time the chip could take for the traced
decode steps: the larger of every held expert's weights once a step over the
HBM peak and the landed picks' operations over the bf16 peak."""
from benchmark.kernels import mistral4_mla as mk
from benchmark.readers import share
from benchmark.readers_granite import picks_share
from benchmark.readers_mistral4 import decode_work, kernel_time

KERNEL = r"^pt_moe_stream_experts(?!\w)"


def read(ctx):
    cfg = ctx["config"]
    t = kernel_time(ctx, KERNEL)
    work = decode_work(ctx)
    if t is None or work is None or not work[2]:
        return None
    least = max(work[2] * mk.moe_stream_bytes(cfg)
                / ctx["peaks"]["hbm_bytes_per_s"],
                mk.moe_stream_flops(cfg, work[3], picks_share(ctx))
                / ctx["peaks"]["bf16_flops_per_s"])
    return share(least, t[0])
