"""Kernels: the decode rows' expert stream (``pt_moe_stream_experts``, self
time in the trace) against the least time the chip could take for the traced
decode steps: the larger of every held expert's weights once a step over the
HBM peak and the landed picks' operations over the bf16 peak."""
from benchmark.kernels import granite_hybrid as gh
from benchmark.readers import share
from benchmark.readers_granite import decode_work, picks_share

KERNEL = r"^pt_moe_stream_experts(?!\w)"


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    seconds, calls = tr.ops_matching(KERNEL) if tr is not None else (0, 0)
    work = decode_work(ctx)
    if not calls or work is None or not work[2]:
        return None
    least = max(work[2] * gh.moe_stream_bytes(cfg)
                / ctx["peaks"]["hbm_bytes_per_s"],
                gh.moe_stream_flops(cfg, work[3], picks_share(ctx))
                / ctx["peaks"]["bf16_flops_per_s"])
    return share(least, seconds)
