"""Kernels: the one-token state update (``pt_ssm_decode_update``, self time
in the trace) against the recurrent state of the traced decode steps'
sequences read and written once over the HBM peak: the kernel moves 8.4 MB a
sequence and layer and computes 5 operations an element, so HBM bounds it."""
from benchmark.kernels import granite_hybrid as gh
from benchmark.readers import share
from benchmark.readers_granite import decode_work

KERNEL = r"^pt_ssm_decode_update(?!\w)"


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    seconds, calls = tr.ops_matching(KERNEL) if tr is not None else (0, 0)
    work = decode_work(ctx)
    if not calls or work is None or not work[3]:
        return None
    nbytes = gh.ssm_decode_bytes(cfg, work[3])
    flops = 5 * gh.state_elems(cfg) * gh.n_mamba(cfg) * work[3]
    least = max(nbytes / ctx["peaks"]["hbm_bytes_per_s"],
                flops / ctx["peaks"]["bf16_flops_per_s"])
    return share(least, seconds)
