"""Kernels: the decode side of latent attention (``pt_mla_paged_decode``,
self time in the trace) against the latent rows the window's decode steps
had to read (the program's ``serving.mla.rows_read``, a step and layer) at
the stored row's bytes over the HBM peak: each row once for all heads."""
from benchmark.kernels import mistral4_mla as mk
from benchmark.readers import share
from benchmark.readers_granite import counter_delta
from benchmark.readers_mistral4 import DECODE_KERNEL, kernel_time


def read(ctx):
    t = kernel_time(ctx, DECODE_KERNEL)
    rows = counter_delta(ctx, "serving.mla.rows_read")
    if t is None or rows <= 0:
        return None
    return share(mk.mla_decode_bytes(ctx["config"], rows)
                 / ctx["peaks"]["hbm_bytes_per_s"], t[0])
