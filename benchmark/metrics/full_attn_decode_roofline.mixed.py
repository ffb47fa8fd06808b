"""Kernels: the full layers' decode attention (``pt_gqa_paged_decode``,
self time in the trace) against the packed rows the window's decode steps
had to read (the program's ``serving.attn.rows_read``, a step and full
layer) at ``kv heads x (head_dim + v_head_dim) x 2`` content bytes a row
over the HBM peak."""
from benchmark.readers_mimo import FULL_DECODE, decode_roofline


def read(ctx):
    return decode_roofline(ctx, FULL_DECODE, "serving.attn.rows_read",
                           window=False)
