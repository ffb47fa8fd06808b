"""Kernels: the window layers' decode attention (``pt_swa_decode``, self
time in the trace) against the ring rows it read (the program's
``serving.window.rows_read``: every slot's 128 rows, a step and window
layer) at ``kv heads x (head_dim + v_head_dim) x 2`` content bytes a row
over the HBM peak (the ring stores K heads in 256 lanes: 1.2 x that)."""
from benchmark.readers_mimo import WINDOW_DECODE, decode_roofline


def read(ctx):
    return decode_roofline(ctx, WINDOW_DECODE, "serving.window.rows_read",
                           window=True)
