"""Kernels: the window layers' prefill attention (``pt_swa_prefill``, self
time in the trace) against the windowed query-key pairs of the window's
chunks (the program's ``serving.window.prefill_pairs``, at most 128 a
query, a chunk and window layer) at ``heads x (head_dim + v_head_dim) x 2``
operations a pair over the bf16 peak: the model's own count."""
from benchmark.readers_mimo import WINDOW_PREFILL, prefill_roofline


def read(ctx):
    return prefill_roofline(ctx, WINDOW_PREFILL,
                            "serving.window.prefill_pairs")
