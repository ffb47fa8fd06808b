"""Kernels: the full layers' prefill attention (``pt_gqa_paged_prefill``,
self time in the trace) against the causal query-key pairs of the window's
chunks (the program's ``serving.attn.prefill_pairs``, a chunk and full
layer) at ``heads x (head_dim + v_head_dim) x 2`` operations a pair over
the bf16 peak: the model's own count."""
from benchmark.readers_mimo import FULL_PREFILL, prefill_roofline


def read(ctx):
    return prefill_roofline(ctx, FULL_PREFILL, "serving.attn.prefill_pairs")
