"""Kernels: the prefill side of latent attention (``pt_mla_paged_prefill``,
self time in the trace) against the causal query-key pairs of the window's
chunks (the program's ``serving.mla.prefill_pairs``, a chunk and layer) at
``heads x (qk_head_dim + v_head_dim) x 2`` operations a pair over the bf16
peak: the model's own count. The kernel attends in the absorbed form, 2.5
times that a pair, so 40% is the most it can read."""
from benchmark.kernels import mistral4_mla as mk
from benchmark.readers import share
from benchmark.readers_granite import counter_delta
from benchmark.readers_mistral4 import PREFILL_KERNEL, kernel_time


def read(ctx):
    t = kernel_time(ctx, PREFILL_KERNEL)
    pairs = counter_delta(ctx, "serving.mla.prefill_pairs")
    if t is None or pairs <= 0:
        return None
    return share(mk.mla_prefill_flops(ctx["config"], pairs)
                 / ctx["peaks"]["bf16_flops_per_s"], t[0])
