"""Kernels: the flash-attention BACKWARD kernels of the train step (the only
Pallas calls that today's trace names: ``flash_mha_bwd_dq_*``,
``flash_mha_bwd_dkv_*``) against the operations they need: dV, dP, dQ and dK,
four matmuls over the causal half, twice the forward's count; the scores
they recompute are not counted. Compute-bound, so the roofline is the bf16
peak."""
from benchmark.kernels import gpt
from benchmark.readers import TRAIN_PROGRAM, module_time, share

KERNELS = r"^flash_mha_bwd_(dq|dkv)_"


def read(ctx):
    tr = ctx["trace"]
    steps = module_time(ctx, TRAIN_PROGRAM)
    if tr is None or steps is None:
        return None
    seconds, calls = tr.ops_matching(KERNELS)
    if not calls:
        return None
    f = ctx["facts"]
    rows = f["tokens_per_step"] // f["seq"]
    flops = 2 * gpt.attention_flops(ctx["config"],
                                    gpt.causal_pairs(0, f["seq"])) \
        * rows * steps[1]
    return share(flops / ctx["peaks"]["bf16_flops_per_s"], seconds)
