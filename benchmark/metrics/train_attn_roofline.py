"""Kernels: flash attention of the train step, forward
(``pt_flash_mha_fwd``) and JAX's two backward kernels together, against
three times the forward's causal count (QK^T and PV forward; dV, dP, dQ and
dK backward; the scores the backward recomputes are not counted) over the
bf16 peak."""
from benchmark.kernels import gpt
from benchmark.readers import TRAIN_PROGRAM, module_time, share

FORWARD = r"^pt_flash_mha_fwd(?!\w)"
BACKWARD = r"^flash_mha_bwd_(dq|dkv)_"


def read(ctx):
    tr = ctx["trace"]
    steps = module_time(ctx, TRAIN_PROGRAM)
    if tr is None or steps is None:
        return None
    fwd, bwd = tr.ops_matching(FORWARD), tr.ops_matching(BACKWARD)
    if not fwd[1] or not bwd[1]:
        return None
    f = ctx["facts"]
    rows = f["tokens_per_step"] // f["seq"]
    flops = 3 * gpt.attention_flops(ctx["config"],
                                    gpt.causal_pairs(0, f["seq"])) \
        * rows * steps[1]
    return share(flops / ctx["peaks"]["bf16_flops_per_s"], fwd[0] + bwd[0])
