"""Training: the whole step's share of the chip's bf16 peak: operations that
forward and backward require per token (every matrix once per use in a
matmul, the causal half of attention, no looked-up table, nothing
recomputed) times the tokens of the steps that ran in the traced part of the
window, over that part's length times the peak."""
from benchmark.kernels import gpt
from benchmark.readers import TRAIN_PROGRAM, module_time, share


def read(ctx):
    t = module_time(ctx, TRAIN_PROGRAM)
    tr = ctx["trace"]
    if t is None or not tr.window_s:
        return None
    f = ctx["facts"]
    per_token = gpt.train_flops_per_token(ctx["config"], f["seq"])
    flops = per_token * f["tokens_per_step"] * t[1]
    return share(flops / ctx["peaks"]["bf16_flops_per_s"], tr.window_s)
