"""Kernels: the decode program's weight-stream kernels
(``pt_stream_linear_layer_tail``: O, FFN1, FFN2 and the next layer's QKV;
``pt_stream_linear_bf16``: the first layer's QKV) against the bytes of the
four stacks once a device step over the HBM peak. The head is not in it:
under the default flags it is an XLA dot, not a stream kernel."""
from benchmark.kernels import gpt
from benchmark.readers import decode_chunks, share

KERNELS = r"^pt_stream_linear_(layer_tail|bf16)(?!\w)"


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    seconds, calls = tr.ops_matching(KERNELS) if tr is not None else (0, 0)
    steps = len(decode_chunks(ctx)) \
        * int(cfg["serving"]["engine"]["decode_chunk"])
    if not calls or not steps:
        return None
    nbytes = gpt.stream_linear_bytes(cfg) - 2 * gpt.head_params(cfg)
    return share(steps * nbytes / ctx["peaks"]["hbm_bytes_per_s"], seconds)
