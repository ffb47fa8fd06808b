"""Kernels: the paged decode attention (``pt_paged_attention_decode_inplace``,
self time in the trace) against the least time the chip could take for the
traced decode steps: the larger of the attention operations over the bf16
peak and the K+V rows their sequences hold over the HBM peak."""
from benchmark.kernels import gpt
from benchmark.readers import decode_chunks, share

KERNEL = r"^pt_paged_attention_decode_inplace(?!\w)"


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    seconds, calls = tr.ops_matching(KERNEL) if tr is not None else (0, 0)
    k = int(cfg["serving"]["engine"]["decode_chunk"])
    rows = sum(c + j + 1 for seqs in decode_chunks(ctx)
               for c, m in seqs for j in range(min(m, k)))
    if not calls or not rows:
        return None
    least = max(gpt.attention_flops(cfg, rows)
                / ctx["peaks"]["bf16_flops_per_s"],
                rows * gpt.kv_bytes_per_token(cfg)
                / ctx["peaks"]["hbm_bytes_per_s"])
    return share(least, seconds)
