"""Kernels: the chunked-prefill attention over the paged pool
(``pt_flash_varlen_paged``, self time in the trace) against the attention
operations of the traced chunks (their real tokens, each attending what is
cached and the causal half of its own chunk) over the bf16 peak."""
from benchmark.kernels import gpt
from benchmark.readers import prefill_chunks, share

KERNEL = r"^pt_flash_varlen_paged(?!\w)"


def read(ctx):
    tr = ctx["trace"]
    seconds, calls = tr.ops_matching(KERNEL) if tr is not None else (0, 0)
    pairs = sum(gpt.causal_pairs(pos, n)
                for pos, n, _final in prefill_chunks(ctx))
    if not calls or not pairs:
        return None
    return share(gpt.attention_flops(ctx["config"], pairs)
                 / ctx["peaks"]["bf16_flops_per_s"], seconds)
