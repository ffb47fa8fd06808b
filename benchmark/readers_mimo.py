"""What the ``.mixed`` readers share (``family: mimo_v2_flash`` behind the
pattern-built programs): the work of the traced decode chunks by this
family's own counts, and the four attention kernels' names."""
from __future__ import annotations

from benchmark.kernels import mimo_v2_flash as mk
from benchmark.readers import decode_chunks
from benchmark.readers_granite import counter_delta, picks_share
from benchmark.readers_mistral4 import kernel_time

FULL_PREFILL = r"^pt_gqa_paged_prefill(?!\w)"
FULL_DECODE = r"^pt_gqa_paged_decode(?!\w)"
WINDOW_PREFILL = r"^pt_swa_prefill(?!\w)"
WINDOW_DECODE = r"^pt_swa_decode(?!\w)"
KERNELS = (FULL_PREFILL, FULL_DECODE, WINDOW_PREFILL, WINDOW_DECODE)


def decode_work(ctx):
    """(operations, bytes, device steps, tokens) of the traced decode
    chunks, or None where the program counted no picks."""
    cfg, ps = ctx["config"], picks_share(ctx)
    if ps is None:
        return None
    k = int(cfg["serving"]["engine"]["decode_chunk"])
    flops = nbytes = steps = tokens = 0
    for seqs in decode_chunks(ctx):
        for j in range(k):
            live = [c + j for c, m in seqs if j < m]
            flops += mk.decode_step_flops(cfg, live, ps)
            nbytes += mk.decode_step_bytes(cfg, live)
            tokens += len(live)
        steps += k
    return flops, nbytes, steps, tokens


def prefill_roofline(ctx, kernel, counter):
    """A prefill attention kernel's self time against the query-key pairs
    the program counted (a chunk and layer) at the model's operations a
    pair over the bf16 peak, in percent; None without either."""
    t = kernel_time(ctx, kernel)
    pairs = counter_delta(ctx, counter)
    if t is None or pairs <= 0:
        return None
    return 100.0 * mk.prefill_attention_flops(ctx["config"], pairs) \
        / ctx["peaks"]["bf16_flops_per_s"] / t[0]


def decode_roofline(ctx, kernel, counter, window):
    """A decode attention kernel's self time against the K|V rows the
    program counted (a step and layer) at the model's content bytes a row
    over the HBM peak, in percent; None without either."""
    t = kernel_time(ctx, kernel)
    rows = counter_delta(ctx, counter)
    if t is None or rows <= 0:
        return None
    return 100.0 * mk.decode_attention_bytes(ctx["config"], rows, window) \
        / ctx["peaks"]["hbm_bytes_per_s"] / t[0]
