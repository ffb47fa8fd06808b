"""Kernels: the chunked SSD scan (``pt_ssd_chunk_scan``, self time in the
trace) against the least time the chip could take for the traced prefill
chunks: the larger of the scan's operations over the bf16 peak and its
inputs, outputs and passed state over the HBM peak."""
from benchmark.kernels import granite_hybrid as gh
from benchmark.readers import prefill_chunks, share

KERNEL = r"^pt_ssd_chunk_scan(?!\w)"


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    seconds, calls = tr.ops_matching(KERNEL) if tr is not None else (0, 0)
    chunks = prefill_chunks(ctx)
    if not calls or not chunks:
        return None
    flops = sum(gh.ssd_chunk_flops(cfg, n) for _pos, n, _f in chunks)
    nbytes = sum(gh.ssd_chunk_bytes(cfg, n) for _pos, n, _f in chunks)
    least = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return share(least, seconds)
