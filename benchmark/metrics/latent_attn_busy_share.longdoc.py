"""Kernels: self time of the two latent-attention kernels
(``pt_mla_paged_prefill``, ``pt_mla_paged_decode``) over the device's busy
time in the traced window, in percent. Their projections (``W_dq``,
``W_uq``, ``W_dkv``, the absorbed ``W_uk`` / ``W_uv``, ``W_o``) are XLA
fusions the trace names by number, not by layer, and are NOT in this
share: it is a floor of what latent attention costs."""
from benchmark.readers_mistral4 import (DECODE_KERNEL, PREFILL_KERNEL,
                                        kernel_time)


def read(ctx):
    tr = ctx["trace"]
    times = [kernel_time(ctx, k) for k in (PREFILL_KERNEL, DECODE_KERNEL)]
    if tr is None or not tr.busy_s or not any(times):
        return None
    return 100.0 * sum(t[0] for t in times if t) / tr.busy_s
