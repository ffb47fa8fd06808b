"""Kernels: self time of the four attention kernels (``pt_gqa_paged_
{prefill,decode}``, ``pt_swa_{prefill,decode}``) over the device's busy
time in the traced window, in percent. Their projections are XLA fusions
the trace names by number, not by layer, and are NOT in this share."""
from benchmark.readers_mimo import KERNELS, kernel_time


def read(ctx):
    tr = ctx["trace"]
    times = [kernel_time(ctx, k) for k in KERNELS]
    if tr is None or not tr.busy_s or not any(times):
        return None
    return 100.0 * sum(t[0] for t in times if t) / tr.busy_s
