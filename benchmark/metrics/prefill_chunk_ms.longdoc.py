"""Model step: device milliseconds of one prefill-chunk program of the
pattern-built model (``jit_pt_hybrid_prefill_chunk``), mean over the chunks
in the traced part of the window, every chunk size."""
from benchmark.readers import module_time
from benchmark.readers_granite import PREFILL_PROGRAM


def read(ctx):
    t = module_time(ctx, PREFILL_PROGRAM)
    return None if t is None else 1e3 * t[0] / t[1]
