"""Model step: device milliseconds of one decode step of the pattern-built
model (its decode program, ``jit_pt_hybrid_decode_chunk``, runs
``decode_chunk`` of them)."""
from benchmark.readers import module_time
from benchmark.readers_granite import DECODE_PROGRAM


def read(ctx):
    t = module_time(ctx, DECODE_PROGRAM)
    if t is None:
        return None
    k = int(ctx["config"]["serving"]["engine"]["decode_chunk"])
    return 1e3 * t[0] / (t[1] * k)
