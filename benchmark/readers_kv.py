"""What the ``kv_walked_share.*`` readers share: the paged decode-attention
kernel's page counters (``serving.kv.pages_walked`` over
``serving.kv.pages_region``, both a decode step and attention layer)."""
from __future__ import annotations

from benchmark.readers_granite import counter_delta


def walked_share(ctx):
    """Pages the decode steps of the window had to read over the pages of
    their layers' regions, in percent; None where the program counts
    neither (it walked the whole region then, whatever it held)."""
    region = counter_delta(ctx, "serving.kv.pages_region")
    if region <= 0:
        return None
    return 100.0 * counter_delta(ctx, "serving.kv.pages_walked") / region
