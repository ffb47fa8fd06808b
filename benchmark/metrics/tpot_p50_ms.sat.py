"""Median time per output token of the requests that finished in the
window; recorded beside the saturated cell's rate, judges nothing."""


def read(ctx):
    return ctx["facts"].get("tpot_p50_ms")
