"""Engine: XLA backend compiles that ended inside the window (JAX's
``backend_compile_duration`` events). Expected 0: every shape is warmed
(``benchmark/models/mistral4_mla.py:warm``)."""


def read(ctx):
    return ctx["facts"]["window_compiles"]
