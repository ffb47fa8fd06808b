"""Engine: XLA backend compiles that ended inside the window (JAX's
``backend_compile_duration`` events). Expected 0: every shape is warmed."""


def read(ctx):
    return ctx["facts"]["window_compiles"]
