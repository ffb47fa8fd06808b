"""Training: host milliseconds inside one ``TrainStep.__call__`` (argument
lists, dispatch), mean over the window's steps; a harness span."""


def read(ctx):
    f = ctx["facts"]
    calls = [(b - a) * 1e3 for a, b in f["steps"] if a >= f["t_open"]]
    return sum(calls) / len(calls) if calls else None
