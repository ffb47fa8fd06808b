"""Training: host milliseconds of one ``TrainStep.__call__`` spent
building the argument lists: parameters, optimizer states,
hyper-parameters, the step's key. The median of the program's
``jit.train_step.args_ms`` histogram, read from its registry after the run
(it outlives the driver's release; the median is untouched by the first,
compiling call and the three checked steps)."""


def read(ctx):
    from paddle_tpu.profiler import stats

    return stats.histogram("jit.train_step.args_ms").percentile(0.5)
