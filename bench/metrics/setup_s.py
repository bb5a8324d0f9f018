"""Process start to the first moment of the measured window: loading,
weights, engine or trainer, warm-up and any compiling."""


def read(run):
    return run.setup_s
