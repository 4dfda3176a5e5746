"""Process start to window start: generation, preparation, fits, compile
or compile-cache loads, and warm-up requests."""


def read(run):
    return run.window[0] - run.t_start
