"""Real corpus tokens x sweeps of every fit request completed in the window,
over the window's seconds (padding does not count)."""

from vbench import readers


def read(run):
    tokens, _ = readers.fit_work(run)
    if run.window_s <= 0 or tokens <= 0:
        return None
    return tokens / run.window_s
