"""Modelled least time of the sweeps completed in the window (the larger of
flops over peak FLOP/s and bytes over peak bytes/s, per `vbench.work`),
over the window, in percent of the chip's peak."""

import sys

from vbench import readers


def read(run):
    if run.peaks is None or run.window_s <= 0:
        return None
    _tokens, w = readers.fit_work(run)
    if w.bytes <= 0:
        return None
    least, bound = w.least_time(run.peaks)
    print(f"fit_mfu bound by {bound}", file=sys.stderr)
    return 100.0 * least / run.window_s
