"""Least time of the real tokens swept at true K over the summed device time
of the `alias_mh_sweep` kernel, in percent."""

from vbench import readers


def read(run):
    return readers.kernel_roofline(run, "alias_mh_sweep")
