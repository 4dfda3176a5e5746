"""Least time of the real tokens swept at true K over the summed device time
of the `lda_gibbs_resample_batched` kernel, in percent (padded token slots
and padded lanes count as no work)."""

from vbench import readers


def read(run):
    return readers.kernel_roofline(run, "lda_gibbs_resample_batched")
