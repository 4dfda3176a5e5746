"""Device busy time outside the Pallas kernels (gathers, noise, tables,
count rebuild, padding copies) over device busy time, in percent."""

from vbench import readers


def read(run):
    red = readers.device(run)
    if red is None or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["custom_call_s"] / red["busy_s"])
