"""Arithmetic the metric readers share (each reader is a file of its own
under `vbench/metrics/`, found by the metric's name)."""

from __future__ import annotations

from vbench import tracing, work

def device(run):
    """The reduced device trace of the window, or None without a trace or
    without a device plane in it."""
    if run.device_trace is None or not run.device_trace["devices"]:
        return None
    red = run.notes.get("reduced")
    if red is None:
        red = run.notes["reduced"] = tracing.reduce(run.device_trace)
    return red


def fit_work(run):
    """(real tokens x sweeps, modelled work) of the window's fit requests."""
    done = [r for r in run.requests if r.ok]
    total = work.ZERO
    for r in done:
        if r.work is not None:
            total = total + r.work
    return sum(r.token_sweeps for r in done), total


def token_work(run):
    """The per-token part of the window's modelled work."""
    k = int(run.cell.config["model"]["num_topics"])
    tokens, _ = fit_work(run)
    return work.token_work(int(tokens), k)


def idle_share(run):
    red = device(run)
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def kernel_roofline(run, kernel: str):
    """Least time of the real tokens the window swept, at true K, over the
    kernel's summed device time, in percent; None where it never ran."""
    if device(run) is None or run.peaks is None:
        return None
    t = tracing.reduce(run.device_trace, (kernel,))["kernel_s"][kernel]
    if t <= 0:
        return None
    least, _bound = token_work(run).least_time(run.peaks)
    return 100.0 * least / t


def counter(run, name: str) -> float:
    """Sum of a counter's deltas over the window, over all label sets."""
    return sum(v for k, v in run.counters.items()
               if k.split("{")[0] == name and not isinstance(v, dict))


def compiles_in_window(run) -> int:
    t0, t1 = run.window
    return sum(1 for t, _e, _d in run.compiles if t0 <= t <= t1)

