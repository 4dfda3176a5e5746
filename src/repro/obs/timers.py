"""Monotonic, device-aware timing helpers.

Two problems with naive `time.time()` deltas in this codebase:

1. `time.time()` is wall clock — NTP steps can make a duration negative
   or wildly inflated. `now()` is `time.perf_counter()`: monotonic,
   highest available resolution, meaningful only as *differences*.
2. JAX dispatch is asynchronous — stopping a timer before the device
   finished measures enqueue time, not compute time. `DeviceTimer.sync()`
   calls `block_until_ready` on the result before reading the clock, so
   kernel/sweep timings are honest.

`DeviceTimer` is also the bridge into the metrics registry: give it a
`Histogram` and labels and the elapsed seconds are observed on stop.
While `repro.obs.config` is disabled the timer skips the sync (preserving
async dispatch — the zero-cost contract) and observes nothing. While
enabled, the wait is a `device.wait` span (`repro.obs.trace`), so a
profiler trace shows where the host blocked on the device — and an
instrumented process syncs once per timed op where an uninstrumented one
does not.
"""

from __future__ import annotations

import time
from typing import Optional

from repro.obs import config, trace
from repro.obs.metrics import Histogram

__all__ = ["now", "DeviceTimer"]


def now() -> float:
    """Monotonic seconds (`perf_counter`); only differences are meaningful."""
    return time.perf_counter()


def _block(value) -> None:
    """`block_until_ready` on whatever jax gives us: a single array, a
    pytree of them, or a host object with no such method (no-op)."""
    if value is None:
        return
    block = getattr(value, "block_until_ready", None)
    if block is not None:
        block()
        return
    try:
        import jax

        jax.block_until_ready(value)
    except Exception:
        pass  # host-only values / jax unavailable: nothing to wait for


class DeviceTimer:
    """Measure a region, waiting out async device work before stopping.

        timer = DeviceTimer(_OP_SECONDS, op="fit", backend=name)
        timer.start()
        result = backend.run(...)
        timer.sync(result)          # block_until_ready, then stop + observe

    `sync()` accepts the value whose readiness defines "done" (an array,
    a state pytree, ...). When obs is disabled the whole object is inert:
    no sync (async dispatch preserved), no observation.
    """

    __slots__ = ("_hist", "_labels", "_t0", "elapsed_s")

    def __init__(self, histogram: Optional[Histogram] = None, **labels):
        self._hist = histogram
        self._labels = labels
        self._t0: Optional[float] = None
        self.elapsed_s: Optional[float] = None

    def start(self) -> "DeviceTimer":
        if config._enabled:
            self._t0 = time.perf_counter()
        return self

    def sync(self, value=None) -> Optional[float]:
        """Wait for `value`'s device work, stop, observe; returns elapsed
        seconds (None when disabled or never started)."""
        if not config._enabled or self._t0 is None:
            return None
        if value is not None:
            with trace.span("device.wait"):
                _block(value)
        self.elapsed_s = time.perf_counter() - self._t0
        self._t0 = None
        if self._hist is not None:
            self._hist.observe(self.elapsed_s, **self._labels)
        return self.elapsed_s

    def stop(self) -> Optional[float]:
        """Stop without waiting on a device value (host-side regions)."""
        return self.sync(None)
