"""Profiler traces: recording a window, and reducing it to device numbers.

`extract` turns a profiler `.xplane.pb` into a compact record: the window
(the `vbench.window` annotation the harness opens around the measured
loop), every device operation on each TPU's "XLA Ops" line that overlaps it,
and the host annotations inside it. `reduce` works on that record alone, so
a test can check it on a recorded trace:

- busy seconds: the union of the device operations' intervals, clipped to
  the window, averaged over the devices used; idle share = 1 - busy/window;
- kernel seconds: the summed durations of a named Pallas kernel's events
  (operation name without its `.N` suffix);
- a breakdown: device operations by self time, and the longest idle gaps,
  each named by the host annotation that was open across it.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "vbench.window"
REQUEST_PREFIX = "vbench."
_OPS_LINE = "XLA Ops"
_NAME = re.compile(r"%?([^\s=]+)")
_SUFFIX = re.compile(r"\.\d+$")


def options():
    import jax

    opts = jax.profiler.ProfileOptions()
    # Host annotations (level 1) stay; Python calls and the runtime's own
    # host events go, which keeps a window's trace to a few MB.
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def op_name(event_name: str) -> str:
    """`%alias_mh_sweep.1 = s32[...] custom-call(...)` -> `alias_mh_sweep`."""
    m = _NAME.match(event_name)
    base = m.group(1) if m else event_name
    return _SUFFIX.sub("", base)


def is_kernel(event_name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in event_name


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def extract(path: str) -> dict:
    """The compact record of one trace file (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], {}
    window = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                devices[plane.name] = [
                    [op_name(e.name), float(e.start_ns), float(e.duration_ns),
                     is_kernel(e.name)]
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(REQUEST_PREFIX):
                        rec = [e.name, float(e.start_ns),
                               float(e.duration_ns)]
                        if e.name == WINDOW:
                            window = rec
                        else:
                            host.append(rec)
    if window is None:
        raise ValueError(f"{path} has no {WINDOW!r} annotation")
    t0, t1 = window[1], window[1] + window[2]
    devices = {
        k: [e for e in evs if e[1] < t1 and e[1] + e[2] > t0]
        for k, evs in devices.items()}
    host = [h for h in host if h[1] < t1 and h[1] + h[2] > t0]
    return {"window_ns": [t0, t1], "devices": devices, "host": host}


def _union(intervals):
    """Sorted, merged [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """Self time of each event: its duration less that of events nested
    inside it (a loop's event spans its body's operations)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    self_t = [e[2] for e in evs]
    stack = []  # indices of open events
    for i, (_name, s, d, _k) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= d
        stack.append(i)
    return [(evs[i][0], max(self_t[i], 0.0), evs[i][3])
            for i in range(len(evs))]


def reduce(rec: dict, kernels=(), top: int = 10) -> dict:
    """Device numbers of one extracted trace record (seconds)."""
    t0, t1 = rec["window_ns"]
    window_s = (t1 - t0) * 1e-9
    busy = []
    kernel_s = {k: 0.0 for k in kernels}
    custom_s = []
    by_op: dict[str, float] = {}
    gaps_all = []
    for evs in rec["devices"].values():
        merged = _union([[max(s, t0), min(s + d, t1)] for _n, s, d, _k in evs])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        custom = 0.0
        for name, s, d, kern in evs:
            clip = (min(s + d, t1) - max(s, t0)) * 1e-9
            if name in kernel_s:
                kernel_s[name] += clip
            if kern:
                custom += clip
        custom_s.append(custom)
        for name, st, _k in _self_times(evs):
            by_op[name] = by_op.get(name, 0.0) + st * 1e-9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps_all.append((a, b))
    n_dev = max(len(rec["devices"]), 1)
    gaps = sorted(gaps_all, key=lambda g: g[0] - g[1])[:top]
    idle = [[_host_at(rec["host"], (a + b) / 2), (b - a) * 1e-9]
            for a, b in gaps]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "kernel_s": {k: v / n_dev for k, v in kernel_s.items()},
        "custom_call_s": sum(custom_s) / n_dev,
        "breakdown": {"device_ops": [[k, v] for k, v in ops],
                      "idle_gaps": idle},
    }


def _host_at(host, t) -> str:
    """Name of the innermost host annotation open at time `t`."""
    best = None
    for name, s, d in host:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "host: between requests"
