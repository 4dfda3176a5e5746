"""Where a traced window's time went, by sampler phase, program span and
XLA program.

`tracing.extract` keeps what the accepted metrics read: device operations
by name and the benchmark's own `vbench.` annotations. This module reads
the same `.xplane.pb` with `vbench.xspace` and adds what the program names
itself:

- the phase of each device operation: the first component of its JAX name
  stack (`tf_op`, the primitive itself left out) that is one of `PHASES`,
  the `jax.named_scope`s of the sampler programs (a scope inside `vmap`
  reads `vmap(<scope>)`); else `kernel` for a Pallas kernel
  (`tpu_custom_call`), else `other`;
- the program's spans (`repro.obs.trace.span`, profiler annotations while
  obs is enabled), whose names start with one of `SPAN_PREFIXES`;
- the window's XLA program executions (the device's "XLA Modules" line).

    python3 vbench/phases.py <trace.xplane.pb>

prints the split as one JSON object. Shares of busy time are device self
times (a loop's event less its body's) of the operations clipped to the
window, over the union of the operations' intervals; span shares are host
self times (a span less the spans nested in it) over the window.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from vbench import tracing, xspace  # noqa: E402

PHASES = ("gather", "noise", "alias_tables", "count_rebuild", "perplexity")
SPAN_PREFIXES = ("client.", "server.", "service.", "batch.", "sampler.",
                 "device.")
#: Host-span layers, each the self time of the spans with these prefixes.
LAYERS = {"wire": ("client.", "server."), "service": ("service.",),
          "batch": ("batch.",), "sampler": ("sampler.",),
          "device_wait": ("device.",)}
_MODULES_LINE = "XLA Modules"
_NO_SPAN = "host: between requests"
_HASH = re.compile(r"\(\d+\)$")
_SHAPE = re.compile(r" = \(?([a-z0-9]+\[[0-9,]*\])")


def phase(tf_op: str, kernel: bool) -> str:
    """The phase of one device operation from its `tf_op` name stack."""
    for part in tf_op.rstrip(":").split("/")[:-1]:
        for name in re.split(r"[()]", part):
            if name in PHASES:
                return name
    return "kernel" if kernel else "other"


def kind(name: str, stats: dict) -> str:
    """`<hlo_category> <output shape>` of one device operation, from its
    metadata (`custom fusion f32[600000]`): what an operation is where it
    has no `tf_op` to name its phase."""
    m = _SHAPE.search(name)
    return f"{stats.get('hlo_category', '?')} {m.group(1) if m else '?'}"


def _ns(line, offset_ps: int, duration_ps: int) -> tuple[float, float]:
    """Start and duration in whole nanoseconds, as `ProfileData` gives
    them (so operations match `tracing.extract`'s exactly)."""
    return (float(line.timestamp_ns + offset_ps // 1000),
            float(duration_ps // 1000))


def extract(path: str) -> dict:
    """`tracing.extract`'s record of one trace, with program spans among
    `host`, and two keys more: `phases` (the phase of each operation of
    `devices`, in the same order) and `modules` (each device's XLA program
    executions `[name, start_ns, duration_ns]` in the window), and
    `kinds`, the `kind` of each operation of `devices`."""
    planes = xspace.read(path)
    window, host, devices, phases, modules = None, [], {}, {}, {}
    kinds = {}
    keep = (tracing.REQUEST_PREFIX,) + SPAN_PREFIXES
    for plane in planes:
        md = plane.event_metadata
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                evs = [(md.get(m, ("", {})), *_ns(line, off, dur))
                       for m, off, dur in line.events]
                if line.name == _MODULES_LINE:
                    modules[plane.name] = [
                        [_HASH.sub("", name), s, d] for (name, _st), s, d
                        in evs]
                elif line.name == tracing._OPS_LINE:
                    devices[plane.name] = [
                        [tracing.op_name(name), s, d,
                         tracing.is_kernel(name)]
                        for (name, _st), s, d in evs]
                    phases[plane.name] = [
                        phase(st.get("tf_op", ""), tracing.is_kernel(name))
                        for (name, st), _s, _d in evs]
                    kinds[plane.name] = [kind(name, st)
                                         for (name, st), _s, _d in evs]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for m, off, dur in line.events:
                    name = md.get(m, ("", {}))[0]
                    if not name.startswith(keep):
                        continue
                    rec = [name, *_ns(line, off, dur)]
                    if name == tracing.WINDOW:
                        window = rec
                    else:
                        host.append(rec)
    if window is None:
        raise ValueError(f"{path} has no {tracing.WINDOW!r} annotation")
    t0, t1 = window[1], window[1] + window[2]

    def inside(e):
        return e[1] < t1 and e[1] + e[2] > t0

    for k in devices:
        keep_ = [inside(e) for e in devices[k]]
        devices[k], phases[k], kinds[k] = (
            [x for x, ok in zip(xs, keep_) if ok]
            for xs in (devices[k], phases[k], kinds[k]))
    return {"window_ns": [t0, t1], "devices": devices,
            "host": [h for h in host if inside(h)], "phases": phases,
            "kinds": kinds,
            "modules": {k: [m for m in v if inside(m)]
                        for k, v in modules.items()}}


def _innermost(host, t0, t1):
    """[start, end, name] pieces of [t0, t1], each named by the innermost
    host event open across it (the program's spans nest on one thread)."""
    out, stack, t = [], [], t0

    def emit(upto):
        nonlocal t
        if upto > t:
            out.append([t, upto, stack[-1][0] if stack else _NO_SPAN])
            t = upto

    for name, s, d in sorted(host, key=lambda h: (h[1], -h[2])):
        while stack and stack[-1][1] <= s:
            emit(min(stack[-1][1], t1))
            stack.pop()
        emit(min(max(s, t0), t1))
        stack.append((name, s + d))
    while stack:
        emit(min(stack[-1][1], t1))
        stack.pop()
    emit(t1)
    return out


def _by_name(gaps, pieces) -> dict:
    """Length of the sorted gaps under each piece's name."""
    out: dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
            out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + hi - lo
            k += 1
    return out


def _program_at(modules):
    """start_ns -> name of the XLA program running then ("" if none)."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]

    def at(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < mods[i][1] + mods[i][2]:
            return mods[i][0]
        return ""
    return at


def split(rec: dict) -> dict:
    """The window's time by phase, span layer and XLA program (seconds,
    and percent of busy or of the window)."""
    t0, t1 = rec["window_ns"]
    window_s = (t1 - t0) * 1e-9
    n_dev = max(len(rec["devices"]), 1)
    phase_s: dict[str, float] = {}
    other_s: dict[str, float] = {}  # `other` self time by XLA program
    other_op: dict[tuple, float] = {}  # ... by program and operation kind
    busy_ns, idle = 0.0, []
    for k, evs in rec["devices"].items():
        program = _program_at(rec["modules"].get(k, []))
        kinds = rec.get("kinds", {}).get(k) or ["?"] * len(evs)
        clipped = [[(p, program(s), kd), max(s, t0),
                    min(s + d, t1) - max(s, t0), kern]
                   for (_n, s, d, kern), p, kd
                   in zip(evs, rec["phases"][k], kinds)]
        for (p, prog, kd), st, _k in tracing._self_times(clipped):
            phase_s[p] = phase_s.get(p, 0.0) + st * 1e-9 / n_dev
            if p == "other":
                other_s[prog] = other_s.get(prog, 0.0) + st * 1e-9 / n_dev
                other_op[prog, kd] = (other_op.get((prog, kd), 0.0)
                                      + st * 1e-9 / n_dev)
        merged = tracing._union([[s, s + d] for _p, s, d, _k in clipped])
        busy_ns += sum(e - s for s, e in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        idle += [[a, b] for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    busy_s = busy_ns * 1e-9 / n_dev

    self_t = tracing._self_times([[n, s, d, False] for n, s, d in
                                  rec["host"]])
    layer_s = {layer: sum(st for n, st, _k in self_t if n.startswith(pre))
               * 1e-9 for layer, pre in LAYERS.items()}
    idle = tracing._union(idle)
    idle_ns = sum(b - a for a, b in idle)
    idle_by = _by_name(idle, _innermost(rec["host"], t0, t1))
    under_span = sum(v for n, v in idle_by.items()
                     if n.startswith(SPAN_PREFIXES))
    requests = sum(1 for n, _s, _d in rec["host"]
                   if n.startswith(tracing.REQUEST_PREFIX))
    programs: dict[str, int] = {}
    program_s: dict[str, float] = {}
    for mods in rec["modules"].values():
        for name, s, d in mods:
            programs[name] = programs.get(name, 0) + 1
            program_s[name] = program_s.get(name, 0.0) + d * 1e-9
    n_programs = sum(programs.values())

    def pct(x, base):
        return 100.0 * x / base if base > 0 else None

    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "phase_s": phase_s,
        "phase_share": {p: pct(v, busy_s) for p, v in phase_s.items()},
        "layer_s": layer_s,
        "layer_share": {k: pct(v, window_s) for k, v in layer_s.items()},
        "other_by_program": sorted(([n, v] for n, v in other_s.items()),
                                   key=lambda x: -x[1])[:15],
        "other_by_op": sorted(([p, kd, v] for (p, kd), v in other_op.items()),
                              key=lambda x: -x[2])[:15],
        "idle_s": idle_ns * 1e-9 / n_dev,
        "idle_under_program_span_share": pct(under_span, idle_ns),
        "idle_by_span": sorted(([n, v * 1e-9 / n_dev, pct(v, idle_ns)]
                                for n, v in idle_by.items()),
                               key=lambda x: -x[1])[:15],
        "requests": requests,
        "programs": n_programs,
        "programs_per_request": (n_programs / n_dev / requests
                                 if requests else None),
        "top_programs": sorted(
            ([n, c, program_s[n]] for n, c in programs.items()),
            key=lambda x: -x[1])[:15],
    }


def span_share(run, prefixes) -> float | None:
    """Self time of the program's spans named with `prefixes` that started
    in the run's window, over the window, in percent: read from the
    program's own span buffer (`repro.obs.trace`, host clock), which a
    traced run fills. None where the program opened no such span."""
    from repro.obs import trace

    t0, t1 = run.window
    inside = [[s.name, s.start_s, s.duration_s, False] for s in trace.spans()
              if t0 <= s.start_s < t1]
    got = [st for n, st, _k in tracing._self_times(inside)
           if n.startswith(prefixes)]
    if not got or run.window_s <= 0:
        return None
    return 100.0 * sum(got) / run.window_s


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[2].strip(), file=sys.stderr)
        return 2
    rec = extract(args[0])
    out = split(rec)
    out["breakdown"] = tracing.reduce(rec)["breakdown"]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
