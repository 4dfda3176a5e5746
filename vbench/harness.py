"""One run of one benchmark cell: set up, measure, check, report.

Everything that belongs to one configuration, traffic mix, verb, metric,
route or reference lives in a file of its own that this module finds by the
name `BENCHMARK.json` gives it:

    vbench/configs/<config>.json      sizes, source, cuts, reference name
    vbench/traffic/<traffic>.json     set-up, warm-up, request, route
    vbench/verbs/<verb>.py            set-up steps and requests
    vbench/routes/<backend>.py        the route a cell must take on the chip
    vbench/references/<ref>.py        the plain reference and its comparison
    vbench/limits/<workload>.json     the limit of each compared number
    vbench/metrics/<metric>.py        one reader per metric; a metric
                                      `<quantity>.<cell kind>` without a
                                      file of its own is read by
                                      `vbench/metrics/<quantity>.py`

A reader takes the finished `Run` and returns a number, or None when it
finds nothing to read (the metric is then left out of the result line).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


_MODULES: dict[str, Any] = {}


def load_module(kind: str, name: str):
    """`vbench/<kind>/<name>.py`, loaded by path (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if path not in _MODULES:
        if not os.path.exists(path):
            raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
        spec = importlib.util.spec_from_file_location(
            f"vbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def metric_reader(name: str):
    """The reader of metric `name`: `metrics/<name>.py`, or else, for a
    name split per kind of cell (`fit_mfu.refit`), the quantity's shared
    reader `metrics/<quantity>.py`."""
    try:
        return load_module("metrics", name)
    except FileNotFoundError:
        if "." not in name:
            raise
        return load_module("metrics", name.split(".", 1)[0])


def _merge(base: dict, over: Optional[dict]) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


@dataclasses.dataclass
class Cell:
    """A workload entry with everything its names point at."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def find_cell(name: str, bench: Optional[dict] = None,
              overrides: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_json(CHECKOUT,
                                                      "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    overrides = overrides or {}
    config = _merge(load_json(HERE, "configs", w["config"] + ".json"),
                    overrides.get("config"))
    traffic = _merge(load_json(HERE, "traffic", w["traffic"] + ".json"),
                     overrides.get("traffic"))
    limits = _merge(load_json(HERE, "limits", name + ".json"),
                    overrides.get("limits"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    moved = {m["name"] for m in e2e}
    pl = [m for m in bench["per_layer"]
          if (name in m["workloads"] if "workloads" in m
              else m["moves"] in moved)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e, per_layer=pl)


@dataclasses.dataclass
class Request:
    """One request as the load generator saw it (host clock, seconds)."""

    verb: str
    sent: float
    done: float = math.inf
    ok: bool = False
    # Real tokens x sweeps of a fit request; 0 for reads.
    token_sweeps: float = 0.0
    work: Any = None  # `work.Work` of a fit request, None for reads
    error: str = ""


@dataclasses.dataclass
class Run:
    """State of one run, shared by verbs, readers and the reference."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    device: Any = None
    peaks: Optional[dict] = None
    client: Any = None
    groups: list = dataclasses.field(default_factory=list)
    handles: list = dataclasses.field(default_factory=list)
    backend: str = ""
    setup_parts: dict = dataclasses.field(default_factory=dict)
    compiles: list = dataclasses.field(default_factory=list)
    requests: list = dataclasses.field(default_factory=list)
    answers: list = dataclasses.field(default_factory=list)
    window: tuple = (0.0, 0.0)
    counters: dict = dataclasses.field(default_factory=dict)
    device_trace: Optional[dict] = None
    memory_peak_bytes: int = 0
    notes: dict = dataclasses.field(default_factory=dict)

    # -- seeds -----------------------------------------------------------

    def derive(self, *tags: int) -> int:
        """A 31-bit seed for the program, from the run seed and tags."""
        ss = np.random.SeedSequence([int(self.seed) % 2**63, *tags])
        return int(ss.generate_state(1)[0] % (2**31 - 1))

    def rng(self, *tags: int) -> np.random.Generator:
        return np.random.default_rng(
            np.random.SeedSequence([int(self.seed) % 2**63, *tags]))

    # -- the program's state ---------------------------------------------

    @property
    def service(self):
        return self.client.server.service

    def capture(self) -> None:
        """Keep (without copying) every served model's assignments."""
        hs = self.service.handles
        self.answers.append([hs[h].state.z for h in self.handles])

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _log(run: Run, what: str) -> None:
    """A progress line on standard error (a run cut short still says where
    it was)."""
    print(f"vbench: {what} at {time.perf_counter() - run.t_start:.1f} s "
          f"({len(run.compiles)} compile events)", file=sys.stderr,
          flush=True)


def _compile_listener(run: Run):
    def on(event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            run.compiles.append((time.perf_counter(), event, duration))
    return on


def _counters() -> dict:
    from repro.obs import metrics

    out = {}
    for name, m in metrics.snapshot().items():
        for s in m["series"]:
            key = name + json.dumps(s["labels"], sort_keys=True)
            out[key] = ({"sum": s["sum"], "count": s["count"]}
                        if "count" in s else s.get("value", 0.0))
    return out


def _delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        b = before.get(k)
        if isinstance(v, dict):
            b = b or {"sum": 0.0, "count": 0}
            out[k] = {"sum": v["sum"] - b["sum"],
                      "count": v["count"] - b["count"]}
        else:
            out[k] = v - (b or 0.0)
    return out


def _device(run: Run, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < run.cell.chips):
        raise NoAccelerator(
            f"cell {run.cell.name} needs {run.cell.chips} TPU chip(s); "
            f"JAX sees {len(devs)} x {devs[0].platform}")
    run.device = devs[0]
    if require_tpu:
        from vbench.peaks import peaks_for

        run.peaks = peaks_for(devs[0].device_kind)


def _setup(run: Run, require_tpu: bool) -> None:
    from vbench import corpus

    t = time.perf_counter()
    run.groups = corpus.generate(run.cell.config["corpus"], run.seed)
    run.setup_parts["generate_s"] = time.perf_counter() - t
    _log(run, "corpus generated")
    from repro.api import VedaliaClient

    run.client = VedaliaClient()
    for i, step in enumerate(run.cell.traffic["setup"]):
        t = time.perf_counter()
        load_module("verbs", step["verb"]).setup(run, step, i)
        run.setup_parts[f"{step['verb']}_s"] = time.perf_counter() - t
        _log(run, f"set-up step {step['verb']} done")
    route = run.cell.traffic["route"]
    if run.backend != route["backend"]:
        raise AssertionError(
            f"{run.cell.name}: route resolved to {run.backend}, "
            f"expected {route['backend']}")
    if require_tpu:
        load_module("routes", route["backend"]).check(run, route)
    # Warm-up: every program the window will run, compiled or loaded from
    # the cache before the window opens.
    warm = run.cell.traffic["warmup"]
    verb = load_module("verbs", warm["verb"])
    t = time.perf_counter()
    for i in range(int(warm["requests"])):
        r = verb.request(run, warm, -1 - i)
        if not r.ok:
            raise RuntimeError(f"warm-up request failed: {r.error}")
    run.setup_parts["warmup_s"] = time.perf_counter() - t
    _log(run, "warm-up done")


def _closed_loop(run: Run, req: dict) -> None:
    """One client: the next request is sent when the last one returns."""
    verb = load_module("verbs", req["verb"])
    deadline = run.window[0] + run.seconds
    i = 0
    while time.perf_counter() < deadline:
        run.requests.append(verb.request(run, req, i))
        i += 1


def _window(run: Run) -> None:
    import jax

    req = run.cell.traffic["request"]
    trace_dir = None
    before = {}
    if run.trace:
        from repro import obs
        from vbench import tracing

        obs.enable()
        before = _counters()
        trace_dir = tempfile.mkdtemp(prefix="vbench_trace_")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tracing.options())
    run.notes["first_answer"] = len(run.answers)
    try:
        with jax.profiler.TraceAnnotation("vbench.window"):
            run.window = (time.perf_counter(), 0.0)
            _closed_loop(run, req)
            ends = [r.done for r in run.requests if r.ok] or [
                time.perf_counter()]
            run.window = (run.window[0], max(max(ends), run.window[0]))
    finally:
        if run.trace:
            jax.profiler.stop_trace()
            from repro import obs

            run.counters = _delta(_counters(), before)
            obs.disable()
    if trace_dir is not None:
        from vbench import tracing

        try:
            run.device_trace = tracing.extract(tracing.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _memory_peak(run: Run) -> int:
    """The allocator's peak of live buffers plus its peak reservation for
    the temporary buffers of running programs: on a TPU a program's temps
    are reserved apart and never show in `peak_bytes_in_use`."""
    stats = run.device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(
        stats.get("peak_bytes_reserved", 0))


def _metrics(run: Run, specs: list) -> dict:
    out = {}
    for m in specs:
        value = metric_reader(m["name"]).read(run)
        if value is None:
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_tpu: bool = True,
             overrides: Optional[dict] = None,
             bench: Optional[dict] = None) -> dict:
    """One run of cell `name`; returns the result line's object."""
    import jax

    cell = find_cell(name, bench, overrides)
    run = Run(cell=cell, seed=int(seed), seconds=float(seconds),
              trace=bool(trace),
              t_start=time.perf_counter() if t_start is None else t_start)
    _device(run, require_tpu)
    jax.monitoring.register_event_duration_secs_listener(
        _compile_listener(run))
    _setup(run, require_tpu)
    _window(run)
    _log(run, f"window closed after {len(run.requests)} requests")
    run.memory_peak_bytes = _memory_peak(run)
    run.setup_parts["setup_s"] = run.window[0] - run.t_start
    run.setup_parts["compile_s"] = sum(
        d for t, _e, d in run.compiles if t < run.window[0])

    # Readers of host numbers and of the trace run before the program's
    # state is freed; the reference runs after, in the memory it leaves.
    specs = cell.per_layer if run.trace else cell.end_to_end
    metrics = _metrics(run, specs)
    reference = load_module("references", cell.config["reference"])
    checks = reference.check(run)
    run.client = None
    gc.collect()
    checks += reference.compare(run)
    _log(run, "reference compared")
    correct = bool(checks) and all(
        c["value"] <= c["limit"] for c in checks)
    failed = sum(1 for r in run.requests if not r.ok)
    correct = correct and failed == 0
    dev = {"platform": run.device.platform, "kind": run.device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": correct, "attempted": len(run.requests),
           "failed": failed, "metrics": metrics, "device": dev}
    if run.device_trace is not None:
        from vbench import tracing

        red = tracing.reduce(run.device_trace)
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        out["breakdown"] = red["breakdown"]
    out["setup"] = run.setup_parts
    out["readings"] = run.notes.get("readings", [])
    out["checks"] = checks
    return out


def report(out: dict) -> None:
    """Standard error ends with each compared number beside its limit; the
    last line of standard output is the result, `checks` last in it."""
    for k, v in out.get("setup", {}).items():
        print(f"setup {k} {v}", file=sys.stderr)
    for r in out.get("readings", []):
        print("reading " + " ".join(f"{k} {v}" for k, v in r.items()),
              file=sys.stderr)
    for c in out["checks"]:
        print(f"check {c['name']} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    checks = out.pop("checks")
    out["checks"] = checks
    print(json.dumps(out), flush=True)
