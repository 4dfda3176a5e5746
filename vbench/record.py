"""Run one benchmark cell once through `vbench/run.py`, and keep what its
result line leaves out.

    python3 vbench/record.py [--keep <dir> | --obs-only] <run.py arguments>

The run is `vbench/run.py`'s own `main`, with the same arguments.

- `--keep <dir>` runs it with `--trace 1` and copies the window's profiler
  trace, without the compiled programs' HLO (`xspace.drop_planes`), to
  `<dir>/<cell>.<seed>.xplane.pb`, and `vbench/phases.py`'s split of that
  trace to `<dir>/<cell>.<seed>.phases.json`.
- `--obs-only` runs it with `--trace 0` and the program's instrumentation
  (`repro.obs`: spans, device waits, counters) enabled from the start, with
  the profiler off: what tracing costs without the profiler.

The last line of standard output is `run.py`'s result line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from vbench import run as vrun  # noqa: E402  (starts run.py's set-up clock)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--keep", default=None)
    mode.add_argument("--obs-only", action="store_true")
    args, rest = ap.parse_known_args(argv)
    named = argparse.ArgumentParser(add_help=False)
    named.add_argument("--workload", default="cell")
    named.add_argument("--seed", default="0")
    cell, _ = named.parse_known_args(rest)
    vrun._environment()
    if args.obs_only:
        from repro import obs

        obs.enable()
        return vrun.main(rest + ["--trace", "0"])
    if not args.keep:
        return vrun.main(rest)

    from vbench import phases, tracing, xspace

    os.makedirs(args.keep, exist_ok=True)
    dest = os.path.join(args.keep, f"{cell.workload}.{cell.seed}.xplane.pb")
    extract = tracing.extract

    def keep(path):
        # The harness deletes the trace once it is read; copy it first.
        xspace.drop_planes(path, dest)
        return extract(path)

    tracing.extract = keep
    try:
        rc = vrun.main(rest + ["--trace", "1"])
    finally:
        tracing.extract = extract
    if rc == 0:
        out = dest[: -len(".xplane.pb")] + ".phases.json"
        with open(out, "w") as f:
            json.dump(phases.split(phases.extract(dest)), f)
        print(f"vbench: phases of the kept trace in {out}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
