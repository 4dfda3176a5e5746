"""Run one benchmark cell once, on the chip this process finds.

    python3 vbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up (set-up), measures for `--seconds`, checks what the timed
path produced against the plain reference, and prints one JSON line last on
standard output: `correct`, `attempted`, `failed`, `metrics`, `device`, with
`--trace 1` also `breakdown`. With no TPU, or fewer chips than the cell asks
for, it exits 2 before any set-up and prints no result. JAX's persistent
compile cache lives at `<checkout>/.jax_cache`.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def _environment() -> None:
    """Caches and logs go inside the checkout or under TMPDIR only."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT,
                                                          ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "vbench_tpu_logs"))
    for p in (CHECKOUT, os.path.join(CHECKOUT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _environment()
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"vbench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 3
    import jax

    from repro.launch import compile_cache
    from vbench import harness

    try:
        harness.find_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"vbench: {e}", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compile_cache.enable()
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"vbench: {e}; nothing was run", file=sys.stderr)
        return 2
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
