"""Benchmark aggregator: `PYTHONPATH=src python -m benchmarks.run [--full]`.

One benchmark per paper table/figure/claim (DESIGN.md §8), plus the
roofline renderer over the dry-run artifacts. Default is the quick profile
(CPU-friendly); --full runs the paper-scale settings.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

BENCHES = [
    ("sampler", "sampler throughput (paper §2.4/§4.3, §5 latency)",
     "benchmarks.sampler_bench"),
    ("perplexity", "RLDA vs LDA quality (paper §3.1/§6)",
     "benchmarks.perplexity_bench"),
    ("verification", "Eq.(6) verification surface (paper §2.5.1)",
     "benchmarks.verification_bench"),
    ("marketplace", "marketplace economics (paper §2.5.2-4)",
     "benchmarks.marketplace_bench"),
    ("coreset", "core-set topic reduction (paper §3.3)",
     "benchmarks.coreset_bench"),
    ("views", "build_view serving path (strip_rating hoist note)",
     "benchmarks.views_bench"),
    ("delta_view", "delta vs full view payload bytes (paper §4.2)",
     "benchmarks.delta_view_bench"),
    ("stream", "streaming ingest throughput / staleness / refit economics",
     "benchmarks.stream_bench"),
    ("batch", "batched multi-model fit engine vs sequential fits",
     "benchmarks.batch_bench"),
    ("alias", "AliasLDA fused path vs the legacy sweep (large-fit gate)",
     "benchmarks.alias_bench"),
    ("offload", "Chital offload tier: server sweep-work eliminated (§2.5)",
     "benchmarks.offload_bench"),
    ("distributed", "pserver fit tier: weak scaling + sparse sync bytes",
     "benchmarks.distributed_bench"),
    ("obs", "observability overhead gates + end-to-end trace export",
     "benchmarks.obs_bench"),
    ("roofline", "roofline terms from the dry-run (deliverable g)",
     "benchmarks.roofline"),
]


def _run_context() -> dict:
    """Who/what produced this summary — what makes perf trajectories
    comparable (or knowably incomparable) across runner classes."""
    import platform

    ctx = {
        "host": platform.node(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "cpu_count": os.cpu_count(),
    }
    try:
        import jax

        ctx.update({
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "device_kind": jax.devices()[0].device_kind,
        })
    except Exception as e:  # context must never fail the bench run
        ctx["jax_error"] = repr(e)
    return ctx


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slower)")
    ap.add_argument("--only", default="", help="comma-separated bench names")
    ap.add_argument("--outdir", default="experiments/bench")
    args = ap.parse_args(argv)

    valid = [name for name, _, _ in BENCHES]
    only = set(filter(None, args.only.split(","))) if args.only else None
    if only:
        unknown = sorted(only - set(valid))
        if unknown:
            # A typo must not masquerade as a clean run of zero benches.
            print(f"error: unknown bench name(s) {unknown}; "
                  f"valid names: {valid}", file=sys.stderr)
            sys.exit(2)
    os.makedirs(args.outdir, exist_ok=True)
    t_start = time.time()
    failures = []
    results = {}
    for name, desc, module in BENCHES:
        if only and name not in only:
            continue
        print(f"\n=== {name}: {desc} ===")
        t0 = time.time()
        try:
            import importlib

            mod = importlib.import_module(module)
            result = mod.run(quick=not args.full)
            result = {"bench": name, "wall_s": round(time.time() - t0, 1),
                      **(result or {})}
            with open(os.path.join(args.outdir, f"{name}.json"), "w") as f:
                json.dump(result, f, indent=1)
            results[name] = result
            print(f"  [{name}] done in {result['wall_s']}s")
        except Exception as e:
            failures.append((name, repr(e)))
            print(f"  [{name}] FAILED: {e}")
            traceback.print_exc()

    # One artifact per run: the perf trajectory reads summary.json, not N
    # scattered per-bench files.
    summary = {
        "profile": "full" if args.full else "quick",
        "requested": sorted(only) if only else valid,
        "wall_s": round(time.time() - t_start, 1),
        "context": _run_context(),
        "failures": failures,
        "benches": results,
    }
    with open(os.path.join(args.outdir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)

    print()
    if failures:
        print(f"{len(failures)} benchmark(s) failed: {failures}")
        sys.exit(1)
    print(f"all benchmarks passed; results in {args.outdir}/ "
          f"(aggregate: {os.path.join(args.outdir, 'summary.json')})")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()  # a process setting: the entry point owns it
    main()
