"""The control and the planted faults of a fit cell, on the chip.

    python3 vbench/control.py --workload <cell> --seeds 1 2 3 [--faults]

Puts the plain reference in the program's place at the cell's own size: the
set-up fit and the warm-up request are reference chains in float32, and the
request that is then compared is answered by

- `control`: the same chain computed in bfloat16, its counts included (the
  configuration states float32 arithmetic; bfloat16 tables are the step a
  later change would be tempted by);
- with `--faults`: `stuck` (the state returned unchanged), `half` (the first
  half of the models left out), `altered` (one token in 64 given another
  topic where it is produced, the counts left as they were).

Each answer goes through the same comparison as a benchmark run, and one
JSON line per seed and case gives its numbers. The benchmark's own runs
never run this; it sets the upper readings of the limits (`PERF.md`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def _answer(case, rf, shape, corp, flat, z_pre, sweeps, key, k):
    """(answer assignments, answer counts) of one case."""
    import jax.numpy as jnp
    import numpy as np

    n = len(z_pre)
    if case == "stuck":
        return z_pre, rf.exact_counts(flat, z_pre, k)
    dtype = jnp.bfloat16 if case == "control" else jnp.float32
    z1, counts = rf.chain(shape, corp, rf.pad_z(shape, z_pre), key, sweeps,
                          dtype)
    z1 = np.asarray(z1)[:n]
    counts = tuple(np.asarray(c.astype(jnp.float32), np.float64)
                   for c in counts)
    if case == "half":
        keep = flat.model < (flat.num_models + 1) // 2
        if flat.num_models == 1:  # one model: leave out half its documents
            keep = flat.docs < flat.num_docs // 2
        z1 = np.where(keep, z_pre, z1)
        counts = rf.exact_counts(flat, z1, k)
    if case == "altered":
        z1 = z1.copy()
        z1[::64] = (z1[::64] + 1) % k
    return z1, counts


def run_seed(name: str, seed: int, cases, overrides=None) -> list:
    import jax
    import numpy as np

    from vbench import corpus, harness

    cell = harness.find_cell(name, overrides=overrides)
    rf = harness.load_module("references", cell.config["reference"])
    run = harness.Run(cell=cell, seed=seed, seconds=0, trace=False,
                      t_start=0)
    m = cell.config["model"]
    k = int(m["num_topics"])
    groups = corpus.generate(cell.config["corpus"], seed)
    flat = rf.flatten([rf.prepare(g, int(cell.config["corpus"]["base_vocab"]))
                       for g in groups])
    shape, corp = rf.device_corpus(flat, m)
    n = len(flat.docs)
    sweeps = int(cell.traffic["request"]["sweeps"])
    z = run.rng(21).integers(0, k, n).astype(np.int32)
    setup = sum(int(s["sweeps"]) for s in cell.traffic["setup"])
    z, _ = rf.chain(shape, corp, rf.pad_z(shape, z),
                    jax.random.PRNGKey(run.derive(22)), setup + sweeps)
    z_pre = np.asarray(z)[:n]
    out = []
    for i, case in enumerate(cases):
        key = jax.random.PRNGKey(run.derive(23, i))
        z1, counts = _answer(case, rf, shape, corp, flat, z_pre, sweeps,
                             key, k)
        err = rf.count_error(counts, rf.exact_counts(flat, z1, k))
        got = rf.chain_numbers(shape, corp, z_pre, z1, sweeps,
                               jax.random.PRNGKey(run.derive(13, 0)))
        got.update(workload=name, seed=seed, case=case, count_err=err)
        out.append(got)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT,
                                                          ".jax_cache")
    for p in (CHECKOUT, os.path.join(CHECKOUT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cases = ["reference", "control"]
    if args.faults:
        cases += ["stuck", "half", "altered"]
    for seed in args.seeds:
        for line in run_seed(args.workload, seed, cases):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
