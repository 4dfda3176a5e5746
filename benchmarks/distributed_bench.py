"""Parameter-server fit tier benchmark (paper §2.2 scale-out, PR 7).

The pserver tier's pitch is three claims, and this bench gates all of
them on a simulated host mesh (the real pod topology shrunk onto forced
host devices — `--xla_force_host_platform_device_count` must be set
before jax initializes, so the measured body runs in a subprocess
worker, exactly like the multi-device tests):

  correctness   at mesh size 1 the tier IS the jnp oracle, bit for bit,
                from identical keys (gate: exact);
  weak scaling  4 workers fitting 4x the tokens should cost about what 1
                worker fitting 1x costs. Forced host devices timeshare
                one machine, so wall-clock is work-normalized:
                eff = min(1, W * T_1 / T_W)
                (gate: >= 0.7 — the shard_map program may not burn >30%
                in sync collectives / padding overhead);
  sync bytes    per-sync traffic is O(cap) support rows, not the O(V)
                full-table all-reduce of the replicated oracle tier
                (gate: strictly below at the same worker count, reported
                as the higher-is-better `sync_bytes_saving` ratio);
  staleness     syncing every 2nd sweep on a (2, 2) doc x vocab mesh
                stays within 2% averaged held-out perplexity of the jnp
                oracle (gate: <= 0.02).

Reported to the perf trajectory: `weak_scaling_efficiency` and
`sync_bytes_saving` (both ratios, higher is better).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_WORKER_DEVICES = 4


def _worker(quick: bool) -> dict:
    """Measured body; runs under _WORKER_DEVICES forced host devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import gibbs, perplexity
    from repro.core.types import Corpus, LDAConfig
    from repro.pserver.sampler import PServerFit
    from repro.pserver.sync import (
        replicated_sync_bytes_per_device,
        sync_bytes_per_device,
    )

    assert jax.device_count() == _WORKER_DEVICES
    k = 16
    # Large vocab + Zipf word marginal: per-worker support cap stays well
    # under V, which is where the sparse delta exchange earns its bytes.
    v = 20_000
    n_per = 20_000 if quick else 80_000
    d_per = 50
    sweeps = 4 if quick else 8

    def zipf_corpus(n, d, seed):
        r = np.random.default_rng(seed)
        w = r.zipf(1.3, size=4 * n) - 1
        w = w[w < v][:n].astype(np.int32)
        assert len(w) == n
        return Corpus(docs=jnp.asarray(np.sort(r.integers(0, d, n))
                                       .astype(np.int32)),
                      words=jnp.asarray(w),
                      weights=jnp.ones(n, jnp.float32))

    def lda_corpus(n, d, vq, kq, seed):
        # Planted, well-separated topics (90% of each topic's mass on its
        # own vocab block): chains recover the same structure, so held-out
        # perplexity is a stable quality probe (uniform corpora drown in
        # overfit noise; sparse random topics are multi-modal).
        r = np.random.default_rng(seed)
        blk = vq // kq
        phi = np.full((kq, vq), 0.1 / vq)
        for t in range(kq):
            phi[t, t * blk:(t + 1) * blk] += (
                0.9 * r.dirichlet(np.full(blk, 0.5)))
        phi /= phi.sum(1, keepdims=True)
        theta_c = r.dirichlet(np.full(kq, 0.3), size=d).cumsum(1)
        docs = r.integers(0, d, n).astype(np.int32)
        zt = (r.random(n)[:, None] > theta_c[docs]).sum(1)
        w = np.empty(n, np.int64)
        for t in range(kq):
            m = zt == t
            w[m] = np.searchsorted(phi[t].cumsum(), r.random(m.sum()))
        return Corpus(docs=jnp.asarray(docs),
                      words=jnp.asarray(np.minimum(w, vq - 1)
                                        .astype(np.int32)),
                      weights=jnp.ones(n, jnp.float32))

    def mesh_of(shape):
        ndev = int(np.prod(shape))
        return jax.sharding.Mesh(
            np.array(jax.devices()[:ndev]).reshape(shape),
            ("data", "model"))

    def timed_fit(mesh, corpus, num_docs, staleness=1):
        cfg = LDAConfig(num_topics=k, vocab_size=v, num_docs=num_docs)
        ps = PServerFit(mesh=mesh, staleness=staleness, local="gibbs")
        ps.run(cfg, corpus, jax.random.PRNGKey(0), 1)  # compile + plan
        t0 = time.perf_counter()
        st = ps.run(cfg, corpus, jax.random.PRNGKey(1), sweeps)
        jax.block_until_ready(st.n_wt)
        return time.perf_counter() - t0, ps, cfg, st

    # -- claim 1: mesh-1 bit-exactness vs the oracle ------------------------
    small = zipf_corpus(4096, 40, 7)
    cfg_s = LDAConfig(num_topics=8, vocab_size=v, num_docs=40)
    ps1 = PServerFit(mesh=mesh_of((1, 1)), local="gibbs")
    st_ps = ps1.run(cfg_s, small, jax.random.PRNGKey(3), 3)
    st_or = gibbs.run(cfg_s, small, jax.random.PRNGKey(3), 3)
    bit_exact = all(
        np.array_equal(np.asarray(getattr(st_ps, f)),
                       np.asarray(getattr(st_or, f)))
        for f in ("z", "n_dt", "n_wt", "n_t"))

    # -- claim 2: work-normalized weak scaling 1 -> 4 data shards -----------
    t1, *_ = timed_fit(mesh_of((1, 1)), zipf_corpus(n_per, d_per, 1),
                       d_per)
    big = zipf_corpus(4 * n_per, 4 * d_per, 2)
    t4, ps4, cfg4, _ = timed_fit(mesh_of((4, 1)), big, 4 * d_per)
    eff = min(1.0, _WORKER_DEVICES * t1 / t4)

    # -- claim 3: per-sync bytes vs the replicated oracle tier --------------
    plan = ps4._plan(cfg4, big)
    ps_bytes = sync_bytes_per_device(plan.n_workers, plan.cap, k)
    repl_bytes = replicated_sync_bytes_per_device(plan.n_workers, v, k)
    saving = repl_bytes / max(ps_bytes, 1)

    # -- claim 4: staleness-2 held-out parity on a (2, 2) mesh --------------
    n_q, d_q, v_q, k_q = 8000, 61, 120, 6
    full = lda_corpus(n_q, d_q, v_q, k_q, 5)
    cut = n_q // 5
    hold = Corpus(docs=full.docs[:cut], words=full.words[:cut],
                  weights=full.weights[:cut])
    train = Corpus(docs=full.docs[cut:], words=full.words[cut:],
                   weights=full.weights[cut:])
    cfg_q = LDAConfig(num_topics=k_q, vocab_size=v_q, num_docs=d_q)
    warm_sweeps, meas_sweeps, chk = 60, 36, 6

    # Shared oracle warm start: both branches fork from one mode, so the
    # measured gap is the cost of staleness, not of mode selection.
    st_warm = gibbs.run(cfg_q, train, jax.random.PRNGKey(9), warm_sweeps)

    def avg_heldout(run_fn, off):
        st, ppxs = st_warm, []
        for i in range(meas_sweeps // chk):
            st = run_fn(st, jax.random.PRNGKey(off + i))
            if (i + 1) * chk >= meas_sweeps // 2:
                ppxs.append(perplexity.perplexity(cfg_q, st, hold))
        return float(np.mean(ppxs))

    ps22 = PServerFit(mesh=mesh_of((2, 2)), staleness=2, local="gibbs")
    p_stale = avg_heldout(
        lambda st, key: ps22.run(cfg_q, train, key, chk, state=st), 100)
    p_oracle = avg_heldout(
        lambda st, key: gibbs.run(cfg_q, train, key, chk, state=st), 200)
    ppx_gap = abs(p_stale - p_oracle) / p_oracle

    return {
        "devices": _WORKER_DEVICES,
        "bit_exact_mesh1": bool(bit_exact),
        "weak_scaling": {"t_1worker_s": round(t1, 3),
                         "t_4worker_4x_s": round(t4, 3)},
        "weak_scaling_efficiency": round(eff, 4),
        "sync_bytes": {"pserver_per_device": ps_bytes,
                       "replicated_per_device": repl_bytes,
                       "support_cap": int(plan.cap), "vocab": v},
        "sync_bytes_saving": round(saving, 3),
        "heldout": {"pserver_stale2": round(p_stale, 3),
                    "oracle": round(p_oracle, 3)},
        "heldout_ppx_gap": round(ppx_gap, 5),
    }


def run(quick: bool = False) -> dict:
    env = dict(os.environ)
    # A CPU virtual-device simulation; the chip's four-device path is
    # `chip_smoke.py --chips 4`.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_WORKER_DEVICES}")
    cmd = [sys.executable, "-m", "benchmarks.distributed_bench", "--worker"]
    if quick:
        cmd.append("--quick")
    print(f"  spawning {_WORKER_DEVICES}-device worker: {' '.join(cmd)}")
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(
            f"distributed_bench worker failed (rc={out.returncode})\n"
            f"--- stdout ---\n{out.stdout}\n--- stderr ---\n{out.stderr}")
    result = json.loads(out.stdout.strip().splitlines()[-1])

    eff = result["weak_scaling_efficiency"]
    saving = result["sync_bytes_saving"]
    gap = result["heldout_ppx_gap"]
    print(f"  mesh-1 bit-exact vs oracle: {result['bit_exact_mesh1']}")
    print(f"  weak scaling (1 -> {_WORKER_DEVICES} data shards, "
          f"work-normalized): {eff:.2f}")
    print(f"  per-sync bytes/device: {result['sync_bytes']}"
          f" -> saving {saving:.1f}x")
    print(f"  held-out ppx, staleness=2 on (2,2) vs oracle: "
          f"{result['heldout']} (gap {gap:.2%})")

    assert result["bit_exact_mesh1"], "mesh-1 run diverged from the oracle"
    assert eff >= 0.7, f"weak-scaling efficiency {eff:.2f} < 0.7"
    assert saving > 1.0, (
        f"sparse sync ({result['sync_bytes']}) not below replicated")
    assert gap <= 0.02, f"held-out ppx gap {gap:.2%} > 2%"
    return result


def main():
    if "--worker" in sys.argv:
        print(json.dumps(_worker(quick="--quick" in sys.argv)))
    else:
        print(json.dumps(run(quick="--quick" in sys.argv), indent=1))


if __name__ == "__main__":
    main()
