"""Plain reference of RLDA fit by sweep-parallel alias Metropolis-Hastings.

The chain of AliasLDA (Li et al. 2014) with its cycle proposal, in the
sweep-parallel schedule: every sweep, each token runs `mh_steps` rounds of
Metropolis-Hastings against the target at the start of the sweep,

    p(t) ∝ (n_dt - own + α)(n_wt - own + β) / (n_t - own + β̄),

its own weight taken out at the topic it held when the sweep started. Even
rounds propose from the token's word row, q(t) ∝ n_wt + β, odd rounds from
its document row, q(t) ∝ n_dt + α, both read from the counts at the start of
the sweep with the token's own weight left in (stale proposals). A move
s -> t is accepted with probability min(1, p(t) q(s) / (p(s) q(t))).

Proposals are drawn by Gumbel-max over the whole row: the same distribution
an alias table draws from, without the table. Everything else (the §4.3
preparation, exact counts, `count_err`, the blocks on the accelerator and
the comparison) is `rlda_gibbs`'s; only the chain differs. Written from the
paper and the configuration alone: it imports nothing of the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from vbench.harness import load_module

_gibbs = load_module("references", "rlda_gibbs")

Flat = _gibbs.Flat
prepare = _gibbs.prepare
flatten = _gibbs.flatten
exact_counts = _gibbs.exact_counts
count_error = _gibbs.count_error
device_corpus = _gibbs.device_corpus
pad_z = _gibbs.pad_z
mean_log_conditional = _gibbs.mean_log_conditional
check = _gibbs.check


@partial(jax.jit, static_argnums=(0, 4, 5))
def chain(shape, corpus, z, key, sweeps: int, dtype=jnp.float32):
    """`sweeps` sweep-parallel alias MH sweeps from assignments `z`, with
    `shape.mh_steps` rounds a sweep. Returns the last assignments and the
    counts the chain built from them in `dtype`."""
    docs, words, model, w = corpus
    if shape.mh_steps < 1:
        raise ValueError("the alias chain needs mh_steps >= 1")
    a = jnp.asarray(shape.alpha, dtype)
    b = jnp.asarray(shape.beta, dtype)
    bb = jnp.asarray(shape.beta_bar, dtype)

    def sweep(i, z):
        n_dt, n_wt, n_t = _gibbs._counts(shape, corpus, z, dtype)
        keys = jax.random.split(jax.random.fold_in(key, i),
                                shape.n_pad // shape.block)

        def body(args):
            d, wd, m, wt, z0, kb = args
            own = wt.astype(dtype)
            rows = (jnp.log(n_wt[wd] + b), jnp.log(n_dt[d] + a))  # log q

            def log_p(t):
                sub = jnp.where(t == z0, own, 0)
                nd = jnp.maximum(n_dt[d, t] - sub, 0)
                nw = jnp.maximum(n_wt[wd, t] - sub, 0)
                nt = jnp.maximum(n_t[m, t] - sub, 1e-9)
                return jnp.log(nd + a) + jnp.log(nw + b) - jnp.log(nt + bb)

            def pick(lq, t):
                return jnp.take_along_axis(lq, t[:, None], axis=1)[:, 0]

            cur = z0
            for s, ks in enumerate(jax.random.split(kb, shape.mh_steps)):
                kp, ka = jax.random.split(ks)
                lq = rows[s % 2]
                g = jax.random.gumbel(kp, lq.shape, jnp.float32).astype(dtype)
                prop = jnp.argmax(lq + g, axis=-1).astype(jnp.int32)
                log_a = (log_p(prop) + pick(lq, cur)) - (
                    log_p(cur) + pick(lq, prop))
                u = jax.random.uniform(ka, cur.shape, jnp.float32)
                cur = jnp.where(jnp.log(u) < log_a.astype(jnp.float32),
                                prop, cur)
            return jnp.where(wt > 0, cur, z0)

        return jax.lax.map(
            body, _gibbs._blocks(shape, docs, words, model, w, z)
            + (keys,)).reshape(-1)

    z = jax.lax.fori_loop(0, sweeps, sweep, z)
    return z, _gibbs._counts(shape, corpus, z, dtype)


def chain_numbers(shape, corp, z0, z1, sweeps: int, key) -> dict:
    """`rlda_gibbs.chain_numbers` against this module's chain."""
    return _gibbs.chain_numbers(shape, corp, z0, z1, sweeps, key, chain)


def compare(run) -> list:
    """`rlda_gibbs.compare` against this module's chain."""
    return _gibbs.compare(run, chain)
