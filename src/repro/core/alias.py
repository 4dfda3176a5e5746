"""AliasLDA (Li et al., 2014a) adapted to TPU — stale proposals + parallel MH.

AliasLDA reduces per-token cost to O(k_d) by drawing topic proposals from a
*stale* per-word alias table (built from a snapshot of the word-topic counts)
and correcting with Metropolis–Hastings. The paper (§3.1, §4.3) relies on
RLDA remaining "compatible with preexisting fast sampling techniques such as
(Yao et al., 2009; Li et al., 2014a)".

TPU adaptation (DESIGN.md §3): staleness is the whole point — the proposal
distribution is fixed for a sweep, so (i) *all* alias tables (per-word and
per-doc — MH rounds alternate Li et al.'s word/doc cycle proposals) are
rebuilt once per sweep, embarrassingly parallel over rows, and (ii) proposal
draws and MH accept/reject for *all tokens* are elementwise-parallel (one
uniform matrix per MH round, no per-token key splitting). We keep the
paper's estimator and only change the schedule from token-sequential to
sweep-parallel.

Alias-table construction is an exact linearization of Vose's algorithm
(`build_alias_tables`): sort each row into light/heavy buckets, take prefix
sums, and read every threshold and alias off the cumulative deficit/excess
curves — O(K log K) work at O(log K) parallel depth per row, vectorized
across the whole (V, K) table at once. The fused Pallas sweep lives in
`repro.kernels.alias_mh`; this module is the jnp system path and the parity
oracle.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.types import Corpus, LDAConfig, LDAState, build_counts


def _build_row(mass: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Exact alias table for one row of Vose-scaled masses (sum == K).

    Linearized Vose: partition buckets into *lights* (mass < 1) and
    *heavies* (mass >= 1) and replay the sequential pairing — each light
    bucket is topped up by the currently-open heavy donor; a drained donor's
    own bucket is topped up by the *next* heavy (the drained-donor chain).
    The donor open when light i arrives is determined purely by where the
    cumulative light deficit D sits against the cumulative heavy excess E,
    so every pairing decision reads off two prefix-sum curves:

      light i:  thresh = mass_i,            alias = first heavy with E > D_{i-1}
      heavy j:  thresh = 1 + E_j - D_{i(j)}, alias = next heavy in order,
                where i(j) = first light with D_i >= E_j (the light whose
                fill drains donor j below 1; D_0 = 0).

    Mass conservation per topic is exact by construction: a heavy topic t
    recovers its excess from the lights it fills plus the chain slice it
    receives from its predecessor.
    """
    k = mass.shape[0]
    light = mass < 1.0
    order = jnp.argsort(jnp.where(light, 0, 1))  # lights first (stable)
    m_s = mass[order]
    light_s = light[order]

    deficit = jnp.where(light_s, 1.0 - m_s, 0.0)
    excess = jnp.where(light_s, 0.0, m_s - 1.0)
    cum_d = jnp.cumsum(deficit)  # constant on the heavy suffix
    cum_e = jnp.cumsum(excess)  # zero on the light prefix

    # Lights: the open donor when light i arrives is the first heavy whose
    # cumulative excess exceeds the deficit already absorbed (D_{i-1}).
    d_prev = cum_d - deficit
    donor = jnp.clip(
        jnp.searchsorted(cum_e, d_prev, side="right"), 0, k - 1)

    # Heavies: donor j is drained by the first light whose cumulative
    # deficit reaches E_j; its residual at that point is the threshold.
    cum_d_ext = jnp.concatenate([jnp.zeros(1, cum_d.dtype), cum_d])
    closer = jnp.clip(
        jnp.searchsorted(cum_d_ext, cum_e, side="left"), 0, k)
    thresh_heavy = jnp.clip(1.0 + cum_e - cum_d_ext[closer], 0.0, 1.0)

    pos = jnp.arange(k, dtype=jnp.int32)
    thresh_s = jnp.where(light_s, m_s, thresh_heavy)
    alias_pos = jnp.where(light_s, donor, jnp.minimum(pos + 1, k - 1))
    alias_s = order[alias_pos].astype(jnp.int32)

    thresh = jnp.zeros_like(m_s).at[order].set(thresh_s)
    alias = jnp.zeros(k, jnp.int32).at[order].set(alias_s)
    return thresh, alias


def build_alias_tables(probs: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Exact alias tables for a whole batch of distributions at once.

    `probs` is (..., K) of non-negative (un-normalized) masses; returns
    `(thresh, alias)` of the same batch shape. Sample u~U[0,1),
    j~U{0..K-1}; topic = j if u < thresh[..., j] else alias[..., j].

    Construction is branch-free sort + prefix sums (see `_build_row`):
    O(K log K) work and O(log K) parallel depth per row, with every row of
    a (V, K) table built in one vectorized pass — this replaces the
    K-step sequential pairing scan that made table rebuilds the serial
    bottleneck of the alias sweep.

    Rows whose total mass is zero (a word never observed, all counts
    flushed) fall back to an explicit uniform distribution rather than
    normalizing against an epsilon floor.
    """
    with jax.named_scope("alias_tables"):
        probs = jnp.asarray(probs, jnp.float32)
        k = probs.shape[-1]
        lead = probs.shape[:-1]
        row_sum = probs.sum(-1, keepdims=True)
        ok = row_sum > 0.0
        mass = jnp.where(ok, probs * (k / jnp.where(ok, row_sum, 1.0)), 1.0)
        flat = mass.reshape((-1, k))
        thresh, alias = jax.vmap(_build_row)(flat)
        return thresh.reshape(lead + (k,)), alias.reshape(lead + (k,))


def build_alias_table(probs: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Alias table for a single distribution (see `build_alias_tables`)."""
    return build_alias_tables(probs)


@partial(jax.jit, static_argnums=(0, 4))
def mh_sweep(
    cfg: LDAConfig,
    state: LDAState,
    corpus: Corpus,
    key: jax.Array,
    mh_steps: int = 2,
) -> LDAState:
    """One AliasLDA-style sweep: stale proposal tables + parallel MH.

    Li et al.'s *cycle* proposal: MH rounds alternate between the stale
    word term and the stale doc term —

        even rounds:  q_w(t) ∝ n_tw + β   (per-word alias tables)
        odd rounds:   q_d(t) ∝ n_td + α   (per-doc alias tables)

    with the accept ratio for move s->t against the stale target
    p(t) ∝ (n_td+α)(n_tw+β)/(n_t+β̄):

        a = min(1, p(t) q(s) / (p(s) q(t)))

    Alternating covers both factors of the target, which is what lets the
    MH chain reach the exact sweep's quality band (a word-only proposal
    under-explores peaked doc-topic distributions). All quantities use the
    sweep-stale snapshot, matching AliasLDA's amortization (tables stale
    for O(K) draws there; one sweep here). Each MH round consumes three
    full-width random matrices (bucket index, bucket-vs-alias uniform,
    accept uniform) drawn from a per-round key — the layout
    `repro.kernels.alias_mh.ops` reproduces outside the fused kernel,
    which is what makes kernel-vs-oracle parity bit-exact.
    """
    k = cfg.num_topics
    n_dt, n_wt, n_t = state.n_dt, state.n_wt, state.n_t

    # Stale proposal tables (word and doc cycles), each built for every
    # row of the count tables in one vectorized pass.
    thresh_w, alias_w = build_alias_tables(n_wt + cfg.beta)  # (V, K)
    thresh_d, alias_d = build_alias_tables(n_dt + cfg.alpha)  # (D, K)

    docs, words, wts = corpus.docs, corpus.words, corpus.weights
    z = state.z

    def log_p(zt):  # stale target, with self-exclusion of own assignment
        own = (zt == z) & (wts > 0)  # token's own count sits at its current z
        sub = jnp.where(own, wts, 0.0)
        ndt = jnp.maximum(n_dt[docs, zt] - sub, 0.0)
        nwt = jnp.maximum(n_wt[words, zt] - sub, 0.0)
        nt = jnp.maximum(n_t[zt] - sub, 1e-9)
        return (
            jnp.log(ndt + cfg.alpha) + jnp.log(nwt + cfg.beta) - jnp.log(nt + cfg.beta_bar)
        )

    def log_q_w(zt):  # stale proposal densities (un-normalized: ratios)
        return jnp.log(n_wt[words, zt] + cfg.beta)

    def log_q_d(zt):
        return jnp.log(n_dt[docs, zt] + cfg.alpha)

    z_cur = z
    for s, k_step in enumerate(jax.random.split(key, mh_steps)):
        kj, ku, ka = jax.random.split(k_step, 3)
        j = jax.random.randint(kj, words.shape, 0, k)
        u = jax.random.uniform(ku, words.shape)
        if s % 2 == 0:  # word-proposal round
            prop = jnp.where(
                u < thresh_w[words, j], j, alias_w[words, j])
            log_q = log_q_w
        else:  # doc-proposal round
            prop = jnp.where(
                u < thresh_d[docs, j], j, alias_d[docs, j])
            log_q = log_q_d
        prop = prop.astype(jnp.int32)
        log_a = (log_p(prop) + log_q(z_cur)) - (log_p(z_cur) + log_q(prop))
        accept = jnp.log(jax.random.uniform(ka, z_cur.shape)) < log_a
        z_cur = jnp.where(accept & (wts > 0), prop, z_cur)
    return build_counts(cfg, corpus, z_cur)


# -- batched multi-model sweeps (the `serving.batch_engine` layout) ---------


def _sweep_batch(cfg, states, corpora, keys, mh_steps, path):
    """One alias sweep over M stacked models (stored units in and out):
    the model-grid fused kernel on the "pallas" path, the vmapped oracle
    otherwise. Mirrors `core.batch._sweep_batch`."""
    if path == "pallas":
        from repro.kernels.alias_mh import ops as kops

        return kops.mh_sweep_many(cfg, states, corpora, keys, mh_steps)
    from repro.core import codec

    def one(st, co, k):
        return codec.encode_state(
            cfg, mh_sweep(cfg, codec.decode_state(cfg, st), co, k, mh_steps))

    return jax.vmap(one)(states, corpora, keys)


@partial(jax.jit, static_argnums=(0, 4, 5, 6))
def run_many(
    cfg: LDAConfig,
    states: LDAState,  # stacked warm states (stored units)
    corpora: Corpus,  # stacked (M, N)
    keys: jax.Array,  # (M, 2) one key per model
    num_sweeps: int,
    mh_steps: int = 4,
    path: str = "jnp",
) -> LDAState:
    """`num_sweeps` alias sweeps over all M stacked models under one jit
    (the per-sweep tables rebuild inside the scanned sweep), so a batched
    alias refit costs one dispatch like `core.batch.run_many`.

    Key discipline matches `_BaseSampler.run` per model: model i consumes
    `jax.random.split(keys[i], num_sweeps)`, one subkey per sweep, so a
    batched run is comparable to M sequential runs from the same keys.
    """
    sweep_keys = jax.vmap(
        lambda k: jax.random.split(k, num_sweeps))(keys)  # (M, S, 2)
    sweep_keys = jnp.swapaxes(sweep_keys, 0, 1)  # (S, M, 2)

    def body(carry, ks):
        return _sweep_batch(
            cfg, carry, corpora, ks, mh_steps, path), None

    states, _ = jax.lax.scan(body, states, sweep_keys)
    return states
