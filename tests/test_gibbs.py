"""Collapsed-Gibbs samplers: blocked-parallel TPU path vs sequential refs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import alias, gibbs, perplexity
from repro.core.sparse import DenseGibbsSampler, SparseLDASampler
from repro.core.types import Corpus, LDAConfig, build_counts, init_state


def _planted_corpus(n_docs=60, vocab=120, k=6, seed=0, mean_tokens=40):
    """Corpus with planted topics so convergence is measurable."""
    rng = np.random.default_rng(seed)
    phi = np.full((k, vocab), 0.02 / vocab)
    block = vocab // k
    for t in range(k):
        phi[t, t * block : (t + 1) * block] += 0.98 / block
    phi /= phi.sum(1, keepdims=True)
    docs, words = [], []
    for d in range(n_docs):
        theta = rng.dirichlet(np.full(k, 0.2))
        n = rng.poisson(mean_tokens) + 5
        zs = rng.choice(k, n, p=theta)
        for z in zs:
            docs.append(d)
            words.append(rng.choice(vocab, p=phi[z]))
    corpus = Corpus(
        docs=jnp.asarray(docs, jnp.int32),
        words=jnp.asarray(words, jnp.int32),
        weights=jnp.ones(len(docs), jnp.float32),
    )
    cfg = LDAConfig(num_topics=k, vocab_size=vocab, num_docs=n_docs)
    return cfg, corpus


def test_counts_consistency_after_sweep():
    cfg, corpus = _planted_corpus()
    state = gibbs.run(cfg, corpus, jax.random.PRNGKey(0), num_sweeps=3)
    rebuilt = build_counts(cfg, corpus, state.z)
    np.testing.assert_allclose(state.n_dt, rebuilt.n_dt, atol=1e-4)
    np.testing.assert_allclose(state.n_wt, rebuilt.n_wt, atol=1e-4)
    np.testing.assert_allclose(state.n_t, rebuilt.n_t, atol=1e-3)
    # totals conserved == total corpus weight
    assert np.isclose(float(state.n_t.sum()), float(corpus.weights.sum()), rtol=1e-5)


def test_parallel_gibbs_converges():
    cfg, corpus = _planted_corpus()
    st0 = init_state(cfg, corpus, jax.random.PRNGKey(1))
    p0 = perplexity.perplexity(cfg, st0, corpus)
    st = gibbs.run(cfg, corpus, jax.random.PRNGKey(2), num_sweeps=30)
    p1 = perplexity.perplexity(cfg, st, corpus)
    assert p1 < 0.6 * p0, (p0, p1)
    # should approach the planted structure: well below vocab-uniform
    assert p1 < cfg.vocab_size * 0.5


def test_parallel_matches_sequential_quality():
    """Blocked-parallel Gibbs reaches the same perplexity band as the
    faithful sequential SparseLDA sampler (the AD-LDA equivalence)."""
    cfg, corpus = _planted_corpus()
    st = gibbs.run(cfg, corpus, jax.random.PRNGKey(3), num_sweeps=40)
    p_par = perplexity.perplexity(cfg, st, corpus)

    seq = SparseLDASampler(
        cfg,
        np.asarray(corpus.docs),
        np.asarray(corpus.words),
        np.asarray(init_state(cfg, corpus, jax.random.PRNGKey(4)).z),
        seed=5,
    )
    seq.run(40)
    st_seq = build_counts(cfg, corpus, jnp.asarray(seq.z, jnp.int32))
    p_seq = perplexity.perplexity(cfg, st_seq, corpus)
    assert abs(np.log(p_par) - np.log(p_seq)) < 0.35, (p_par, p_seq)


def test_sparse_equals_dense_sequential():
    """SparseLDA's bucket decomposition is exact: same rng, same trajectory
    as the dense O(k) sampler for the first sweep? (They consume randomness
    differently, so compare converged quality instead.)"""
    cfg, corpus = _planted_corpus(n_docs=30, mean_tokens=25)
    z0 = np.asarray(init_state(cfg, corpus, jax.random.PRNGKey(0)).z)
    a = SparseLDASampler(cfg, np.asarray(corpus.docs), np.asarray(corpus.words), z0, seed=7)
    b = DenseGibbsSampler(cfg, np.asarray(corpus.docs), np.asarray(corpus.words), z0, seed=7)
    a.run(25)
    b.run(25)
    pa = perplexity.perplexity(cfg, build_counts(cfg, corpus, jnp.asarray(a.z, jnp.int32)), corpus)
    pb = perplexity.perplexity(cfg, build_counts(cfg, corpus, jnp.asarray(b.z, jnp.int32)), corpus)
    assert abs(np.log(pa) - np.log(pb)) < 0.3, (pa, pb)


def test_fixed_point_path_tracks_float_path():
    cfg, corpus = _planted_corpus()
    cfg_fx = LDAConfig(
        num_topics=cfg.num_topics, vocab_size=cfg.vocab_size,
        num_docs=cfg.num_docs, w_bits=8,
    )
    st_f = gibbs.run(cfg, corpus, jax.random.PRNGKey(6), num_sweeps=25)
    st_x = gibbs.run(cfg_fx, corpus, jax.random.PRNGKey(6), num_sweeps=25)
    pf = perplexity.perplexity(cfg, st_f, corpus)
    px = perplexity.perplexity(cfg_fx, st_x, corpus)
    assert abs(np.log(pf) - np.log(px)) < 0.2, (pf, px)


def test_alias_mh_sweep_converges():
    cfg, corpus = _planted_corpus()
    st = init_state(cfg, corpus, jax.random.PRNGKey(8))
    p0 = perplexity.perplexity(cfg, st, corpus)
    for i in range(30):
        st = alias.mh_sweep(cfg, st, corpus, jax.random.PRNGKey(10 + i), 4)
    p1 = perplexity.perplexity(cfg, st, corpus)
    assert p1 < 0.7 * p0, (p0, p1)


def _alias_reconstruction(thresh, al):
    """p[t] = (thresh[t] + Σ_{j: alias[j]==t} (1-thresh[j])) / k."""
    thresh, al = np.asarray(thresh), np.asarray(al)
    recon = thresh.copy()
    for j in range(len(thresh)):
        recon[al[j]] += 1.0 - thresh[j]
    return recon / len(thresh)


def test_alias_table_is_exact_distribution():
    """Alias table encodes the input distribution exactly."""
    rng = np.random.default_rng(0)
    for k in (2, 3, 8, 33, 64):
        p = rng.dirichlet(np.full(k, 0.4))
        thresh, al = alias.build_alias_table(jnp.asarray(p, jnp.float32))
        np.testing.assert_allclose(
            _alias_reconstruction(thresh, al), p, atol=2e-5)


def test_alias_table_exact_on_degenerate_rows():
    """Property sweep over the shapes that break pairing builders: the
    K-long drained-donor chain (one near-empty bucket), one-hot rows,
    zero-probability entries, exactly-uniform rows, and large K. Every
    threshold must stay in [0, 1] and the reconstruction must be exact."""
    rng = np.random.default_rng(1)
    cases = [
        np.r_[1e-7, np.full(63, (1 - 1e-7) / 63)],  # drain chain
        np.eye(16)[3],  # one-hot: zero-probability topics must never win
        np.r_[np.zeros(12), rng.dirichlet(np.full(4, 0.3))],
        np.full(32, 1 / 32),  # exactly uniform (all-heavy, zero excess)
        np.array([0.999, 0.001]),
        rng.dirichlet(np.full(256, 0.05)),  # large sparse K
    ]
    for p in cases:
        thresh, al = alias.build_alias_table(jnp.asarray(p, jnp.float32))
        t = np.asarray(thresh)
        assert ((t >= 0.0) & (t <= 1.0)).all(), p
        np.testing.assert_allclose(
            _alias_reconstruction(thresh, al), p / p.sum(), atol=2e-5)
        # zero-probability topics are unreachable: a zero bucket keeps no
        # mass and no bucket above threshold aliases into it
        zero = np.flatnonzero(p == 0.0)
        if zero.size:
            np.testing.assert_allclose(t[zero], 0.0, atol=1e-7)


def test_alias_table_zero_row_uniform_fallback():
    """An all-zero row (word never observed) falls back to an explicit
    uniform distribution, not an epsilon-normalized artifact."""
    thresh, al = alias.build_alias_table(jnp.zeros(16))
    np.testing.assert_allclose(np.asarray(thresh), 1.0)
    np.testing.assert_allclose(
        _alias_reconstruction(thresh, al), np.full(16, 1 / 16), atol=1e-7)


def test_alias_tables_batched_matches_per_row():
    """The whole-(V, K) vectorized builder == the single-row builder on
    every row, including a zero row mixed into the batch."""
    rng = np.random.default_rng(2)
    probs = rng.dirichlet(np.full(24, 0.2), size=40).astype(np.float32)
    probs[7] = 0.0
    thresh, al = alias.build_alias_tables(jnp.asarray(probs))
    assert thresh.shape == al.shape == (40, 24)
    for i in (0, 7, 13, 39):
        t_i, a_i = alias.build_alias_table(jnp.asarray(probs[i]))
        np.testing.assert_array_equal(np.asarray(thresh[i]), np.asarray(t_i))
        np.testing.assert_array_equal(np.asarray(al[i]), np.asarray(a_i))


def _oracle_build_row(mass):
    """The gather-based row builder the blocked one replaced (binary
    searches, per-element gathers, a scatter to un-permute), kept as the
    bit-exact oracle."""
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


def _oracle_build_alias_tables(probs):
    with jax.named_scope("alias_tables"):
        probs = jnp.asarray(probs, jnp.float32)
        k = probs.shape[-1]
        lead = probs.shape[:-1]
        row_sum = probs.sum(-1, keepdims=True)
        ok = row_sum > 0.0
        mass = jnp.where(ok, probs * (k / jnp.where(ok, row_sum, 1.0)), 1.0)
        flat = mass.reshape((-1, k))
        thresh, alias = jax.vmap(_oracle_build_row)(flat)
        return thresh.reshape(lead + (k,)), alias.reshape(lead + (k,))


def _parity_rows(k, seed):
    """Rows that stress the builder: integer counts + beta (ties), sparse
    Dirichlet rows, all-zero, one-hot and exactly uniform rows, and the
    drained-donor chain (one near-empty bucket)."""
    rng = np.random.default_rng(seed)
    chain = np.r_[1e-7, np.full(k - 1, (1 - 1e-7) / (k - 1))]
    return np.concatenate([
        rng.integers(0, 4, (24, k)) + 0.01,
        rng.dirichlet(np.full(k, 0.05), 8),
        np.zeros((3, k)),
        np.eye(k)[rng.permutation(k)[:4]],
        np.full((2, k), 1.0 / k), np.full((1, k), 3.0),
        chain[None], chain[::-1][None],
    ]).astype(np.float32)


@pytest.mark.parametrize("blocked", [False, True], ids=["default", "16_rows"])
@pytest.mark.parametrize("k", [2, 12, 128, 256, 1024])
def test_alias_tables_bit_identical_to_gather_builder(monkeypatch, k, blocked):
    """The gather-free blocked builder returns the gather-based builder's
    thresholds and aliases bit for bit. With 16-row blocks the 42-44 rows
    leave a remainder, so the scanned blocks and the remainder both run;
    at K=1024 the default block (32 rows) does the same."""
    if blocked:
        monkeypatch.setattr(alias, "_BLOCK_ELEMS", 16 * k * (k + 1))
    probs = jnp.asarray(_parity_rows(k, seed=k))
    want_t, want_a = jax.jit(_oracle_build_alias_tables)(probs)
    got_t, got_a = alias.build_alias_tables(probs)
    np.testing.assert_array_equal(
        np.asarray(got_t).view(np.int32), np.asarray(want_t).view(np.int32))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


def test_sweep_checkify_clean():
    """Sanitized leg (REPRO_SANITIZE=1): a full sweep is clean under
    checkify's float + index checks — no NaNs, no div-by-zero, and every
    count-table gather/scatter in bounds."""
    import os

    if os.environ.get("REPRO_SANITIZE") != "1":
        pytest.skip("sanitized leg only (set REPRO_SANITIZE=1)")
    from jax.experimental import checkify

    cfg, corpus = _planted_corpus(n_docs=20, vocab=60, k=4, mean_tokens=20)
    state = gibbs.run(cfg, corpus, jax.random.PRNGKey(0), num_sweeps=1)

    checked = checkify.checkify(
        lambda st, key: gibbs.sweep(cfg, st, corpus, key, block=256),
        errors=checkify.float_checks | checkify.index_checks,
    )
    err, new_state = checked(state, jax.random.PRNGKey(1))
    err.throw()
    assert new_state.z.shape == state.z.shape
