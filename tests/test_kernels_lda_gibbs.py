"""lda_gibbs Pallas kernel vs pure-jnp oracle: shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fractional, gibbs, perplexity
from repro.core.types import Corpus, LDAConfig, init_state
from repro.kernels.lda_gibbs import ops as kops
from repro.kernels.lda_gibbs.kernel import (
    gibbs_resample_blocked,
    gibbs_resample_blocked_batched,
    gibbs_resample_blocked_quant,
    pack_halves,
)
from repro.kernels.lda_gibbs.ref import resample_tile


def _random_counts(rng, n, k, dtype):
    return jnp.asarray(rng.integers(0, 50, (n, k)).astype(dtype))


@pytest.mark.parametrize("n,k,token_block", [
    (256, 128, 256), (512, 128, 256), (1024, 256, 256),
    (512, 384, 128), (256, 128, 64),
])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_kernel_matches_ref_sweep(n, k, token_block, dtype):
    rng = np.random.default_rng(int(n + k))
    w_bits = 8 if dtype == np.int32 else None
    rows_d = _random_counts(rng, n, k, dtype)
    rows_w = _random_counts(rng, n, k, dtype)
    tot = jnp.asarray(rng.integers(1, 500, k).astype(dtype))
    z = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    wts = jnp.asarray((rng.random(n) * (rng.random(n) > 0.1)).astype(np.float32))
    g = jax.random.gumbel(jax.random.PRNGKey(0), (n, k), jnp.float32)

    out = gibbs_resample_blocked(
        rows_d, rows_w, tot, z, wts, g,
        alpha=0.1, beta=0.01, beta_bar=0.01 * k, w_bits=w_bits,
        token_block=token_block, interpret=True,
    )
    if w_bits is not None:
        scale = fractional.precision(w_bits)
        rd = rows_d.astype(jnp.float32) * scale
        rw = rows_w.astype(jnp.float32) * scale
        tt = tot.astype(jnp.float32) * scale
    else:
        rd, rw, tt = rows_d, rows_w, tot
    ref = resample_tile(rd, rw, tt, z, wts, g, 0.1, 0.01, 0.01 * k)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def _corpus(rng, n, v, d):
    return Corpus(
        docs=jnp.asarray(rng.integers(0, d, n), jnp.int32),
        words=jnp.asarray(rng.integers(0, v, n), jnp.int32),
        weights=jnp.asarray(rng.random(n).astype(np.float32)),
    )


@pytest.mark.parametrize("w_bits", [None, 8])
def test_ops_sweep_matches_system_gibbs_statistics(w_bits):
    """Kernel-path sweep and system (pure-jnp) sweep see the same scores:
    with identical gumbel they must produce identical assignments; here we
    check distributional equivalence via converged perplexity instead."""
    rng = np.random.default_rng(0)
    cfg = LDAConfig(num_topics=12, vocab_size=150, num_docs=40, w_bits=w_bits)
    corpus = _corpus(rng, 3000, 150, 40)

    st_sys = gibbs.run(cfg, corpus, jax.random.PRNGKey(1), num_sweeps=20)
    st_k = gibbs.run(cfg, corpus, jax.random.PRNGKey(2), num_sweeps=0)
    st_k = init_state(cfg, corpus, jax.random.PRNGKey(2))
    if w_bits is not None:
        from repro.core.types import LDAState

        st_k = LDAState(
            z=st_k.z,
            n_dt=fractional.to_fixed(st_k.n_dt, w_bits),
            n_wt=fractional.to_fixed(st_k.n_wt, w_bits),
            n_t=fractional.to_fixed(st_k.n_t, w_bits),
        )
    for i in range(20):
        st_k = kops.sweep(cfg, st_k, corpus, jax.random.PRNGKey(100 + i))
    p_sys = perplexity.perplexity(cfg, st_sys, corpus)
    p_k = perplexity.perplexity(cfg, st_k, corpus)
    assert abs(np.log(p_sys) - np.log(p_k)) < 0.25, (p_sys, p_k)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_batched_kernel_matches_ref_per_model(dtype):
    """The model-grid kernel is M independent single-model tiles: each grid
    step must index its own model's count rows, preserve exact
    self-exclusion, and honor w_bits fixed-point rescaling."""
    rng = np.random.default_rng(11)
    m, n, k, token_block = 3, 512, 128, 256
    w_bits = 8 if dtype == np.int32 else None
    rows_d = jnp.asarray(rng.integers(0, 50, (m, n, k)).astype(dtype))
    rows_w = jnp.asarray(rng.integers(0, 50, (m, n, k)).astype(dtype))
    tot = jnp.asarray(rng.integers(1, 500, (m, k)).astype(dtype))
    z = jnp.asarray(rng.integers(0, k, (m, n)).astype(np.int32))
    wts = jnp.asarray(
        (rng.random((m, n)) * (rng.random((m, n)) > 0.1)).astype(np.float32))
    g = jax.random.gumbel(jax.random.PRNGKey(2), (m, n, k), jnp.float32)

    out = gibbs_resample_blocked_batched(
        rows_d, rows_w, tot, z, wts, g,
        alpha=0.1, beta=0.01, beta_bar=0.01 * k, w_bits=w_bits,
        token_block=token_block, interpret=True,
    )
    assert out.shape == (m, n)
    for i in range(m):
        if w_bits is not None:
            scale = fractional.precision(w_bits)
            rd = rows_d[i].astype(jnp.float32) * scale
            rw = rows_w[i].astype(jnp.float32) * scale
            tt = tot[i].astype(jnp.float32) * scale
        else:
            rd, rw, tt = rows_d[i], rows_w[i], tot[i]
        ref = resample_tile(rd, rw, tt, z[i], wts[i], g[i],
                            0.1, 0.01, 0.01 * k)
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(ref))


@pytest.mark.parametrize("w_bits", [None, 8])
def test_ops_sweep_many_matches_single_model_sweeps(w_bits):
    """Full batched kernel sweep (gather + model-grid kernel + vmapped
    rebuild) == the single-model kernel sweep per model, bit for bit."""
    m = 3
    cfg = LDAConfig(num_topics=12, vocab_size=150, num_docs=40,
                    w_bits=w_bits)
    corpora = [_corpus(np.random.default_rng(40 + i), 600, 150, 40)
               for i in range(m)]
    stacked = Corpus(
        docs=jnp.stack([c.docs for c in corpora]),
        words=jnp.stack([c.words for c in corpora]),
        weights=jnp.stack([c.weights for c in corpora]),
    )
    keys = jax.random.split(jax.random.PRNGKey(9), m)
    states = jax.vmap(
        lambda co, k: init_state(cfg, co, k))(stacked, keys)
    if w_bits is not None:
        from repro.core.types import LDAState

        states = LDAState(
            z=states.z,
            n_dt=fractional.to_fixed(states.n_dt, w_bits),
            n_wt=fractional.to_fixed(states.n_wt, w_bits),
            n_t=fractional.to_fixed(states.n_t, w_bits),
        )
    out = kops.sweep_many(cfg, states, stacked, keys)
    for i in range(m):
        st_i = jax.tree_util.tree_map(lambda x: x[i], states)
        ref = kops.sweep(cfg, st_i, corpora[i], keys[i])
        np.testing.assert_array_equal(np.asarray(out.z[i]),
                                      np.asarray(ref.z))
        np.testing.assert_array_equal(np.asarray(out.n_wt[i]),
                                      np.asarray(ref.n_wt))


def test_kernel_keeps_padding_assignments():
    rng = np.random.default_rng(3)
    n, k = 256, 128
    rows = _random_counts(rng, n, k, np.float32)
    z = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    wts = jnp.zeros(n, jnp.float32)  # all padding
    g = jax.random.gumbel(jax.random.PRNGKey(0), (n, k), jnp.float32)
    out = gibbs_resample_blocked(
        rows, rows, jnp.ones(k), z, wts, g,
        alpha=0.1, beta=0.01, beta_bar=1.28, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(z))


@pytest.mark.parametrize("bits", [8, 4])
def test_quant_kernel_matches_ref_on_dequantized_rows(bits):
    """Packed word-topic rows (uint8 codes, split-halves nibbles for
    bits=4) dequantized in the tile == the oracle fed the dequantized
    rows, bit for bit; N is deliberately not a multiple of the tile."""
    rng = np.random.default_rng(5 + bits)
    n, k = 700, 256
    levels = 255 if bits == 8 else 15
    codes = jnp.asarray(rng.integers(0, levels + 1, (n, k)).astype(np.uint8))
    scales = jnp.asarray(rng.random(n).astype(np.float32) * 3.0)
    rows_d = _random_counts(rng, n, k, np.float32)
    tot = jnp.asarray(rng.integers(1, 500, k).astype(np.float32))
    z = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    wts = jnp.asarray(
        (rng.random(n) * (rng.random(n) > 0.1)).astype(np.float32))
    g = jax.random.gumbel(jax.random.PRNGKey(4), (n, k), jnp.float32)

    out = gibbs_resample_blocked_quant(
        pack_halves(codes) if bits == 4 else codes, scales, rows_d, tot,
        z, wts, g, alpha=0.1, beta=0.01, beta_bar=0.01 * k, bits=bits,
        token_block=256, interpret=True,
    )
    rows_w = codes.astype(jnp.float32) * scales[:, None]
    ref = resample_tile(rows_d, rows_w, tot, z, wts, g, 0.1, 0.01, 0.01 * k)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
