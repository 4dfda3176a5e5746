"""Client/server distributed Gibbs — the Chital topology on a pod (§Perf C).

The paper's network: each client holds *its own documents* and samples them
against a locally-cached copy of the shared word-topic model; the server
aggregates model updates. The pod rendering via `shard_map`:

  data shards   = client cohorts: token arrays and doc-topic counts are
                  partitioned by document across ('pod','data');
  n_wt, n_t     = the model cache: replicated, rebuilt by psum — exactly
                  the paper's "central model cache and updating server";
  staleness     = `sync_every`: clients run several local sweeps against
                  their stale model copy (plus their OWN running deltas)
                  before the next server sync — AliasLDA-grade staleness
                  (§2.4) amortizes the sync collective over M sweeps.

Contrast with the naive GSPMD lowering of `gibbs.sweep` (model-sharded
n_dt): there the partitioner cannot prove doc-locality and all-gathers the
entire token corpus to every device each sweep — the dominant collective
in the baseline dry-run. Here doc-locality is structural.

This module keeps the *fully-replicated* model: every shard holds the whole
(V, K) table and each server sync all-reduces it whole, so it is the
small-mesh oracle. The production scale-out path — vocab-sharded state and
sparse delta-row exchange — lives in `repro.pserver`, which reuses
`local_sweep`, `make_shard_map`, and `partition_by_doc` from here.

Caller contract: documents are partitioned contiguously across the data
shards in blocks of `sweep.d_local` (= ceil(num_docs / n_shards)); `docs`
holds SHARD-LOCAL doc ids in [0, d_local). Any corpus fits any mesh: the
last shard's tail is padding (zero-weight tokens, empty n_dt rows) and
`shard_corpus` builds the padded layout host-side from a flat corpus.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.gibbs import resample_block
from repro.core.types import LDAConfig
from repro.launch.mesh import auto_axes


def make_shard_map(fn, mesh, in_specs, out_specs):
    """`jax.shard_map` over `mesh` with Auto axes (a caller's Explicit
    mesh is converted, so the program's gathers and outputs need no
    out-shardings) and replication checks off: every program here
    produces replicated outputs by explicit psum."""
    return jax.shard_map(fn, mesh=auto_axes(mesh), in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def local_sweep(cfg, docs, words, z, wts, n_dt, n_wt, n_t, key, block):
    """One full resampling pass over one shard's tokens (pure local).

    Identical schedule and key discipline to `gibbs.sweep`'s inner loop
    (pad to `block` multiples, one subkey + one (block, K) Gumbel draw per
    block), so a single-shard run is bit-comparable to the oracle. `n_dt`
    and `n_wt` may be shard-local tables — `docs`/`words` just index rows.
    """
    n = docs.shape[0]
    nblocks = -(-n // block)
    pad = nblocks * block - n

    def padded(x, fill=0):
        return jnp.pad(x, (0, pad), constant_values=fill)

    d_b = padded(docs).reshape(nblocks, block)
    w_b = padded(words).reshape(nblocks, block)
    z_b = padded(z).reshape(nblocks, block)
    wt_b = padded(wts, 0).reshape(nblocks, block)
    keys = jax.random.split(key, nblocks)

    def body(args):
        d, w, zz, wt, k = args
        g = jax.random.gumbel(k, (block, cfg.num_topics), jnp.float32)
        return resample_block(cfg, d, w, zz, wt, n_dt, n_wt, n_t, g)

    return jax.lax.map(body, (d_b, w_b, z_b, wt_b, keys)).reshape(-1)[:n]


def partition_by_doc(num_docs: int, docs: np.ndarray, n_shards: int):
    """Host-side contiguous doc partition of a flat token stream.

    Shard `w` owns docs `[w*d_local, (w+1)*d_local)` with
    `d_local = ceil(num_docs / n_shards)`; each shard's tokens are padded
    to the max per-shard token count `t_local`. Returns
    ``(d_local, t_local, perm, inv)`` where `perm` is the
    `(n_shards * t_local,)` map from padded slot to original token index
    (sentinel `len(docs)` marks padding) and `inv` is the `(len(docs),)`
    inverse (slot of each original token). With one shard `perm` is the
    identity, which is what keeps single-shard runs bit-exact vs the
    unsharded oracle.
    """
    docs = np.asarray(docs)
    n = docs.shape[0]
    d_local = -(-num_docs // n_shards)
    shard = np.minimum(docs // d_local, n_shards - 1).astype(np.int64)
    order = np.argsort(shard, kind="stable")
    counts = np.bincount(shard, minlength=n_shards)
    t_local = max(1, int(counts.max()))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    within = np.arange(n, dtype=np.int64) - starts[shard[order]]
    slots = shard[order] * t_local + within
    perm = np.full(n_shards * t_local, n, np.int64)
    perm[slots] = order
    inv = np.empty(n, np.int64)
    inv[order] = slots
    return d_local, t_local, perm, inv


def shard_corpus(cfg: LDAConfig, corpus, z, n_dt, n_shards: int):
    """Pad + partition a flat corpus for an `n_shards` client/server sweep.

    Returns ``(docs_l, words, z_sh, wts, n_dt_sh, inv)``: token arrays of
    length `n_shards * t_local` (pad tokens carry weight 0 and doc/word 0,
    so they keep their assignment and contribute nothing), `docs_l` in
    shard-local ids, and `n_dt_sh` with rows padded to
    `n_shards * d_local`. Recover original-order assignments with
    ``z_sh[inv]`` and the true doc-topic table with
    ``n_dt_sh[:cfg.num_docs]``.
    """
    d_local, t_local, perm, inv = partition_by_doc(
        cfg.num_docs, np.asarray(corpus.docs), n_shards)
    perm_j = jnp.asarray(perm)
    shard_of = jnp.asarray(
        (np.arange(n_shards * t_local) // t_local) * d_local, jnp.int32)

    def take(x, fill):
        return jnp.take(x, perm_j, mode="fill", fill_value=fill)

    docs_l = take(corpus.docs, 0) - jnp.where(
        perm_j < corpus.num_tokens, shard_of, 0)
    pad_rows = n_shards * d_local - cfg.num_docs
    n_dt_sh = jnp.pad(n_dt, ((0, pad_rows), (0, 0)))
    return (docs_l.astype(jnp.int32), take(corpus.words, 0), take(z, 0),
            take(corpus.weights, 0.0), n_dt_sh, jnp.asarray(inv))


def make_client_server_sweep(cfg: LDAConfig, mesh, *, block: int = 8192,
                             sync_every: int = 1):
    """Returns jit-able fn(docs, words, z, wts, n_dt_local, n_wt, key) ->
    (z, n_dt_local, n_wt, n_t), running `sync_every` client-local sweeps
    per server sync. Counts are real-valued float32 (callers on the w_bits
    path convert at the boundary).

    Token arrays must be length `n_shards * t_local` for some per-shard
    capacity (`shard_corpus` builds that layout, padding the last shard
    with zero-weight tokens when `num_docs % n_shards != 0`), and
    `n_dt_local` must have `n_shards * sweep.d_local` rows.
    """
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = P(data_axes if len(data_axes) > 1 else data_axes[0])
    n_shards = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in data_axes:
        n_shards *= sizes[a]
    d_local = -(-cfg.num_docs // n_shards)

    def shard_fn(docs, words, z, wts, n_dt, n_wt, key):
        # Distinct randomness per client cohort.
        idx = jnp.int32(0)
        for a in data_axes:
            idx = idx * sizes[a] + jax.lax.axis_index(a)
        key = jax.random.fold_in(key, idx)

        # The model cache minus this client's own contribution: local
        # deltas stay fresh while other clients' updates stay stale.
        def own_contrib(zz):
            return (jnp.zeros_like(n_wt)
                    .at[words, zz].add(wts.astype(n_wt.dtype)))

        n_wt_others = n_wt - own_contrib(z)

        for _ in range(sync_every):
            key, sub = jax.random.split(key)
            cur_wt = n_wt_others + own_contrib(z)
            cur_t = cur_wt.sum(axis=0)
            z = local_sweep(cfg, docs, words, z, wts, n_dt, cur_wt, cur_t,
                            sub, block)
            n_dt = (jnp.zeros_like(n_dt)
                    .at[docs, z].add(wts.astype(n_dt.dtype)))

        # Server sync: aggregate every client's contribution (the paper's
        # "model cache and updating server", one all-reduce per M sweeps).
        n_wt_new = jax.lax.psum(own_contrib(z), data_axes)
        return z, n_dt, n_wt_new, n_wt_new.sum(axis=0)

    mapped = make_shard_map(
        shard_fn,
        mesh,
        (bspec, bspec, bspec, bspec, P(bspec[0], None),
         P(None, None), P()),
        (bspec, P(bspec[0], None), P(None, None), P(None)),
    )

    def sweep(docs, words, z, wts, n_dt_local, n_wt, key):
        return mapped(docs, words, z, wts, n_dt_local, n_wt, key)

    sweep.d_local = d_local
    sweep.n_shards = n_shards
    return sweep
