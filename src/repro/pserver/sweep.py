"""shard_map program factory for the parameter-server fit tier.

One program = `num_sweeps` sweeps on a (data, model) mesh where every
device is a worker over its own contiguous doc slab. Per worker the carry
is tiny and support-local:

  z (t_local,)            assignments of the worker's token slab
  n_dt (d_local, K)       the worker's doc-topic rows
  cache_s (cap, K)        support cache as of the last sync
  own_s (cap, K)          the worker's own contribution at the last sync
  nt_s (K,)               global topic totals as of the last sync

Within a `staleness`-sweep window every sweep scores against

  cur_cache = cache_s + (own(z) - own_s)       # own deltas fresh,
  cur_t     = nt_s    + (own(z) - own_s).sum   # remote deltas stale

— the same own-fresh/remote-stale split as `core.distributed`, but on
(cap, K) support rows instead of the full (V, K) table. Every `staleness`
sweeps the workers exchange delta rows (`sync.exchange_deltas`); at the
program boundary the authoritative word-topic table is rebuilt exactly by
scatter + `psum_scatter` across the model axis (vocab-sharded assembly;
no worker materializes (V, K) when the model axis is >1).

Bit-exactness (the `distributed_bench` oracle gate): at mesh (1,1) the
token permutation is the identity, the worker key is not folded, and the
local "gibbs" engine is literally `core.distributed.local_sweep` — the
same pad/split/Gumbel schedule as `gibbs.sweep` — so a float32 run from
identical keys reproduces `core.gibbs.run` bit for bit (any `staleness`:
a worker is never stale w.r.t. itself). The "pallas" engine reuses
`kernels.lda_gibbs`'s fused tile kernel (one Gumbel matrix per sweep, its
own key discipline); "mh" is the AliasLDA-style stale-proposal sampler
whose accept step scores against the bounded-staleness cache — the MH
correction absorbing staleness exactly as the alias backend's stale
tables do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.alias import build_alias_tables
from repro.core.distributed import local_sweep, make_shard_map
from repro.core.types import LDAConfig
from repro.pserver import sync
from repro.pserver.topology import PServerPlan

_DATA_AXES = ("pod", "data")


def _axis_split(mesh):
    """(all_axes, data_axes, model_axis) of a worker mesh; the model axis
    must be minor (last) so the flat worker index matches
    `topology.build_plan`'s row-major (data, model) layout."""
    axes = tuple(mesh.axis_names)
    data_axes = tuple(a for a in axes if a in _DATA_AXES)
    model_axis = "model" if "model" in axes else None
    assert set(axes) <= set(_DATA_AXES) | {"model"}, axes
    if model_axis is not None:
        assert axes[-1] == "model", f"model axis must be minor, got {axes}"
    return axes, data_axes, model_axis


def make_pserver_program(
    cfg: LDAConfig,
    mesh,
    plan: PServerPlan,
    *,
    num_sweeps: int,
    staleness: int = 1,
    block: int = 4096,
    local: str = "gibbs",
    mh_steps: int = 4,
):
    """Build the jit-able pserver program for one (mesh, plan) pair.

    Returns fn(docs_l, words_l, z, wts, support, n_dt, cache0, n_t0, keys)
    -> (z, n_dt, n_wt, n_t) with token/support/doc arrays in the plan's
    flat padded layout, `keys` of shape (num_sweeps, 2), and `n_wt` the
    assembled (v_pad, K) table (model-sharded across the mesh when the
    model axis is >1). All counts are real-valued float32; the sampler
    handles the stored-unit boundary.
    """
    if local not in ("gibbs", "pallas", "mh"):
        raise ValueError(f"unknown pserver local engine {local!r}")
    axes, data_axes, model_axis = _axis_split(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_workers = plan.n_workers
    assert n_workers == int(mesh.devices.size), (n_workers, mesh)
    k = cfg.num_topics
    cap, d_local, v_pad = plan.cap, plan.d_local, plan.v_pad
    n_model = sizes.get("model", 1)
    assert v_pad % n_model == 0, (v_pad, n_model)
    n_full, tail = divmod(num_sweeps, staleness)

    def _local_gibbs(z, docs, words, wts, n_dt, cache, n_t, kk):
        return local_sweep(
            cfg, docs, words, z, wts, n_dt, cache, n_t, kk, block)

    def _local_pallas(z, docs, words, wts, n_dt, cache, n_t, kk):
        from repro.kernels.lda_gibbs.kernel import gibbs_resample_blocked

        kp = -(-k // 128) * 128

        def padk(x):
            return jnp.pad(x, ((0, 0), (0, kp - k)))

        gumbel = jax.random.gumbel(kk, (docs.shape[0], kp), jnp.float32)
        gumbel = jnp.where(jnp.arange(kp)[None, :] < k, gumbel, -jnp.inf)
        return gibbs_resample_blocked(
            padk(n_dt[docs]), padk(cache[words]), jnp.pad(n_t, (0, kp - k)),
            z, wts, gumbel,
            alpha=cfg.alpha, beta=cfg.beta, beta_bar=cfg.beta_bar,
            w_bits=None, interpret=jax.default_backend() == "cpu")

    def _local_mh(z, docs, words, wts, n_dt, cache, n_t, kk):
        # AliasLDA word/doc cycle proposals from the *window-stale* support
        # cache, accept/reject against the bounded-staleness target — the
        # MH machinery is what absorbs the staleness (core.alias §docs).
        thresh_w, alias_w = build_alias_tables(cache + cfg.beta)  # (cap, K)
        thresh_d, alias_d = build_alias_tables(n_dt + cfg.alpha)  # (dl, K)

        def log_p(zt):
            own_m = (zt == z) & (wts > 0)
            sub = jnp.where(own_m, wts, 0.0)
            ndt = jnp.maximum(n_dt[docs, zt] - sub, 0.0)
            nwt = jnp.maximum(cache[words, zt] - sub, 0.0)
            nt = jnp.maximum(n_t[zt] - sub, 1e-9)
            return (jnp.log(ndt + cfg.alpha) + jnp.log(nwt + cfg.beta)
                    - jnp.log(nt + cfg.beta_bar))

        def log_q_w(zt):
            return jnp.log(cache[words, zt] + cfg.beta)

        def log_q_d(zt):
            return jnp.log(n_dt[docs, zt] + cfg.alpha)

        z_cur = z
        for s, k_step in enumerate(jax.random.split(kk, mh_steps)):
            kj, ku, ka = jax.random.split(k_step, 3)
            j = jax.random.randint(kj, words.shape, 0, k)
            u = jax.random.uniform(ku, words.shape)
            if s % 2 == 0:
                prop = jnp.where(u < thresh_w[words, j], j, alias_w[words, j])
                log_q = log_q_w
            else:
                prop = jnp.where(u < thresh_d[docs, j], j, alias_d[docs, j])
                log_q = log_q_d
            prop = prop.astype(jnp.int32)
            log_a = (log_p(prop) + log_q(z_cur)) - (log_p(z_cur) + log_q(prop))
            accept = jnp.log(jax.random.uniform(ka, z_cur.shape)) < log_a
            z_cur = jnp.where(accept & (wts > 0), prop, z_cur)
        return z_cur

    local_fn = {"gibbs": _local_gibbs, "pallas": _local_pallas,
                "mh": _local_mh}[local]

    def shard_fn(docs, words, z, wts, support, n_dt, cache, n_t, keys):
        if n_workers > 1:
            widx = jnp.int32(0)
            for a in axes:
                widx = widx * sizes[a] + jax.lax.axis_index(a)

        def own(zz):
            return sync.own_rows(words, zz, wts, cap, k)

        def one_sweep(z, n_dt, cache_s, own_s, nt_s, kk):
            delta_now = own(z) - own_s
            cur_cache = cache_s + delta_now
            cur_t = nt_s + delta_now.sum(axis=0)
            if n_workers > 1:
                kk = jax.random.fold_in(kk, widx)
            z = local_fn(z, docs, words, wts, n_dt, cur_cache, cur_t, kk)
            n_dt = (jnp.zeros((d_local, k), jnp.float32)
                    .at[docs, z].add(wts))
            return z, n_dt

        def window(carry, ks):  # ks: (staleness, 2)
            z, n_dt, cache_s, own_s, nt_s = carry
            for i in range(staleness):
                z, n_dt = one_sweep(z, n_dt, cache_s, own_s, nt_s, ks[i])
            cache_s, nt_s = sync.exchange_deltas(
                support, own(z) - own_s, cache_s, nt_s, axes)
            own_s = own(z)
            return (z, n_dt, cache_s, own_s, nt_s), None

        carry = (z, n_dt, cache, own(z), n_t)
        if n_full:
            ks = keys[: n_full * staleness].reshape(n_full, staleness, 2)
            carry, _ = jax.lax.scan(window, carry, ks)
        z, n_dt, cache_s, own_s, nt_s = carry
        # Tail sweeps (num_sweeps % staleness) need no trailing sync — the
        # boundary rebuild below is exact regardless of cache state.
        for i in range(tail):
            z, n_dt = one_sweep(z, n_dt, cache_s, own_s, nt_s,
                                keys[n_full * staleness + i])

        # Exact boundary rebuild of the authoritative vocab-sharded table:
        # scatter this worker's tokens into (v_pad, K), reduce-scatter
        # across the model axis (each worker keeps only its vocab shard),
        # then sum the data replicas.
        g = jnp.take(support, words)  # global word ids (pads carry wt 0)
        contrib = (jnp.zeros((v_pad, k), jnp.float32)
                   .at[g, z].add(wts))
        n_t_out = jax.lax.psum(contrib.sum(axis=0), axes)
        if model_axis is not None and n_model > 1:
            nwt_out = jax.lax.psum_scatter(
                contrib, model_axis, scatter_dimension=0, tiled=True)
            if data_axes:
                nwt_out = jax.lax.psum(nwt_out, data_axes)
        else:
            nwt_out = jax.lax.psum(contrib, axes)
        return z, n_dt, nwt_out, n_t_out

    flat = P(axes if len(axes) > 1 else axes[0])
    row = P(flat[0], None)
    nwt_spec = (P(model_axis, None)
                if model_axis is not None and n_model > 1
                else P(None, None))
    mapped = make_shard_map(
        shard_fn,
        mesh,
        (flat, flat, flat, flat, flat, row, row, P(), P()),
        (flat, row, nwt_spec, P(None)),
    )
    return jax.jit(mapped)
