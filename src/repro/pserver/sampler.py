"""Backend-shaped driver for the parameter-server fit tier.

`PServerFit` owns the host-side lifecycle: build/cache the placement plan
(`topology.build_plan`) per (corpus, mesh), build/cache the compiled
shard_map program (`sweep.make_pserver_program`) per shape class, shuffle
state/corpus into the plan's padded worker layout, and translate back at
the boundary. Counts cross the boundary in *stored* units (fixed point
when ``cfg.w_bits`` is set) exactly like every other backend; internally
everything is real-valued float32.

Key discipline matches `gibbs.run` (split for init, one subkey per
sweep), and on a 1-worker mesh the whole pipeline — identity token
permutation, unfolded worker key, `local="gibbs"` — reproduces the jnp
oracle bit for bit from identical keys (see `sweep.py`). On the w_bits
path a multi-sweep `run` loops single-sweep programs so the per-sweep
quantization round-trip matches the oracle chain too.

The mesh defaults to all local devices on the data axis of a
("data", "model") mesh (production axis names, `launch.mesh`); pass an
explicit mesh to vocab-shard across a model axis. Unlike
`core.distributed`, callers hand over a *flat* corpus with global doc
ids — the plan does the partitioning.
"""

from __future__ import annotations

import hashlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.core.types import Corpus, LDAConfig, LDAState, init_state
from repro.launch.mesh import make_data_mesh
from repro.obs import metrics, timers
from repro.pserver import sync as sync_lib
from repro.pserver import topology
from repro.pserver.sweep import make_pserver_program

#: Sync accounting happens here, the host-side launch boundary — inside
#: the compiled shard_map program there is no host to count on. Bytes are
#: the analytic per-device cost of `pserver.sync` (what the wire would
#: carry), not a measured transport.
_SYNCS = metrics.counter(
    "vedalia_pserver_syncs_total",
    "Stale-synchronous model syncs executed (full windows only).")
_SYNC_BYTES = metrics.counter(
    "vedalia_pserver_sync_bytes_total",
    "Analytic per-device bytes moved by pserver syncs.")
_STALENESS = metrics.gauge(
    "vedalia_pserver_staleness",
    "Configured sweeps-per-sync window of the last launch.")
_FIT_SECONDS = metrics.histogram(
    "vedalia_pserver_fit_seconds",
    "Wall time of one pserver program launch (device-synced).",
    labels=("local",))


class PServerFit:
    """Stale-synchronous sharded fit engine (see module docstring)."""

    # Plans and compiled programs are cached per shape class; streaming
    # updates grow corpora every round, so bound both caches (LRU) or a
    # long-lived service leaks one compiled program per update.
    _MAX_CACHED = 8

    def __init__(self, mesh=None, block: int = 4096, staleness: int = 1,
                 local: str = "auto", cap: Optional[int] = None,
                 mh_steps: int = 4):
        if local not in ("auto", "gibbs", "pallas", "mh"):
            raise ValueError(f"unknown pserver local engine {local!r}")
        if staleness < 1:
            raise ValueError(f"staleness must be >= 1, got {staleness}")
        self.mesh = mesh
        self.block = block
        self.staleness = staleness
        self.local = local
        self.cap = cap
        self.mh_steps = mh_steps
        self._plans: dict[tuple, topology.PServerPlan] = {}
        self._programs: dict[tuple, object] = {}

    # -- caches -------------------------------------------------------------

    def _mesh(self):
        if self.mesh is None:
            self.mesh = make_data_mesh()
        return self.mesh

    def _local(self) -> str:
        if self.local != "auto":
            return self.local
        return "pallas" if jax.default_backend() == "tpu" else "gibbs"

    @staticmethod
    def _lru_get(cache, key, build):
        val = cache.pop(key, None)
        if val is None:
            val = build()
        cache[key] = val  # re-insert: dict order is recency order
        while len(cache) > PServerFit._MAX_CACHED:
            cache.pop(next(iter(cache)))
        return val

    def _mesh_dims(self, mesh) -> tuple[int, int]:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_model = sizes.get("model", 1)
        n_data = int(mesh.devices.size) // n_model
        return n_data, n_model

    def _plan(self, cfg: LDAConfig, corpus: Corpus) -> topology.PServerPlan:
        n_data, n_model = self._mesh_dims(self._mesh())
        docs = np.asarray(corpus.docs)
        words = np.asarray(corpus.words)
        digest = hashlib.sha1(docs.tobytes() + words.tobytes()).hexdigest()
        key = (cfg.num_docs, cfg.vocab_size, n_data, n_model, self.cap,
               corpus.num_tokens, digest)
        return self._lru_get(
            self._plans, key,
            lambda: topology.build_plan(
                cfg, docs, words, n_data, n_model, cap=self.cap))

    def _program(self, cfg: LDAConfig, plan: topology.PServerPlan,
                 num_sweeps: int, staleness: int):
        mesh = self._mesh()
        key = (cfg, id(mesh), plan.d_local, plan.t_local, plan.cap,
               plan.v_pad, num_sweeps, staleness, self.block, self._local(),
               self.mh_steps)
        return self._lru_get(
            self._programs, key,
            lambda: make_pserver_program(
                cfg, mesh, plan, num_sweeps=num_sweeps, staleness=staleness,
                block=self.block, local=self._local(),
                mh_steps=self.mh_steps))

    # -- boundary -----------------------------------------------------------

    def _fit(self, cfg: LDAConfig, real: LDAState, corpus: Corpus,
             keys: jax.Array, staleness: int) -> LDAState:
        """Run one program over real-valued state; keys is (S, 2)."""
        mesh = self._mesh()
        plan = self._plan(cfg, corpus)
        prog = self._program(cfg, plan, int(keys.shape[0]), staleness)

        perm = jnp.asarray(plan.perm)
        sup = jnp.asarray(plan.support.reshape(-1))
        z_p = jnp.take(real.z.astype(jnp.int32), perm,
                       mode="fill", fill_value=0)
        wts_p = jnp.take(corpus.weights, perm, mode="fill", fill_value=0.0)
        # Sentinel support ids are one past v_pad's last row: OOB gathers
        # fill 0, so unused cache rows start (and stay) empty.
        cache0 = jnp.take(real.n_wt, sup, axis=0, mode="fill",
                          fill_value=0.0)
        pad_rows = plan.n_workers * plan.d_local - cfg.num_docs
        n_dt_p = jnp.pad(real.n_dt, ((0, pad_rows), (0, 0)))

        timer = timers.DeviceTimer(
            _FIT_SECONDS, local=self._local()).start()
        z_p, n_dt_p, n_wt, n_t = prog(
            jnp.asarray(plan.docs_l), jnp.asarray(plan.words_l),
            z_p, wts_p, sup, n_dt_p, cache0, real.n_t, keys)
        timer.sync(n_wt)
        # Sync accounting mirrors the program's schedule: one model sync
        # per *full* staleness window (`divmod` in sweep.py — tail sweeps
        # run on stale reads and never pay a trailing sync).
        num_syncs = int(keys.shape[0]) // staleness
        if num_syncs:
            _SYNCS.inc(num_syncs)
            _SYNC_BYTES.inc(num_syncs * sync_lib.sync_bytes_per_device(
                plan.n_workers, plan.cap, cfg.num_topics))
        _STALENESS.set(staleness)
        z = jnp.take(z_p, jnp.asarray(plan.inv))
        return LDAState(z=z, n_dt=n_dt_p[: cfg.num_docs],
                        n_wt=n_wt[: cfg.vocab_size], n_t=n_t)

    # -- Sampler protocol ---------------------------------------------------

    def sweep(self, cfg: LDAConfig, state: LDAState, corpus: Corpus,
              key: jax.Array) -> LDAState:
        real = codec.decode_state(cfg, state)
        out = self._fit(cfg, real, corpus, key[None], staleness=1)
        return codec.encode_state(cfg, out)

    def run(self, cfg: LDAConfig, corpus: Corpus, key: jax.Array,
            num_sweeps: int, state: Optional[LDAState] = None) -> LDAState:
        if state is None:
            key, sub = jax.random.split(key)
            state = codec.encode_state(cfg, init_state(cfg, corpus, sub))
        if num_sweeps <= 0:
            return state
        keys = jax.random.split(key, num_sweeps)
        if cfg.quant_spec.live_fixed:
            # Stored-unit quantization between sweeps must match the
            # oracle chain (encode/decode round-trip per sweep), so the
            # fused multi-sweep program only serves the float32 path.
            for k in keys:
                state = self.sweep(cfg, state, corpus, k)
            return state
        real = codec.decode_state(cfg, state)
        out = self._fit(cfg, real, corpus, keys, self.staleness)
        return codec.encode_state(cfg, out)

    def __repr__(self):
        return (f"PServerFit(staleness={self.staleness}, "
                f"local={self.local!r})")
