"""Pluggable Gibbs-sampler backends behind one `Sampler` protocol.

The engine/backend split of Li et al. (2014): every consumer of topic-model
inference (the `VedaliaService` facade, incremental `update`, benchmarks,
the marketplace runtime) talks to a `Sampler`, and the concrete sweep
implementation is chosen by name:

  jnp          pure-jnp blocked parallel sweep (`core.gibbs`) — the oracle
  pallas       fused Pallas TPU kernel (`kernels.lda_gibbs`), interpret
               mode on CPU — the production TPU path
  distributed  client/server sharded sweep (`core.distributed`) — the
               paper's "model cache and updating server" on a pod, with
               the (V, K) model fully replicated per shard (the small-mesh
               oracle the pserver tier bit-compares against)
  pserver      parameter-server fit tier (`repro.pserver`): doc-sharded
               tokens, vocab-sharded word-topic state across the model
               mesh axis, bounded-staleness support caches synced by
               sparse delta-row exchange — the pod-scale production path
  alias        AliasLDA (Li et al., 2014a) stale-proposal + parallel-MH
               sweep — proposal-based fast sampler; vectorized oracle in
               `core.alias`, fused proposal+MH Pallas kernel in
               `kernels.alias_mh` (path="auto" picks pallas on TPU)
  sparse       SparseLDA (Yao et al., 2009) sequential s/r/q-bucket sweep
               (`core.sparse`) — the paper's phone-side reference
  batched      multi-model batched sweep (`core.batch`): M compatible
               product models stacked into one launch — vmapped jnp oracle
               on CPU, model-grid Pallas kernel on TPU

All backends speak *stored* `LDAState` at the boundary (fixed point when
``cfg.w_bits`` is set — see `repro.api.codec`) so they are interchangeable
mid-run: a model fit by one backend can be updated by another.

Every backend carries a :class:`SamplerCapabilities` record; `"auto"` is a
pseudo-backend resolved by :func:`select_backend` from the workload (corpus
size, fit-vs-update, device kind) against those capabilities.

Register additional backends with :func:`register_backend`; a backend only
needs `sweep(cfg, state, corpus, key)` — `run` has a default loop. The
`repro.core.gibbs` *module* itself satisfies the protocol, which is what
keeps the legacy call sites working unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codec import decode_state, encode_state
from repro.core.types import Corpus, LDAConfig, LDAState, init_state
from repro.obs import trace


@dataclasses.dataclass(frozen=True)
class SamplerCapabilities:
    """What a backend can do — the routing metadata of the registry.

    warm_start:     accepts a prior `LDAState` and continues the chain
                    (required by `refine` and incremental `update`).
    weighted:       honors fractional per-token weights (RLDA's ψ·c), not
                    just unit counts.
    device_kind:    the device class the schedule is designed for:
                    "tpu" (dense parallel sweeps), "pod" (sharded
                    multi-host), "phone" (sequential, cache-friendly).
    proposal_based: draws from a stale proposal corrected by MH rather
                    than the exact conditional (affects mixing per sweep).
    quant_modes:    the `QuantSpec` modes this backend honors in its hot
                    path. Every backend speaks stored state (f32/fixed)
                    at the boundary; backends that additionally read
                    *packed* sweep-stale tables (int8/int4 codes + per-row
                    scales, dequantized in-kernel) list those modes too.
                    A packed-spec config on a backend without packed
                    support still fits correctly — it simply runs on the
                    live f32/fixed representation.
    """

    warm_start: bool = True
    weighted: bool = True
    device_kind: str = "tpu"
    proposal_based: bool = False
    quant_modes: tuple = ("f32", "fixed")

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["quant_modes"] = list(self.quant_modes)
        return d


@runtime_checkable
class Sampler(Protocol):
    """One full-corpus collapsed-Gibbs sweep engine."""

    def sweep(
        self, cfg: LDAConfig, state: LDAState, corpus: Corpus, key: jax.Array
    ) -> LDAState: ...

    def run(
        self,
        cfg: LDAConfig,
        corpus: Corpus,
        key: jax.Array,
        num_sweeps: int,
        state: Optional[LDAState] = None,
    ) -> LDAState: ...


_REGISTRY: dict[str, type] = {}

#: Pseudo-backend name resolved per workload by :func:`select_backend`.
AUTO = "auto"


def register_backend(name: str, capabilities: Optional[SamplerCapabilities] = None):
    """Class decorator: make `get_backend(name)` construct this sampler."""

    def deco(cls):
        cls.name = name
        if capabilities is not None:
            cls.capabilities = capabilities
        elif not hasattr(cls, "capabilities"):
            cls.capabilities = SamplerCapabilities()
        _REGISTRY[name] = cls
        return cls

    return deco


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


def backend_capabilities(name: Optional[str] = None):
    """Capabilities of one backend, or `{name: SamplerCapabilities}` for all."""
    if name is not None:
        try:
            return _REGISTRY[name].capabilities
        except KeyError:
            raise KeyError(
                f"unknown sampler backend {name!r}; "
                f"available: {available_backends()}"
            ) from None
    return {n: cls.capabilities for n, cls in sorted(_REGISTRY.items())}


# Workload-size boundary above which the O(k_d)-per-token proposal sampler
# (alias) beats the dense parallel sweep's O(k) score tile.
_LARGE_CORPUS_TOKENS = 100_000


def select_backend(
    *,
    num_tokens: int = 0,
    task: str = "fit",
    device_kind: Optional[str] = None,
    available: Optional[list[str]] = None,
    num_models: int = 1,
) -> str:
    """Resolve the `"auto"` pseudo-backend for a workload.

    Routing order (first match wins):
      1. multi-model work (`num_models > 1` — batch fits, coalesced
         refits) goes to the stacked `batched` sweep — one launch for all
         M models instead of M cold launches — *including* under an
         explicit `device_kind`, as long as the batched backend is built
         for that device class (an explicit "tpu" must not silently
         serialize a coalesced refit);
      2. an explicit `device_kind` picks the backend built for that device
         class ("phone" -> sparse, "pod" -> pserver, "tpu" -> jnp); the
         replicated `distributed` backend stays registered as the pod
         small-mesh oracle but is no longer the routed default;
      3. updates go to the oracle sweep — incremental resampling needs
         exact-conditional warm-start semantics, not MH proposals;
      4. large fits go to the proposal sampler (`alias`), whose per-token
         cost is independent of K;
      5. everything else gets the jnp oracle.
    """
    names = set(available if available is not None else available_backends())

    def pick(*candidates: str) -> str:
        for c in candidates:
            if c in names:
                return c
        return "jnp"

    if device_kind is not None:
        if num_models > 1:
            batched = _REGISTRY.get("batched")
            if ("batched" in names and batched is not None
                    and batched.capabilities.device_kind == device_kind):
                return "batched"
        preferred = {"phone": "sparse", "pod": "pserver", "tpu": "jnp"}
        want = preferred.get(device_kind)
        if want in names:
            return want
        for n in sorted(names):  # any backend built for that device class
            cls = _REGISTRY.get(n)  # `available` may list remote-only names
            if cls is not None and cls.capabilities.device_kind == device_kind:
                return n
        return pick("jnp")
    if num_models > 1:
        return pick("batched", "jnp")
    if task == "update":
        return pick("jnp")
    if num_tokens >= _LARGE_CORPUS_TOKENS:
        return pick("alias", "jnp")
    return pick("jnp")


def get_backend(name: str = "jnp", **opts) -> Sampler:
    """Construct a registered sampler backend by name."""
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown sampler backend {name!r}; "
            f"available: {available_backends()}"
        ) from None
    return cls(**opts)


class _BaseSampler:
    """Default multi-sweep driver with the same key discipline as
    `gibbs.run` (split for init, then one subkey per sweep) so backends
    are drop-in comparable from identical seeds."""

    @trace.span("sampler.run")
    def run(self, cfg, corpus, key, num_sweeps, state=None):
        if state is None:
            key, sub = jax.random.split(key)
            state = encode_state(cfg, init_state(cfg, corpus, sub))
        for k in jax.random.split(key, num_sweeps):
            state = self.sweep(cfg, state, corpus, k)
        return state

    def __repr__(self):
        return f"{type(self).__name__}(name={getattr(self, 'name', '?')!r})"


@register_backend("jnp", SamplerCapabilities(device_kind="tpu"))
class JnpSampler(_BaseSampler):
    """The pure-jnp blocked parallel sweep — system path and parity oracle."""

    def __init__(self, block: int = 4096):
        self.block = block

    def sweep(self, cfg, state, corpus, key):
        from repro.core import gibbs

        return gibbs.sweep(cfg, state, corpus, key, self.block)

    @trace.span("sampler.run")
    def run(self, cfg, corpus, key, num_sweeps, state=None):
        # gibbs.run scans the sweeps under one jit — keep that fast path.
        from repro.core import gibbs

        return gibbs.run(cfg, corpus, key, num_sweeps, state=state,
                         block=self.block)


@register_backend(
    "pallas",
    SamplerCapabilities(
        device_kind="tpu",
        quant_modes=("f32", "fixed", "int8", "int4_packed")),
)
class PallasSampler(_BaseSampler):
    """The fused Pallas score+Gumbel-max kernel (interpret mode on CPU)."""

    def sweep(self, cfg, state, corpus, key):
        from repro.kernels.lda_gibbs import ops as kops

        return kops.sweep(cfg, state, corpus, key)


@register_backend("distributed", SamplerCapabilities(device_kind="pod"))
class DistributedSampler(_BaseSampler):
    """Client/server sharded sweep (`core.distributed`) on a device mesh.

    Counts cross the boundary in stored units and are decoded/encoded here;
    the sharded sweep itself is real-valued float32.

    Caller contract (mesh): the mesh must use the production axis names of
    `launch.mesh` — data parallelism on ("pod",) "data", an optional minor
    "model" axis (unsharded here: the model is replicated). The lazy
    default places every local device on the data axis of a
    ("data", "model") mesh. With a single data shard global doc ids are
    shard-local ids; on a multi-shard mesh the caller contract of
    `core.distributed` applies (documents contiguously partitioned in
    blocks of ceil(num_docs / n_shards), shard-local ids, token arrays
    padded per shard — `core.distributed.shard_corpus` builds that
    layout). The `pserver` backend does this partitioning itself and is
    the routed pod default; this backend remains the replicated
    small-mesh oracle.
    """

    # Compiled shard_map programs are cached per LDAConfig; streaming
    # updates grow num_docs every round, so bound the cache (LRU) or a
    # long-lived service leaks one compiled program per update.
    _MAX_CACHED_PROGRAMS = 8

    def __init__(self, mesh=None, block: int = 4096, sync_every: int = 1):
        self.mesh = mesh
        self.block = block
        self.sync_every = sync_every
        self._cache: dict[LDAConfig, object] = {}

    def _mesh(self):
        if self.mesh is None:
            from repro.launch.mesh import make_data_mesh

            # Production axis names (launch.mesh), all devices on data.
            self.mesh = make_data_mesh()
        return self.mesh

    def _sweep_fn(self, cfg: LDAConfig):
        fn = self._cache.pop(cfg, None)
        if fn is None:
            from repro.core import distributed

            raw = distributed.make_client_server_sweep(
                cfg, self._mesh(), block=self.block,
                sync_every=self.sync_every)
            fn = jax.jit(raw)
        self._cache[cfg] = fn  # re-insert: dict order is recency order
        while len(self._cache) > self._MAX_CACHED_PROGRAMS:
            self._cache.pop(next(iter(self._cache)))
        return fn

    def sweep(self, cfg, state, corpus, key):
        real = decode_state(cfg, state)
        fn = self._sweep_fn(cfg)
        z, n_dt, n_wt, n_t = fn(
            corpus.docs, corpus.words, real.z, corpus.weights,
            real.n_dt, real.n_wt, key)
        return encode_state(
            cfg, LDAState(z=z, n_dt=n_dt, n_wt=n_wt, n_t=n_t))


@register_backend("pserver", SamplerCapabilities(device_kind="pod"))
class PServerSampler(_BaseSampler):
    """Parameter-server fit tier (`repro.pserver`) — the routed pod path.

    Doc-sharded tokens across every mesh device, vocab-sharded
    authoritative word-topic state across the "model" axis, and
    bounded-staleness per-worker support caches synced by sparse delta-row
    exchange every `staleness` sweeps — see `repro.pserver` for the
    architecture and `core.distributed` for the replicated oracle it
    bit-compares against at mesh size 1.

    Unlike `DistributedSampler`, callers hand over a flat corpus with
    *global* doc ids; the tier plans its own contiguous partition (any
    corpus fits any mesh). `local` picks the per-worker sweep engine:
    "gibbs" (the exact-conditional `core.distributed.local_sweep`),
    "pallas" (the fused `kernels.lda_gibbs` tile kernel), "mh" (AliasLDA
    stale-proposal MH whose accept step absorbs the cache staleness), or
    "auto" (pallas on TPU, gibbs elsewhere). The mesh defaults to all
    local devices on the data axis of a ("data", "model") mesh.
    """

    def __init__(self, mesh=None, block: int = 4096, staleness: int = 1,
                 local: str = "auto", cap=None, mh_steps: int = 4):
        from repro.pserver.sampler import PServerFit

        self._fit = PServerFit(
            mesh=mesh, block=block, staleness=staleness, local=local,
            cap=cap, mh_steps=mh_steps)
        self.staleness = staleness

    def sweep(self, cfg, state, corpus, key):
        return self._fit.sweep(cfg, state, corpus, key)

    @trace.span("sampler.run")
    def run(self, cfg, corpus, key, num_sweeps, state=None):
        return self._fit.run(cfg, corpus, key, num_sweeps, state=state)


@register_backend(
    "alias",
    SamplerCapabilities(
        device_kind="tpu", proposal_based=True,
        quant_modes=("f32", "fixed", "int8", "int4_packed")),
)
class AliasSampler(_BaseSampler):
    """AliasLDA sweep-parallel MH (`core.alias` / `kernels.alias_mh`).

    Stale per-word alias proposals + parallel Metropolis–Hastings; the
    per-token cost is O(k_d), independent of K, so this is the large-corpus
    fit path. Counts cross the boundary in stored units.

    `path` selects the execution path per sweep — the same split as
    `BatchedSampler`: "jnp" is the vectorized oracle (`core.alias.mh_sweep`
    on decoded counts), "pallas" the fused proposal+MH kernel
    (`kernels.alias_mh.ops`, interpret mode on CPU, bit-exact vs the
    oracle from identical keys), and "auto" (default) picks pallas on TPU
    and the oracle elsewhere.

    The stacked `run_many` surface (leading (M,) axis, the
    `BatchedSampler` protocol) lets `serving.batch_engine` bucket
    multi-model alias fits into single launches: "pallas" rides the
    model-grid `mh_sweep_many` kernel, "jnp" the vmapped oracle — all
    sweeps of all M models scanned under one jit (`core.alias.run_many`).
    """

    def __init__(self, mh_steps: int = 4, path: str = "auto"):
        if path not in ("auto", "jnp", "pallas"):
            raise ValueError(f"unknown alias path {path!r}")
        self.mh_steps = mh_steps
        self.path = path

    def _path(self) -> str:
        if self.path != "auto":
            return self.path
        return "pallas" if jax.default_backend() == "tpu" else "jnp"

    def sweep(self, cfg, state, corpus, key):
        if self._path() == "pallas":
            from repro.kernels.alias_mh import ops as kops

            return kops.mh_sweep(cfg, state, corpus, key, self.mh_steps)
        from repro.core import alias

        real = decode_state(cfg, state)
        return encode_state(
            cfg, alias.mh_sweep(cfg, real, corpus, key, self.mh_steps))

    @trace.span("sampler.run_many")
    def run_many(self, cfg, corpora, keys, num_sweeps, states=None):
        """Batched multi-sweep alias fit/refit (cold when `states` is
        None): all sweeps of all M models scanned under one jit
        (`core.alias.run_many`), with `_BaseSampler.run`'s per-model key
        discipline so a batched run is comparable to M sequential runs
        from the same keys."""
        from repro.core import alias
        from repro.core import batch as batch_lib

        if states is None:
            pairs = jax.vmap(jax.random.split)(keys)  # (M, 2, 2)
            keys, subs = pairs[:, 0], pairs[:, 1]
            states = batch_lib.init_many(cfg, corpora, subs)
        return alias.run_many(
            cfg, states, corpora, keys, num_sweeps, self.mh_steps,
            self._path())


@register_backend(
    "sparse",
    SamplerCapabilities(device_kind="phone"),
)
class SparseSampler(_BaseSampler):
    """SparseLDA sequential s/r/q-bucket sweep (`core.sparse`).

    The paper's phone-side sampler as a first-class backend: exact
    sequential collapsed Gibbs in numpy, O(k_d + k_w) per token. Slow on
    large corpora by design — it models the mobile device, and is the
    `device_kind="phone"` route of the `auto` selector.
    """

    def __init__(self, dense: bool = False):
        self.dense = dense  # True => the O(k) MALLET-style baseline

    def _sequential(self, cfg, state, corpus, key, num_sweeps):
        from repro.core import sparse
        from repro.core.codec import decode_counts_np, rebuild_state

        cls = sparse.DenseGibbsSampler if self.dense else sparse.SparseLDASampler
        # Stored counts cross the boundary decoded, not rebuilt from
        # (z, weights): for incremental updates the corpus freezes old
        # tokens by zeroing their weights while their mass must keep
        # participating in the conditional. The numpy seed derives from the
        # jax key so backends are comparable from identical seeds.
        seed = int(jax.random.randint(key, (), 0, np.iinfo(np.int32).max))
        s = cls(
            cfg,
            np.asarray(corpus.docs),
            np.asarray(corpus.words),
            np.asarray(state.z),
            weights=np.asarray(corpus.weights, np.float64),
            seed=seed,
            counts=decode_counts_np(cfg, state),
        )
        s.run(num_sweeps)
        return rebuild_state(cfg, corpus, jnp.asarray(s.z, jnp.int32))

    def sweep(self, cfg, state, corpus, key):
        return self._sequential(cfg, state, corpus, key, 1)

    @trace.span("sampler.run")
    def run(self, cfg, corpus, key, num_sweeps, state=None):
        if state is None:
            key, sub = jax.random.split(key)
            state = encode_state(cfg, init_state(cfg, corpus, sub))
        # One sampler instance for the whole run: counts and bucket caches
        # are built once, not once per sweep.
        return self._sequential(cfg, state, corpus, key, num_sweeps)


@register_backend("batched", SamplerCapabilities(device_kind="tpu"))
class BatchedSampler(_BaseSampler):
    """Multi-model batched sweep (`core.batch`): M compatible product
    models stacked into one launch.

    The stacked surface is `run_many`/`sweep_batch` (leading (M,) axis on
    every `Corpus`/`LDAState` leaf; `serving.batch_engine` does the
    bucketing and padding). `path` selects the execution path per launch:
    "jnp" is the vmapped oracle sweep, "pallas" the model-grid fused
    kernel, and "auto" (default) picks pallas on TPU and the oracle
    elsewhere — the same split as the single-model backends.

    The single-model `Sampler` protocol still works (an M=1 stack), so
    `backend="batched"` is valid anywhere a backend name is accepted.
    """

    def __init__(self, path: str = "auto", block: int = 4096):
        if path not in ("auto", "jnp", "pallas"):
            raise ValueError(f"unknown batched path {path!r}")
        self.path = path
        self.block = block

    def _path(self) -> str:
        if self.path != "auto":
            return self.path
        return "pallas" if jax.default_backend() == "tpu" else "jnp"

    def sweep_batch(self, cfg, states, corpora, keys):
        """One fused sweep over stacked models ((M, 2) keys)."""
        from repro.core import batch

        return batch.sweep_batch(
            cfg, states, corpora, keys, self.block, self._path())

    @trace.span("sampler.run_many")
    def run_many(self, cfg, corpora, keys, num_sweeps, states=None):
        """Batched multi-sweep fit/refit: cold when `states` is None."""
        from repro.core import batch

        return batch.fit_many(
            cfg, corpora, keys, num_sweeps, states=states, block=self.block,
            path=self._path())

    def _stack1(self, tree):
        return jax.tree_util.tree_map(lambda x: x[None], tree)

    def sweep(self, cfg, state, corpus, key):
        out = self.sweep_batch(
            cfg, self._stack1(state), self._stack1(corpus), key[None])
        return jax.tree_util.tree_map(lambda x: x[0], out)

    def run(self, cfg, corpus, key, num_sweeps, state=None):
        out = self.run_many(
            cfg, self._stack1(corpus), key[None], num_sweeps,
            states=None if state is None else self._stack1(state))
        return jax.tree_util.tree_map(lambda x: x[0], out)
