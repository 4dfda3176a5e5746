"""`VedaliaService` — the one public facade over the paper's system (§3-§5).

Reviews stream in, RLDA models are fit and incrementally updated, and
bandwidth-frugal model views stream out. The service composes the pieces
every consumer used to hand-wire —

    rlda.prepare -> <sampler backend>.run -> update.add_documents
                 -> coreset.select_core_set -> views.build_view

— behind four verbs with typed request/response dataclasses:

    fit(reviews)            -> ModelHandle
    update(handle, reviews) -> UpdateResponse   (incremental, §3.2)
    view(handle)            -> ViewResponse     (streamed payload, §4.2)
    top_reviews(handle, t)  -> TopReviewsResponse (ViewPager order, §3.4)

The sampler backend ("jnp" | "pallas" | "distributed", see
`repro.api.backends`) is chosen per service or per call; a model fit by one
backend can be refined or updated by another because all backends share the
stored-state codec (`repro.api.codec`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.backends import AUTO, Sampler, get_backend, select_backend
from repro.core import codec, coreset, perplexity as perplexity_lib, rlda, update
from repro.core import views as views_lib
from repro.core.rlda import Review, RLDACorpus
from repro.core.types import LDAState
from repro.core.views import ModelView
from repro.obs import metrics, timers, trace

#: Backend-labelled service-op latency — the tier-attribution histogram
#: ("where do the milliseconds go") the ISSUE's motivation asks for. Device
#: ops (`fit`, `refine*`, `update`) stop via `DeviceTimer.sync(state)` so
#: async dispatch can't fake a fast sampler.
_OP_SECONDS = metrics.histogram(
    "vedalia_service_op_seconds",
    "Service operation latency by op and sampler backend.",
    labels=("op", "backend"))
_VIEW_BYTES = metrics.histogram(
    "vedalia_service_view_bytes",
    "Serialized view payload size (what a device downloads).",
    labels=(), buckets=metrics.BYTE_BUCKETS)


@dataclasses.dataclass(frozen=True)
class FitRequest:
    """A fit task (also the queue item of `serving.TopicEngine`)."""

    uid: int
    reviews: Sequence[Review]
    num_topics: int = 12
    base_vocab: Optional[int] = None  # None => inferred from the reviews
    alpha: float = 0.1
    beta: float = 0.01
    w_bits: Optional[int] = 8
    backend: Optional[str] = None  # None => the service default
    num_sweeps: Optional[int] = None
    top_n: int = 10  # used by TopicEngine's fit+view serving


@dataclasses.dataclass
class ModelHandle:
    """A served topic model: prepared corpus metadata + live sampler state.

    `prep` grows with every `update` (helpfulness/rating metadata must cover
    the appended reviews so views stay computable).
    """

    handle_id: int
    prep: RLDACorpus
    model: update.UpdatableModel
    backend: str
    sweeps_run: int = 0

    @property
    def cfg(self):
        return self.model.cfg

    @property
    def state(self) -> LDAState:
        return self.model.state

    @property
    def num_reviews(self) -> int:
        return self.model.cfg.num_docs


@dataclasses.dataclass(frozen=True)
class UpdateResponse:
    handle_id: int
    num_new_reviews: int
    kind: str  # "incremental" | "full_recompute"
    perplexity: float


@dataclasses.dataclass(frozen=True)
class ViewResponse:
    handle_id: int
    view: ModelView
    topic_ids: list[int]
    payload: str  # the JSON actually streamed to a device
    valid: bool  # Chital validation stage (§2.5.5)

    @property
    def payload_bytes(self) -> int:
        return len(self.payload)


@dataclasses.dataclass(frozen=True)
class TopReviewsResponse:
    handle_id: int
    topic_id: int
    review_ids: list[int]


@dataclasses.dataclass(frozen=True)
class SpotCheckResponse:
    """Outcome of the server-side check of a device-computed state.

    `state_perplexity` is the server's own recomputation on the submitted
    state (never trusted from the claim); `post_perplexity` is the
    perplexity after `num_sweeps` of server-side re-Gibbs on a throwaway
    copy — the Eq. (6) verification step made real. `deviation` is the
    relative gap between the claimed and recomputed perplexity, when a
    claim was supplied.
    """

    valid: bool
    reason: str
    state_perplexity: Optional[float] = None
    post_perplexity: Optional[float] = None
    deviation: Optional[float] = None


def _infer_base_vocab(reviews: Sequence[Review]) -> int:
    hi = 0
    for r in reviews:
        if len(r.tokens):
            hi = max(hi, int(np.max(r.tokens)))
    return hi + 1


class VedaliaService:
    """Fit / update / view topic models through pluggable sampler backends."""

    def __init__(
        self,
        *,
        backend: str = "jnp",
        num_sweeps: int = 30,
        update_sweeps: int = 3,
        backend_opts: Optional[dict] = None,
        seed: int = 0,
    ):
        self.default_backend = backend
        self.num_sweeps = num_sweeps
        self.update_sweeps = update_sweeps
        self._backend_opts = dict(backend_opts or {})
        self._samplers: dict[str, Sampler] = {}
        self._seed = seed
        self._op = 0
        self.handles: dict[int, ModelHandle] = {}
        self._next_id = 0

    # -- internals ---------------------------------------------------------

    def sampler(self, name: Optional[str] = None) -> Sampler:
        """The (cached) sampler backend instance for `name`."""
        name = name or self.default_backend
        if name == AUTO:  # no workload context here: the generic route
            name = select_backend()
        if name not in self._samplers:
            self._samplers[name] = get_backend(
                name, **self._backend_opts.get(name, {}))
        return self._samplers[name]

    def _resolve(
        self,
        backend: Optional[str],
        *,
        num_tokens: int,
        task: str,
        device_kind: Optional[str] = None,
        num_models: int = 1,
    ) -> str:
        """Concrete backend name for a call (routes the `auto` pseudo-backend
        by workload: corpus size, fit-vs-update, device kind, model count)."""
        backend = backend or self.default_backend
        if backend == AUTO:
            backend = select_backend(
                num_tokens=num_tokens, task=task, device_kind=device_kind,
                num_models=num_models)
        return backend

    def _key(self, seed: Optional[int] = None) -> jax.Array:
        if seed is not None:
            return jax.random.PRNGKey(seed)
        self._op += 1
        return jax.random.PRNGKey(self._seed * 1_000_003 + self._op)

    def _keys(self, m: int, seed: Optional[int] = None) -> list[jax.Array]:
        """One independent PRNG key per model of a batch."""
        if seed is not None:
            base = jax.random.PRNGKey(seed)
            return [jax.random.fold_in(base, i) for i in range(m)]
        return [self._key() for _ in range(m)]

    def _register(self, handle: ModelHandle) -> ModelHandle:
        self.handles[handle.handle_id] = handle
        return handle

    def _new_id(self) -> int:
        hid = self._next_id
        self._next_id += 1
        return hid

    # -- fit ---------------------------------------------------------------

    def fit(
        self,
        reviews: Sequence[Review],
        *,
        num_topics: int = 12,
        base_vocab: Optional[int] = None,
        alpha: float = 0.1,
        beta: float = 0.01,
        w_bits: Optional[int] = 8,
        backend: Optional[str] = None,
        num_sweeps: Optional[int] = None,
        seed: Optional[int] = None,
        device_kind: Optional[str] = None,
    ) -> ModelHandle:
        """Prepare raw reviews (§4.3 transformation) and fit from scratch."""
        if not len(reviews):
            raise ValueError("fit() needs at least one review")
        if base_vocab is None:
            base_vocab = _infer_base_vocab(reviews)
        prep = rlda.prepare(
            list(reviews), base_vocab=base_vocab, num_topics=num_topics,
            alpha=alpha, beta=beta, w_bits=w_bits)
        return self.fit_prepared(
            prep, backend=backend, num_sweeps=num_sweeps, seed=seed,
            device_kind=device_kind)

    @trace.span("service.fit_prepared")
    def fit_prepared(
        self,
        prep: RLDACorpus,
        *,
        backend: Optional[str] = None,
        num_sweeps: Optional[int] = None,
        seed: Optional[int] = None,
        device_kind: Optional[str] = None,
    ) -> ModelHandle:
        """Fit an already-prepared RLDA corpus (custom weighting paths)."""
        backend = self._resolve(
            backend, num_tokens=prep.corpus.num_tokens, task="fit",
            device_kind=device_kind)
        sweeps = num_sweeps if num_sweeps is not None else self.num_sweeps
        timer = timers.DeviceTimer(_OP_SECONDS, op="fit", backend=backend)
        timer.start()
        state = self.sampler(backend).run(
            prep.cfg, prep.corpus, self._key(seed), sweeps)
        timer.sync(state.n_wt)
        model = update.UpdatableModel(
            cfg=prep.cfg, corpus=prep.corpus, state=state)
        return self._register(ModelHandle(
            handle_id=self._new_id(), prep=prep, model=model,
            backend=backend, sweeps_run=sweeps))

    @trace.span("service.fit_batch")
    def fit_batch(
        self,
        review_sets: Sequence[Sequence[Review]],
        *,
        num_topics: int = 12,
        base_vocab: Optional[int] = None,
        alpha: float = 0.1,
        beta: float = 0.01,
        w_bits: Optional[int] = 8,
        backend: Optional[str] = None,
        num_sweeps: Optional[int] = None,
        seed: Optional[int] = None,
        device_kind: Optional[str] = None,
    ) -> list[ModelHandle]:
        """Fit one model per review set — batched into as few sampler
        launches as bucketing allows (`serving.batch_engine`).

        All sets share the fit parameters, so the prepared models are
        stack-compatible by construction; a `base_vocab` of None is
        inferred over *all* sets jointly (per-set inference would make the
        models vocabulary-incompatible).
        """
        if not len(review_sets):
            raise ValueError("fit_batch() needs at least one review set")
        for i, rs in enumerate(review_sets):
            if not len(rs):
                raise ValueError(f"fit_batch() review set {i} is empty")
        if base_vocab is None:
            base_vocab = max(_infer_base_vocab(rs) for rs in review_sets)
        preps = [
            rlda.prepare(
                list(rs), base_vocab=base_vocab, num_topics=num_topics,
                alpha=alpha, beta=beta, w_bits=w_bits)
            for rs in review_sets
        ]
        return self.fit_batch_prepared(
            preps, backend=backend, num_sweeps=num_sweeps, seed=seed,
            device_kind=device_kind)

    def fit_batch_prepared(
        self,
        preps: Sequence[RLDACorpus],
        *,
        backend: Optional[str] = None,
        num_sweeps: Optional[int] = None,
        seed: Optional[int] = None,
        device_kind: Optional[str] = None,
    ) -> list[ModelHandle]:
        """Batched fit of already-prepared corpora (one handle each).

        The `auto` route resolves multi-model fits to the `batched`
        backend. Any resolved backend whose sampler carries the stacked
        `run_many` surface (`batched`, `alias`) launches through
        `serving.batch_engine`; other backends (or a single model) fall
        back to sequential `fit_prepared` calls, so the surface is safe
        to call unconditionally.
        """
        if not len(preps):
            raise ValueError("fit_batch_prepared() needs at least one corpus")
        total_tokens = sum(p.corpus.num_tokens for p in preps)
        backend = self._resolve(
            backend, num_tokens=total_tokens, task="fit",
            device_kind=device_kind, num_models=len(preps))
        sampler = self.sampler(backend)
        if len(preps) == 1 or not hasattr(sampler, "run_many"):
            return [
                self.fit_prepared(
                    p, backend=backend, num_sweeps=num_sweeps,
                    seed=seed if seed is None else seed + i)
                for i, p in enumerate(preps)
            ]
        import repro.serving.batch_engine as batch_engine

        sweeps = num_sweeps if num_sweeps is not None else self.num_sweeps
        timer = timers.DeviceTimer(
            _OP_SECONDS, op="fit_batch", backend=backend)
        timer.start()
        states, _ = batch_engine.run_batched(
            sampler,
            [p.cfg for p in preps],
            [p.corpus for p in preps],
            self._keys(len(preps), seed),
            sweeps,
        )
        timer.sync(states[-1].n_wt)
        return [
            self._register(ModelHandle(
                handle_id=self._new_id(), prep=p,
                model=update.UpdatableModel(
                    cfg=p.cfg, corpus=p.corpus, state=st),
                backend=backend, sweeps_run=sweeps))
            for p, st in zip(preps, states)
        ]

    def adopt(
        self,
        prep: RLDACorpus,
        state: LDAState,
        *,
        backend: Optional[str] = None,
        sweeps_run: int = 0,
    ) -> ModelHandle:
        """Wrap an externally-fitted state (e.g. a Chital marketplace
        winner's submission payload) into a served handle."""
        model = update.UpdatableModel(
            cfg=prep.cfg, corpus=prep.corpus, state=state)
        return self._register(ModelHandle(
            handle_id=self._new_id(), prep=prep, model=model,
            backend=self._resolve(
                backend, num_tokens=prep.corpus.num_tokens, task="update"),
            sweeps_run=sweeps_run))

    @trace.span("service.refine")
    def refine(
        self,
        handle: ModelHandle,
        num_sweeps: int,
        *,
        backend: Optional[str] = None,
        seed: Optional[int] = None,
    ) -> ModelHandle:
        """Continue sampling the handle's model (any backend, warm state)."""
        backend = self._resolve(
            backend or handle.backend,
            num_tokens=handle.model.corpus.num_tokens, task="update")
        timer = timers.DeviceTimer(_OP_SECONDS, op="refine", backend=backend)
        timer.start()
        handle.model.state = self.sampler(backend).run(
            handle.cfg, handle.model.corpus, self._key(seed), num_sweeps,
            state=handle.model.state)
        timer.sync(handle.model.state.n_wt)
        handle.sweeps_run += num_sweeps
        handle.backend = backend
        return handle

    @trace.span("service.refine_many")
    def refine_many(
        self,
        handles: Sequence[ModelHandle],
        num_sweeps: int,
        *,
        backend: Optional[str] = None,
        seed: Optional[int] = None,
    ) -> list[ModelHandle]:
        """Warm-refit several served models at once.

        The `auto` route resolves multi-model refits to the `batched`
        backend; any resolved backend whose sampler carries the stacked
        `run_many` surface (`batched`, `alias`) continues
        stack-compatible handles' chains (bucketed by
        `serving.batch_engine`) in one launch instead of N sequential
        `refine` calls. Incompatible handles, a backend without the
        stacked surface, or a single handle fall back to per-handle
        `refine`.
        """
        handles = list(handles)
        if not handles:
            return handles
        # Dedup repeated handles (same served model named twice): each
        # model must run its sweeps exactly once, not burn a stacked slot
        # per mention and double-count sweeps_run.
        unique = list({h.handle_id: h for h in handles}.values())
        backend = self._resolve(
            backend,
            num_tokens=max(h.model.corpus.num_tokens for h in unique),
            task="update", num_models=len(unique))
        sampler = self.sampler(backend)
        if len(unique) == 1 or not hasattr(sampler, "run_many"):
            for i, h in enumerate(unique):
                # Per-handle seeds, like the fit_batch_prepared fallback:
                # a shared explicit seed would give every model the same
                # gumbel stream (correlated chains).
                self.refine(h, num_sweeps, backend=backend,
                            seed=seed if seed is None else seed + i)
            return handles
        import repro.serving.batch_engine as batch_engine

        timer = timers.DeviceTimer(
            _OP_SECONDS, op="refine_many", backend=backend)
        timer.start()
        states, _ = batch_engine.run_batched(
            sampler,
            [h.cfg for h in unique],
            [h.model.corpus for h in unique],
            self._keys(len(unique), seed),
            num_sweeps,
            states=[h.model.state for h in unique],
        )
        timer.sync(states[-1].n_wt)
        for h, st in zip(unique, states):
            h.model.state = st
            h.sweeps_run += num_sweeps
            h.backend = backend
        return handles

    # -- update (§3.2) -----------------------------------------------------

    def update(
        self,
        handle: ModelHandle,
        new_reviews: Sequence[Review],
        *,
        update_sweeps: Optional[int] = None,
        seed: Optional[int] = None,
        backend: Optional[str] = None,
    ) -> UpdateResponse:
        """Add reviews to a served model: incremental resampling of the new
        tokens, with the periodic full recompute of §3.2. `backend`
        overrides the handle's fit backend for this (and future) updates —
        the stored-state codec makes that a supported mid-run switch."""
        if not len(new_reviews):
            raise ValueError("update() needs at least one new review")
        prep, cfg = handle.prep, handle.cfg
        prep_new = rlda.prepare(
            list(new_reviews), base_vocab=prep.base_vocab,
            num_topics=cfg.num_topics, alpha=cfg.alpha, beta=cfg.beta,
            w_bits=cfg.w_bits)

        backend = self._resolve(
            backend or handle.backend,
            num_tokens=handle.model.corpus.num_tokens, task="update")
        handle.backend = backend
        timer = timers.DeviceTimer(_OP_SECONDS, op="update", backend=backend)
        timer.start()
        handle.model = update.add_documents(
            handle.model,
            np.asarray(prep_new.corpus.docs) + cfg.num_docs,
            np.asarray(prep_new.corpus.words),
            np.asarray(prep_new.corpus.weights),
            self._key(seed),
            update_sweeps=(update_sweeps if update_sweeps is not None
                           else self.update_sweeps),
            sampler=self.sampler(backend),
            # Explicit: token-free trailing reviews still count as docs.
            num_docs=cfg.num_docs + len(new_reviews),
        )
        timer.sync(handle.model.state.n_wt)
        # Corpus and per-review metadata must cover the appended documents.
        handle.prep = dataclasses.replace(
            prep,
            cfg=handle.model.cfg,
            corpus=handle.model.corpus,
            psi=np.concatenate([prep.psi, prep_new.psi]),
            tiers=np.concatenate([prep.tiers, prep_new.tiers]),
            tier_probs=np.concatenate([prep.tier_probs, prep_new.tier_probs]),
            ratings=np.concatenate([prep.ratings, prep_new.ratings]),
            helpful=np.concatenate([prep.helpful, prep_new.helpful]),
            unhelpful=np.concatenate([prep.unhelpful, prep_new.unhelpful]),
        )
        kind = ("full_recompute"
                if handle.model.updates_since_recompute == 0 else
                "incremental")
        return UpdateResponse(
            handle_id=handle.handle_id,
            num_new_reviews=len(new_reviews),
            kind=kind,
            perplexity=self.perplexity(handle),
        )

    # -- serving (§4.2, §3.4) ----------------------------------------------

    def view(
        self,
        handle: ModelHandle,
        topics: Optional[Sequence[int]] = None,
        top_n: int = 10,
        *,
        mass_coverage: float = 0.9,
        max_topics: Optional[int] = None,
    ) -> ViewResponse:
        """The streamed model view. `topics=None` selects the core set
        (§3.3); the response carries the JSON payload a device receives."""
        if topics is None:
            core, _ = coreset.select_core_set(
                handle.cfg, handle.state,
                mass_coverage=mass_coverage, max_topics=max_topics)
            topics = core
        timer = timers.DeviceTimer(
            _OP_SECONDS, op="view", backend=handle.backend)
        timer.start()
        topic_ids = [int(t) for t in topics]
        view = views_lib.build_view(
            handle.prep, handle.state, topic_ids, top_n=top_n)
        payload = view.to_json()
        timer.stop()  # host-side op: nothing async to wait out
        _VIEW_BYTES.observe(len(payload))
        return ViewResponse(
            handle_id=handle.handle_id,
            view=view,
            topic_ids=topic_ids,
            payload=payload,
            valid=view.validate(),
        )

    def top_reviews(
        self, handle: ModelHandle, topic_id: int, n: int = 5
    ) -> TopReviewsResponse:
        ids = views_lib.top_reviews_for_topic(
            handle.prep, handle.state, int(topic_id), n=n)
        return TopReviewsResponse(
            handle_id=handle.handle_id, topic_id=int(topic_id),
            review_ids=ids)

    @trace.span("service.perplexity")
    def perplexity(self, handle: ModelHandle) -> float:
        ppx = perplexity_lib.perplexity(
            handle.cfg, handle.state, handle.model.corpus)
        # The read waits for every program queued before it (a served
        # refit's sweeps and count rebuilds): device time, not service work.
        with trace.span("device.wait"):
            return float(ppx)

    def heldout_perplexity(
        self, handle: ModelHandle, reviews: Sequence[Review]
    ) -> float:
        """Perplexity of *unseen* reviews under the handle's current model.

        Held-out documents have no fitted θ̂_d, so tokens are scored under
        the posterior-predictive mixture with the corpus-wide topic weights:
        p(w) = Σ_t θ̄_t φ̂_tw, θ̄_t ∝ n_t + α. No state is touched — this is
        the drift guard of the streaming scheduler, called between updates.
        """
        if not len(reviews):
            raise ValueError("heldout_perplexity() needs at least one review")
        cfg = handle.cfg
        prep = rlda.prepare(
            list(reviews), base_vocab=handle.prep.base_vocab,
            num_topics=cfg.num_topics, alpha=cfg.alpha, beta=cfg.beta,
            w_bits=cfg.w_bits)
        sc = codec.codec_for(cfg)
        n_wt = sc.decode_array_np(handle.state.n_wt)  # (V, K)
        n_t = sc.decode_array_np(handle.state.n_t)  # (K,)
        phi = (n_wt + cfg.beta) / (n_t[None, :] + cfg.beta_bar)
        theta_bar = (n_t + cfg.alpha) / (n_t.sum() + cfg.alpha * cfg.num_topics)
        words = np.asarray(prep.corpus.words)
        wts = np.asarray(prep.corpus.weights, np.float64)
        p = phi[words] @ theta_bar  # (N,)
        ll = float(np.sum(wts * np.log(np.maximum(p, 1e-30))))
        return float(np.exp(-ll / max(wts.sum(), 1e-9)))

    # -- offload tier (§2.5.5 server-side checks) ---------------------------

    def validate_state(
        self, handle: ModelHandle, state: LDAState, *, count_tol: float = 2.0
    ) -> tuple[bool, str]:
        """Structural validation of an externally-computed state against the
        handle's corpus — the Chital validation stage for *state-carrying*
        submissions.

        Checks: array shapes, z assignments in `[0, K)`, finite counts, and
        count consistency with a scatter-rebuild from `(corpus, z)` — the
        stored state of every legitimate sampler IS `rebuild_state(cfg,
        corpus, z)`, so counts that disagree with their own assignments
        (beyond `count_tol` stored units of rounding slack) mean the
        submission was corrupted or fabricated.
        """
        cfg, corpus = handle.cfg, handle.model.corpus
        z = np.asarray(state.z)
        if z.shape != (corpus.num_tokens,):
            return False, (f"z has shape {z.shape}; corpus needs "
                           f"{(corpus.num_tokens,)}")
        if not np.issubdtype(z.dtype, np.integer):
            return False, f"z must be integer topic ids, got {z.dtype}"
        if z.size and (z.min() < 0 or z.max() >= cfg.num_topics):
            return False, (f"z assignments outside [0, {cfg.num_topics})")
        expect = {
            "n_dt": (cfg.num_docs, cfg.num_topics),
            "n_wt": (cfg.vocab_size, cfg.num_topics),
            "n_t": (cfg.num_topics,),
        }
        for name, shape in expect.items():
            arr = np.asarray(getattr(state, name))
            if arr.shape != shape:
                return False, (f"{name} has shape {arr.shape}; corpus needs "
                               f"{shape}")
            if not np.all(np.isfinite(arr)):
                return False, f"{name} contains non-finite entries"
        rebuilt = codec.rebuild_state(cfg, corpus, jnp.asarray(z))
        for name in expect:
            got = np.asarray(getattr(state, name), np.float64)
            want = np.asarray(getattr(rebuilt, name), np.float64)
            dev = float(np.max(np.abs(got - want))) if got.size else 0.0
            if dev > count_tol:
                return False, (f"{name} inconsistent with its own "
                               f"assignments (max deviation {dev:.1f} "
                               f"stored units)")
        return True, "ok"

    def spot_check(
        self,
        handle: ModelHandle,
        state: LDAState,
        *,
        claimed_perplexity: Optional[float] = None,
        num_sweeps: int = 0,
        claim_tol: float = 0.01,
        backend: Optional[str] = None,
        seed: Optional[int] = None,
    ) -> SpotCheckResponse:
        """Server-side check of a device-computed state, without touching
        the served handle.

        Always: structural validation plus the server's own perplexity
        recomputation on the submitted state (compared against
        `claimed_perplexity` when given — a fabricated claim fails here
        deterministically). With `num_sweeps > 0`: additionally runs that
        many re-Gibbs sweeps on a throwaway copy and reports the
        post-check perplexity — the real `reverify` behind Eq. (6), an
        unconverged submission reveals itself by a large drop.
        """
        ok, reason = self.validate_state(handle, state)
        if not ok:
            return SpotCheckResponse(valid=False, reason=reason)
        cfg, corpus = handle.cfg, handle.model.corpus
        state_ppx = float(perplexity_lib.perplexity(cfg, state, corpus))
        deviation = None
        if claimed_perplexity is not None:
            claimed = float(claimed_perplexity)
            deviation = abs(state_ppx - claimed) / max(abs(claimed), 1e-9)
            if deviation > claim_tol:
                return SpotCheckResponse(
                    valid=False,
                    reason=(f"claimed perplexity {claimed:.3f} deviates "
                            f"{deviation:.1%} from recomputed "
                            f"{state_ppx:.3f}"),
                    state_perplexity=state_ppx, deviation=deviation)
        post_ppx = None
        if num_sweeps > 0:
            backend = self._resolve(
                backend, num_tokens=corpus.num_tokens, task="update")
            post = self.sampler(backend).run(
                cfg, corpus, self._key(seed), num_sweeps, state=state)
            post_ppx = float(perplexity_lib.perplexity(cfg, post, corpus))
        return SpotCheckResponse(
            valid=True, reason="ok", state_perplexity=state_ppx,
            post_perplexity=post_ppx, deviation=deviation)

    def adopt_state(
        self, handle: ModelHandle, state: LDAState, *, sweeps_run: int = 0
    ) -> ModelHandle:
        """Swap a device-computed state into an *existing* served handle —
        the offload tier's adoption step (unlike `adopt`, which wraps a
        state into a new handle). Validation always runs here: adoption is
        the trust boundary, independent of the probabilistic Eq. (6) gate.
        """
        ok, reason = self.validate_state(handle, state)
        if not ok:
            raise ValueError(f"refusing to adopt state: {reason}")
        handle.model.state = LDAState(
            z=jnp.asarray(np.asarray(state.z)),
            n_dt=jnp.asarray(np.asarray(state.n_dt)),
            n_wt=jnp.asarray(np.asarray(state.n_wt)),
            n_t=jnp.asarray(np.asarray(state.n_t)),
        )
        handle.sweeps_run += int(sweeps_run)
        return handle

    def release(self, handle) -> None:
        """Drop a served handle (by handle or id); frees model state."""
        hid = handle.handle_id if isinstance(handle, ModelHandle) else int(handle)
        self.handles.pop(hid, None)
