"""Batched multi-model fit engine: parity, bucketing, protocol, coalescing.

The PR-4 acceptance gates:
  * a batched `sweep_batch` is bit-exact M independent single-model sweeps
    (same keys -> same chains) on the jnp oracle path;
  * `fit_batch` over M toy corpora matches per-model sequential fits —
    perplexity within tolerance, exact per-model count invariants;
  * the `auto` selector routes multi-model work to `batched`;
  * `fit_batch`/`refine_batch` protocol verbs round-trip with per-model
    results in request order;
  * `stream.IncrementalScheduler` coalesces same-window refits into one
    `refine_batch` launch per shard.
"""

import jax
import numpy as np
import pytest

from repro.api import VedaliaClient, select_backend
from repro.api.backends import backend_capabilities, get_backend
from repro.api.protocol import RemoteError
from repro.api.service import FitRequest, VedaliaService
from repro.core import batch as batch_lib
from repro.core import codec, gibbs, rlda
from repro.core.types import LDAConfig
from repro.data import reviews as reviews_data
from repro.serving import batch_engine
from repro.serving.topic_engine import TopicEngine
from repro.stream import IncrementalScheduler, ReviewEvent, StreamRouter


def _review_sets(m, n=14, vocab=200, topics=4):
    sets = []
    for s in range(m):
        spec = reviews_data.SyntheticSpec(
            num_reviews=n, vocab_size=vocab, num_topics=topics,
            mean_tokens=25, num_users=30, seed=50 + s)
        sets.append(reviews_data.generate(spec).reviews)
    return sets


def _preps(m, **kw):
    return [
        rlda.prepare(rs, base_vocab=200, num_topics=6, **kw)
        for rs in _review_sets(m)
    ]


def _assert_count_invariants(cfg, corpus, state):
    """Exact per-model invariants, to fixed-point codec resolution."""
    n_dt, n_wt, n_t = codec.decode_counts_np(cfg, state)
    w = np.asarray(corpus.weights, np.float64)
    docs = np.asarray(corpus.docs)
    # one ulp of the stored representation per contributing array entry
    eps = (0.5 / 2 ** (cfg.w_bits + 1)) if cfg.w_bits is not None else 1e-4
    per_doc = np.zeros(cfg.num_docs)
    np.add.at(per_doc, docs, w)
    np.testing.assert_allclose(
        n_dt.sum(axis=1), per_doc, atol=eps * cfg.num_topics + 1e-3)
    np.testing.assert_allclose(
        n_wt.sum(axis=0), n_t, atol=eps * (cfg.vocab_size + 1) + 1e-3)
    assert abs(n_wt.sum() - w.sum()) <= eps * corpus.num_tokens + 1e-2


# -- registry / selector -----------------------------------------------------


def test_batched_backend_registered_with_capabilities():
    caps = backend_capabilities("batched")
    assert caps.warm_start and caps.weighted
    assert caps.device_kind == "tpu"


def test_auto_selector_routes_multi_model_to_batched():
    assert select_backend(num_models=4) == "batched"
    assert select_backend(num_models=16, task="update") == "batched"
    assert select_backend(num_models=1) == "jnp"
    # device_kind still wins: a phone stack is not a batched TPU fit
    assert select_backend(num_models=4, device_kind="phone") == "sparse"
    # degraded registries fall back
    assert select_backend(num_models=4, available=["jnp"]) == "jnp"


def test_unknown_batched_path_rejected():
    with pytest.raises(ValueError, match="path"):
        get_backend("batched", path="cuda")


# -- bucketing ---------------------------------------------------------------


def test_length_and_doc_buckets_are_power_of_two_ladders():
    assert batch_engine.length_bucket(1) == 256
    assert batch_engine.length_bucket(256) == 256
    assert batch_engine.length_bucket(257) == 512
    assert batch_engine.length_bucket(900) == 1024
    assert batch_engine.doc_bucket(17) == 32


def test_plan_buckets_groups_compatible_models():
    preps = _preps(3, w_bits=8)
    other = rlda.prepare(_review_sets(1)[0], base_vocab=200, num_topics=9,
                         w_bits=8)  # different K: never stacks
    items = [(p.cfg, p.corpus) for p in preps] + [(other.cfg, other.corpus)]
    buckets = batch_engine.plan_buckets(items)
    by_len = {tuple(sorted(b)) for b in buckets}
    assert all(3 not in b or len(b) == 1 for b in by_len)  # K=9 isolated
    # max_models splits a bucket
    split = batch_engine.plan_buckets(items[:3], max_models=2)
    assert sorted(len(b) for b in split) in ([1, 2], [1, 1, 1])
    assert sorted(i for b in split for i in b) == [0, 1, 2]


def test_batch_cfg_rejects_incompatible_models():
    a = LDAConfig(num_topics=4, vocab_size=100, num_docs=8)
    b = LDAConfig(num_topics=8, vocab_size=100, num_docs=8)
    with pytest.raises(ValueError, match="incompatible"):
        batch_lib.batch_cfg([a, b], 8)
    with pytest.raises(ValueError, match="capacity"):
        batch_lib.batch_cfg([a], 4)


# -- sweep parity ------------------------------------------------------------


@pytest.mark.parametrize("w_bits", [None, 8])
def test_sweep_batch_is_exactly_m_single_model_sweeps(w_bits):
    preps = _preps(3, w_bits=w_bits)
    cfgs = [p.cfg for p in preps]
    n_pad = max(p.corpus.num_tokens for p in preps)
    bcfg = batch_lib.batch_cfg(
        cfgs, batch_engine.doc_bucket(max(c.num_docs for c in cfgs)))
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    stacked, _, _ = batch_lib.stack_bucket(
        [p.corpus for p in preps], list(keys), n_pad, bcfg.num_docs)
    states = batch_lib.init_many(bcfg, stacked, keys)

    out = batch_lib.sweep_batch(bcfg, states, stacked, keys)
    for i, p in enumerate(preps):
        n = p.corpus.num_tokens
        padded = batch_lib.pad_corpus(p.corpus, n_pad)
        st_i = codec.rebuild_state(bcfg, padded, states.z[i])
        ref = gibbs.sweep(bcfg, st_i, padded, keys[i])
        np.testing.assert_array_equal(
            np.asarray(out.z[i, :n]), np.asarray(ref.z[:n]))


def _sized_review_sets(sizes):
    """One review set per `(num_reviews, seed)`, so token and document
    counts differ from set to set."""
    return [
        reviews_data.generate(reviews_data.SyntheticSpec(
            num_reviews=n, vocab_size=200, num_topics=4, mean_tokens=25,
            num_users=30, seed=seed)).reviews
        for n, seed in sizes
    ]


class _RecordingSampler:
    """Keeps every stacked `run_many` result, and forbids any count rebuild
    between launches (that is, while a bucket unstacks)."""

    def __init__(self, inner, monkeypatch):
        self.inner, self.outs, self._mp = inner, [], monkeypatch
        self._real = (codec.rebuild_state, codec.StateCodec.rebuild_state,
                      codec.build_counts)

    def _patch(self, fns):
        self._mp.setattr(codec, "rebuild_state", fns[0])
        self._mp.setattr(codec.StateCodec, "rebuild_state", fns[1])
        self._mp.setattr(codec, "build_counts", fns[2])

    def run_many(self, *args, **kw):
        self._patch(self._real)
        out = self.inner.run_many(*args, **kw)
        self.outs.append(out)

        def boom(*_a, **_k):
            raise AssertionError("unstacking rebuilt counts")

        self._patch((boom, boom, boom))
        return out


@pytest.mark.parametrize("path", ["jnp", "pallas"])
@pytest.mark.parametrize("w_bits", [None, 8])
def test_unstack_states_trims_and_rebuilds_per_model(w_bits, path,
                                                     monkeypatch):
    """Unstacking slices the launch's own rebuilt counts: z is the stacked
    z trimmed, the counts are the model's rebuild (one stored unit for
    fixed point, 1e-5 relative for float32), and no rebuild runs."""
    preps = [rlda.prepare(rs, base_vocab=200, num_topics=6, w_bits=w_bits)
             for rs in _sized_review_sets([(4, 50), (10, 51), (14, 52),
                                           (12, 53)])]
    cfgs = [p.cfg for p in preps]
    corpora = [p.corpus for p in preps]
    keys = [jax.random.PRNGKey(i) for i in range(len(preps))]
    buckets = batch_engine.plan_buckets(list(zip(cfgs, corpora)))
    assert len(buckets) >= 2
    assert len({c.num_tokens for c in corpora}) == len(corpora)
    assert len({c.num_docs for c in cfgs}) == len(cfgs)

    sampler = _RecordingSampler(get_backend("batched", path=path),
                                monkeypatch)
    cold, _ = batch_engine.run_batched(sampler, cfgs, corpora, keys, 1)
    states, stats = batch_engine.run_batched(
        sampler, cfgs, corpora, keys, 2, states=cold)
    monkeypatch.undo()
    assert stats.num_models == len(preps)
    assert stats.num_launches == len(buckets)

    warm_outs = sampler.outs[len(buckets):]
    for idxs, out in zip(buckets, warm_outs):
        for i in idxs:
            cfg, corpus, st = cfgs[i], corpora[i], states[i]
            n = corpus.num_tokens
            assert st.z.shape == (n,)
            assert st.n_dt.shape == (cfg.num_docs, cfg.num_topics)
            np.testing.assert_array_equal(
                np.asarray(st.z), np.asarray(out.z[idxs.index(i), :n]))
            ref = codec.rebuild_state(cfg, corpus, st.z)
            for got, want in ((st.n_dt, ref.n_dt), (st.n_wt, ref.n_wt),
                              (st.n_t, ref.n_t)):
                assert got.dtype == want.dtype and got.shape == want.shape
                if w_bits is None:
                    np.testing.assert_allclose(
                        np.asarray(got), np.asarray(want), rtol=1e-5)
                else:
                    assert np.abs(np.asarray(got, np.int64)
                                  - np.asarray(want, np.int64)).max() <= 1
            _assert_count_invariants(cfg, corpus, st)


def test_refine_many_new_subset_of_known_shapes_compiles_nothing():
    """Compile keys never name a bucket's composition: once refine_many has
    run over handles of three shapes, another subset in another order of
    the same shapes (same bucket shape, same M) compiles no program."""
    sets = _sized_review_sets([(4, 50), (6, 51), (10, 52)])
    svc = VedaliaService(backend="auto", num_sweeps=2)
    handles = svc.fit_batch(sets + sets, num_topics=6, base_vocab=200)
    assert len({h.model.corpus.num_tokens for h in handles}) == 3
    assert len(batch_engine.plan_buckets(
        [(h.cfg, h.model.corpus) for h in handles])) == 1
    svc.refine_many(handles[:3], 2)

    compiles = []

    def on(event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        out = svc.refine_many([handles[5], handles[3], handles[1]], 2)
        jax.block_until_ready([h.state for h in out])
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert compiles == []
    assert [h.sweeps_run for h in handles] == [4, 6, 4, 4, 2, 4]


# -- service-level parity (the PR acceptance test) ---------------------------


def test_fit_batch_matches_sequential_fits():
    """fit_many over M=4 toy corpora vs per-model sequential fits: same
    seeds -> perplexity within tolerance, exact count invariants."""
    m, sweeps = 4, 12
    sets = _review_sets(m)

    seq_svc = VedaliaService(backend="jnp", num_sweeps=sweeps)
    seq_ppx = []
    for i, rs in enumerate(sets):
        h = seq_svc.fit(rs, num_topics=6, base_vocab=200, seed=7 + i)
        seq_ppx.append(seq_svc.perplexity(h))

    bat_svc = VedaliaService(backend="auto", num_sweeps=sweeps)
    handles = bat_svc.fit_batch(sets, num_topics=6, base_vocab=200, seed=7)
    assert [h.backend for h in handles] == ["batched"] * m
    assert sorted(bat_svc.handles) == [h.handle_id for h in handles]

    for h, ps in zip(handles, seq_ppx):
        pb = bat_svc.perplexity(h)
        # Different chains (independent keys): converged-quality parity,
        # same tolerance as the kernel-vs-oracle statistics test.
        assert abs(np.log(pb) - np.log(ps)) < 0.3, (pb, ps)
        _assert_count_invariants(h.cfg, h.model.corpus, h.state)


def test_fit_batch_single_model_falls_back_to_sequential():
    svc = VedaliaService(backend="auto", num_sweeps=4)
    (h,) = svc.fit_batch(_review_sets(1), num_topics=4, base_vocab=200)
    assert h.backend == "jnp"  # num_models=1 never routes to batched


def test_fit_batch_rejects_empty_sets():
    svc = VedaliaService(num_sweeps=2)
    with pytest.raises(ValueError, match="at least one"):
        svc.fit_batch([])
    with pytest.raises(ValueError, match="set 1 is empty"):
        svc.fit_batch([_review_sets(1)[0], []])


def test_refine_many_dedups_repeated_handles():
    svc = VedaliaService(backend="auto", num_sweeps=4)
    handles = svc.fit_batch(_review_sets(2), num_topics=4, base_vocab=200)
    h = handles[0]
    before = h.sweeps_run
    out = svc.refine_many([h, h, handles[1]], 3)
    assert len(out) == 3  # input order/length preserved
    assert h.sweeps_run == before + 3  # one model, one refit


def test_refine_many_sequential_fallback_derives_per_handle_seeds():
    svc = VedaliaService(backend="jnp", num_sweeps=4)
    handles = svc.fit_batch(_review_sets(2), num_topics=4, base_vocab=200,
                            backend="jnp")
    svc.refine_many(handles, 3, backend="jnp", seed=11)
    # identical seeds would give both models the same gumbel stream; with
    # per-handle derivation the refined states must differ
    assert not np.array_equal(np.asarray(handles[0].state.z[:50]),
                              np.asarray(handles[1].state.z[:50]))
    assert all(h.backend == "jnp" for h in handles)


def test_perf_gate_update_refuses_partial_summary(tmp_path):
    import importlib.util
    import json
    import os

    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(
            os.path.dirname(__file__), "..", "benchmarks", "perf_gate.py"))
    perf_gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(perf_gate)

    summary = tmp_path / "summary.json"
    baseline = tmp_path / "baseline.json"
    summary.write_text(json.dumps({
        "benches": {"sampler": {"samplers": {
            "parallel": {"tokens_per_s": 100},
            "kernel": {"tokens_per_s": 100}}}}}))
    # partial (no `batch` bench): --update must refuse, not drop the gate
    assert perf_gate.main(["--summary", str(summary),
                           "--baseline", str(baseline), "--update"]) == 1
    assert not baseline.exists()
    # a full summary (every gated bench) refreshes, and the gate then
    # passes and regresses
    summary.write_text(json.dumps({
        "benches": {
            "sampler": {"samplers": {
                "parallel": {"tokens_per_s": 100},
                "kernel": {"tokens_per_s": 100}}},
            "batch": {"models_per_s": {"batched": 10}, "speedup": 5},
            "alias": {"tokens_per_s": {"alias": 1000}},
            "offload": {"offloaded_sweep_fraction": 0.7,
                        "no_phony_adopted": 1.0},
            "distributed": {"weak_scaling_efficiency": 1.0,
                            "sync_bytes_saving": 4.0},
            "obs": {"overhead_ok": 1.0},
            "delta_view": {"quantized_saving": 2.4},
        }}))
    assert perf_gate.main(["--summary", str(summary),
                           "--baseline", str(baseline), "--update"]) == 0
    assert perf_gate.main(
        ["--summary", str(summary), "--baseline", str(baseline),
         "--require",
         "sampler,batch,alias,offload,distributed,obs,delta_view"]) == 0
    summary.write_text(json.dumps({
        "benches": {
            "sampler": {"samplers": {
                "parallel": {"tokens_per_s": 50},  # -50%: regression
                "kernel": {"tokens_per_s": 100}}},
            "batch": {"models_per_s": {"batched": 10}, "speedup": 5},
            "alias": {"tokens_per_s": {"alias": 1000}},
            "offload": {"offloaded_sweep_fraction": 0.7,
                        "no_phony_adopted": 1.0},
            "distributed": {"weak_scaling_efficiency": 1.0,
                            "sync_bytes_saving": 4.0},
            "obs": {"overhead_ok": 1.0},
            "delta_view": {"quantized_saving": 2.4},
        }}))
    assert perf_gate.main(["--summary", str(summary),
                           "--baseline", str(baseline)]) == 1


def test_fit_batch_and_refine_many_stack_alias_backend(monkeypatch):
    """Regression: the service used to serialize any explicit non-batched
    backend; a backend with the stacked `run_many` surface (alias) must
    launch through the batch engine from fit_batch AND refine_many —
    observed directly by counting `run_batched` invocations, since every
    softer assertion also holds on the sequential fallback path."""
    launches = []
    real_run_batched = batch_engine.run_batched

    def counting_run_batched(sampler, *args, **kw):
        out, stats = real_run_batched(sampler, *args, **kw)
        launches.append((type(sampler).__name__, stats.num_launches))
        return out, stats

    monkeypatch.setattr(batch_engine, "run_batched", counting_run_batched)
    svc = VedaliaService(backend="auto", num_sweeps=4)
    handles = svc.fit_batch(_review_sets(3), num_topics=6, base_vocab=200,
                            backend="alias", seed=3)
    assert launches == [("AliasSampler", 1)]  # one stacked launch, not 3
    assert all(h.backend == "alias" for h in handles)
    # distinct per-handle chains (per-model key discipline held)
    assert not np.array_equal(np.asarray(handles[0].state.z[:50]),
                              np.asarray(handles[1].state.z[:50]))
    for h in handles:
        _assert_count_invariants(h.cfg, h.model.corpus, h.state)
    before = [h.sweeps_run for h in handles]
    svc.refine_many(handles, 2, backend="alias")
    assert launches == [("AliasSampler", 1)] * 2  # warm refit batched too
    assert [h.sweeps_run for h in handles] == [b + 2 for b in before]
    assert all(h.backend == "alias" for h in handles)
    assert all(svc.view(h).valid for h in handles)


def test_refine_many_batches_compatible_handles():
    svc = VedaliaService(backend="auto", num_sweeps=5)
    handles = svc.fit_batch(_review_sets(3), num_topics=6, base_vocab=200)
    before = [h.sweeps_run for h in handles]
    svc.refine_many(handles, 3)
    assert [h.sweeps_run for h in handles] == [b + 3 for b in before]
    assert all(h.backend == "batched" for h in handles)
    for h in handles:
        _assert_count_invariants(h.cfg, h.model.corpus, h.state)


# -- protocol ----------------------------------------------------------------


def test_protocol_fit_batch_and_refine_batch_roundtrip():
    client = VedaliaClient(backend="auto", num_sweeps=5)
    sets = _review_sets(3)
    fits = client.fit_batch(sets, num_topics=6, base_vocab=200)
    assert len(fits) == 3
    assert [f.backend for f in fits] == ["batched"] * 3
    assert [f.num_reviews for f in fits] == [len(rs) for rs in sets]
    refined = client.refine_batch([f.handle_id for f in fits], 2)
    assert [r.handle_id for r in refined] == [f.handle_id for f in fits]
    assert all(r.sweeps_run == f.sweeps_run + 2
               for r, f in zip(refined, fits))
    view = client.sync_view(fits[0].handle_id)
    assert view.valid and len(view.topics) >= 1


def test_protocol_refine_batch_unknown_handle_is_not_found():
    client = VedaliaClient(backend="jnp", num_sweeps=2)
    fit = client.fit(_review_sets(1)[0], num_topics=4, base_vocab=200)
    with pytest.raises(RemoteError) as e:
        client.refine_batch([fit.handle_id, 999], 1)
    assert e.value.code == "not_found"


# -- TopicEngine wave batching -----------------------------------------------


def test_topic_engine_fit_many_serves_batched_waves():
    eng = TopicEngine(backend="auto", num_sweeps=5, max_batch=4)
    sets = _review_sets(4)
    reqs = [FitRequest(uid=i, reviews=rs, num_topics=6, base_vocab=200)
            for i, rs in enumerate(sets)]
    results = eng.fit_many(reqs)
    assert [r.uid for r in results] == [0, 1, 2, 3]
    assert all(r.fit.backend == "batched" for r in results)
    assert all(np.isfinite(r.perplexity) for r in results)
    assert all(r.view.valid for r in results)
    # explicit per-model backend keeps the sequential path
    eng2 = TopicEngine(backend="jnp", num_sweeps=3, max_batch=4)
    res2 = eng2.fit_many([
        FitRequest(uid=9, reviews=sets[0], num_topics=4, base_vocab=200)])
    assert res2[0].fit.backend == "jnp"


# -- streaming refit coalescing ----------------------------------------------


def test_scheduler_coalesces_same_window_refits():
    client = VedaliaClient(backend="jnp", num_sweeps=4, update_sweeps=1)
    router = StreamRouter([0], capacity=256)
    sched = IncrementalScheduler(
        {0: client}, router, microbatch=3, min_fit_reviews=4,
        staleness_budget=100.0, refit_sweeps=2, refit_policy="always",
        heldout_every=1000,
        fit_kwargs=dict(num_topics=4, base_vocab=200, num_sweeps=3))

    sets = _review_sets(2, n=8)
    seq = 0
    # bootstrap both products
    for pid in (0, 1):
        for r in sets[pid][:4]:
            assert router.offer(ReviewEvent(seq=seq, t=0.1, product_id=pid,
                                            review=r))
            seq += 1
    sched.step(1.0)
    assert sched.stats.fits == 2 and sched.stats.refits == 0

    # one micro-batch per product inside the SAME scheduling window
    for pid in (0, 1):
        for r in sets[pid][4:7]:
            assert router.offer(ReviewEvent(seq=seq, t=1.1, product_id=pid,
                                            review=r))
            seq += 1
    sched.step(2.0)
    st = sched.stats
    assert st.updates == 2
    assert st.refits == 2  # both products refit (always policy)...
    assert st.refit_launches == 1  # ...in ONE coalesced refine_batch
    assert st.coalesced_refits == 1
    for status in sched.products.values():
        assert status.signatures  # re-anchored after the batched refit
        v = client.sync_view(status.handle_id)
        assert v.valid


def test_scheduler_falls_back_without_batched_backend():
    client = VedaliaClient(backend="jnp", num_sweeps=4, update_sweeps=1)
    router = StreamRouter([0], capacity=256)
    sched = IncrementalScheduler(
        {0: client}, router, microbatch=3, min_fit_reviews=4,
        staleness_budget=100.0, refit_sweeps=2, refit_policy="always",
        heldout_every=1000,
        fit_kwargs=dict(num_topics=4, base_vocab=200, num_sweeps=3))
    # a shard whose hello predates the batched backend
    sched._backends[0] = ["jnp", "alias", "sparse"]

    sets = _review_sets(2, n=8)
    seq = 0
    for pid in (0, 1):
        for r in sets[pid][:4]:
            assert router.offer(ReviewEvent(seq=seq, t=0.1, product_id=pid,
                                            review=r))
            seq += 1
    sched.step(1.0)
    for pid in (0, 1):
        for r in sets[pid][4:7]:
            assert router.offer(ReviewEvent(seq=seq, t=1.1, product_id=pid,
                                            review=r))
            seq += 1
    sched.step(2.0)
    st = sched.stats
    assert st.refits == 2 and st.refit_launches == 2
    assert st.coalesced_refits == 0
