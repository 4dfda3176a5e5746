"""Observability layer: registry semantics, span/wire propagation, the
`metrics` verb, and trace-id hygiene across snapshot restore + eviction.

Everything here runs with the module-level obs switch explicitly managed
by the autouse fixture — the layer is disabled-by-default, so every test
that expects recording opts in and every test leaves the process clean.
"""

from __future__ import annotations

import pytest

from repro import obs
from repro.api import VedaliaClient, VedaliaServer, protocol
from repro.data import reviews as reviews_data
from repro.obs import metrics, timers, trace
from repro.stream import snapshot as snapshot_lib


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    metrics.reset()
    trace.reset()
    yield
    obs.disable()
    metrics.reset()
    trace.reset()


def _reviews(n=20, vocab=120, seed=0):
    spec = reviews_data.SyntheticSpec(
        num_reviews=n, vocab_size=vocab, num_topics=4, mean_tokens=25,
        seed=seed)
    return reviews_data.generate(spec).reviews


def _fit_client(server=None, **server_kw):
    server = server or VedaliaServer(backend="jnp", num_sweeps=2,
                                     **server_kw)
    client = VedaliaClient(server=server)
    fit = client.fit(_reviews(), num_topics=4, base_vocab=120, w_bits=None)
    return server, client, fit


# -- registry ----------------------------------------------------------------


def test_disabled_recording_is_noop():
    c = metrics.counter("t_disabled_total", "x")
    h = metrics.histogram("t_disabled_seconds", "x")
    c.inc()
    h.observe(0.5)
    assert c.value() == 0.0
    assert h.count() == 0
    assert metrics.snapshot() == {}


def test_counter_labels_and_negative():
    obs.enable()
    c = metrics.counter("t_reqs_total", "x", labels=("verb",))
    c.inc(verb="fit")
    c.inc(2.0, verb="fit")
    c.inc(verb="view")
    assert c.value(verb="fit") == 3.0
    assert c.value(verb="view") == 1.0
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1.0, verb="fit")
    with pytest.raises(ValueError, match="takes labels"):
        c.inc(wrong="fit")
    with pytest.raises(ValueError, match="takes labels"):
        c.inc()  # missing the declared label entirely


def test_redeclaration_is_get_or_create_but_conflicts_raise():
    c1 = metrics.counter("t_shared_total", "x", labels=("a",))
    c2 = metrics.counter("t_shared_total", "different help", labels=("a",))
    assert c1 is c2
    with pytest.raises(ValueError, match="conflicting"):
        metrics.gauge("t_shared_total", "x", labels=("a",))  # type flip
    with pytest.raises(ValueError, match="conflicting"):
        metrics.counter("t_shared_total", "x", labels=("b",))  # label flip
    h1 = metrics.histogram("t_shared_seconds", "x", buckets=(1.0, 2.0))
    assert metrics.histogram("t_shared_seconds", "x") is h1  # None buckets ok
    with pytest.raises(ValueError, match="conflicting"):
        metrics.histogram("t_shared_seconds", "x", buckets=(1.0, 4.0))


def test_histogram_bucket_edges_are_inclusive():
    obs.enable()
    h = metrics.histogram("t_edges", "x", buckets=(1.0, 2.0))
    for v in (1.0, 1.5, 2.0, 5.0):  # boundary values land in their bucket
        h.observe(v)
    [series] = metrics.snapshot()["t_edges"]["series"]
    assert series["counts"] == [1, 2, 1]  # le=1 / le=2 / +Inf
    assert series["count"] == 4
    assert series["sum"] == pytest.approx(9.5)
    text = metrics.render_prometheus()
    assert 't_edges_bucket{le="1"} 1' in text
    assert 't_edges_bucket{le="2"} 3' in text  # cumulative
    assert 't_edges_bucket{le="+Inf"} 4' in text
    assert "t_edges_count 4" in text


def test_histogram_bad_buckets_raise():
    with pytest.raises(ValueError, match="at least one bucket"):
        metrics.histogram("t_empty", "x", buckets=())
    with pytest.raises(ValueError, match="duplicate"):
        metrics.histogram("t_dup", "x", buckets=(1.0, 1.0, 2.0))


def test_prometheus_exposition_shape():
    obs.enable()
    metrics.counter("t_prom_total", "help text", labels=("q",)).inc(q='a"b')
    text = metrics.render_prometheus()
    assert "# HELP t_prom_total help text" in text
    assert "# TYPE t_prom_total counter" in text
    assert 't_prom_total{q="a\\"b"} 1' in text  # label escaping
    metrics.counter("t_prom_empty_total", "never recorded")
    assert "t_prom_empty_total" not in metrics.render_prometheus()


# -- spans & wire propagation ------------------------------------------------


def test_disabled_span_records_nothing():
    with trace.span("outer") as sp:
        sp.set(k=1)  # the null span accepts the live-span surface
        assert trace.wire_context() is None
    assert trace.spans() == []


def test_nested_spans_share_one_trace():
    obs.enable()
    with trace.span("outer") as outer:
        with trace.span("inner", k=3) as inner:
            pass
    outer_sp, = [s for s in trace.spans() if s.name == "outer"]
    inner_sp, = [s for s in trace.spans() if s.name == "inner"]
    assert inner_sp.trace_id == outer_sp.trace_id == outer.trace_id
    assert inner_sp.parent_id == outer_sp.span_id
    assert outer_sp.parent_id is None
    assert inner_sp.attrs == {"k": 3}
    assert inner_sp is inner  # the yielded span is the recorded one


def test_remote_parent_adopts_and_tolerates_garbage():
    obs.enable()
    with trace.remote_parent({"trace_id": "t" * 16,
                              "parent_span_id": "p1"}):
        with trace.span("server.x"):
            pass
    sp, = trace.spans()
    assert sp.trace_id == "t" * 16
    assert sp.parent_id == "p1"
    # Malformed wire fields must degrade to a fresh trace, never an error.
    for garbage in (None, "notadict", {}, {"parent_span_id": "p"}):
        with trace.remote_parent(garbage):
            with trace.span("server.y"):
                pass
    fresh = [s for s in trace.spans() if s.name == "server.y"]
    assert len(fresh) == 4
    assert all(s.parent_id is None for s in fresh)


def test_span_ids_never_duplicate():
    obs.enable()
    for _ in range(50):
        with trace.span("a"):
            with trace.span("b"):
                pass
    ids = [s.span_id for s in trace.spans()]
    assert len(ids) == len(set(ids)) == 100


def test_chrome_export_events():
    obs.enable()
    with trace.span("outer", shard=2):
        with trace.span("inner"):
            pass
    events = trace.chrome_trace_events()
    assert [e["name"] for e in events] == ["inner", "outer"]
    assert all(e["ph"] == "X" for e in events)
    assert {e["tid"] for e in events} == {1}  # one trace -> one lane
    outer = next(e for e in events if e["name"] == "outer")
    assert outer["args"]["shard"] == 2
    assert outer["dur"] >= 0


def _host_event_names(log_dir) -> set:
    import glob
    import os

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_enabled_span_is_a_profiler_host_event(tmp_path):
    """While obs is enabled a span is also a profiler annotation of the
    same name, on the trace's host clock; while disabled it is not."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        obs.enable()
        with trace.span("obs_test.on", k=1):
            jnp.ones(8).block_until_ready()
        obs.disable()
        with trace.span("obs_test.off"):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path))
    assert "obs_test.on" in names  # attributes stay off the event name
    assert "obs_test.off" not in names
    on, = trace.spans()  # the span record is unchanged by the annotation
    assert on.name == "obs_test.on" and on.attrs == {"k": 1}


def _span_tree() -> dict:
    """name -> set of parent names, over the buffered spans."""
    by_id = {s.span_id: s for s in trace.spans()}
    out: dict = {}
    for s in trace.spans():
        parent = by_id.get(s.parent_id)
        out.setdefault(s.name, set()).add(parent.name if parent else None)
    return out


def test_request_path_spans_name_each_layer():
    """A coalesced refit over the wire opens one span per layer: wire,
    service, batch engine, sampler, the device waits and the per-model
    perplexities of the response, each of whose host reads is a wait."""
    server = VedaliaServer(backend="batched", num_sweeps=1)
    client = VedaliaClient(server=server)
    fits = client.fit_batch([_reviews(seed=i) for i in range(3)],
                            num_topics=4, base_vocab=120, w_bits=None)
    obs.enable()
    client.refine_batch([f.handle_id for f in fits], 1)
    tree = _span_tree()
    assert tree["server.refine_batch"] == {"client.refine_batch"}
    assert tree["service.refine_many"] == {"server.refine_batch"}
    for name in ("batch.plan", "batch.stack", "batch.launch",
                 "batch.unstack"):
        assert tree[name] == {"service.refine_many"}
    assert tree["device.wait"] == {"service.refine_many",
                                   "service.perplexity"}
    assert tree["sampler.run_many"] == {"batch.launch"}
    assert tree["service.perplexity"] == {"server.refine_batch"}
    assert sum(s.name == "service.perplexity" for s in trace.spans()) == 3
    by_id = {s.span_id: s for s in trace.spans()}
    assert sum(s.name == "device.wait" and by_id[s.parent_id].name
               == "service.perplexity" for s in trace.spans()) == 3

    trace.reset()
    client.refine(fits[0].handle_id, 1, backend="jnp")
    tree = _span_tree()
    assert tree["service.refine"] == {"server.refine"}
    assert tree["device.wait"] == {"service.refine", "service.perplexity"}


def test_device_timer_wait_is_a_span():
    import jax.numpy as jnp

    obs.enable()
    timers.DeviceTimer().start().sync(None)  # nothing to wait for
    assert trace.spans() == []
    timers.DeviceTimer().start().sync(jnp.ones(4))
    assert [s.name for s in trace.spans()] == ["device.wait"]


# -- timers ------------------------------------------------------------------


def test_device_timer_disabled_and_enabled():
    h = metrics.histogram("t_timer_seconds", "x", labels=("op",))
    t = timers.DeviceTimer(h, op="fit").start()
    assert t.sync(None) is None  # disabled: no block, no observation
    assert h.count(op="fit") == 0
    obs.enable()
    t = timers.DeviceTimer(h, op="fit").start()
    elapsed = t.sync(None)
    assert elapsed is not None and elapsed >= 0.0
    assert h.count(op="fit") == 1
    # enabled but never started (e.g. enabled mid-flight): still a no-op
    t2 = timers.DeviceTimer(h, op="fit")
    assert t2.sync(None) is None
    assert h.count(op="fit") == 1


# -- the metrics wire verb ---------------------------------------------------


def test_metrics_verb_roundtrip_dict_and_prometheus():
    obs.enable()
    _, client, fit = _fit_client()
    got = client.metrics()
    assert got.enabled is True
    assert got.exposition is None
    reqs = got.metrics["vedalia_server_requests_total"]
    fit_series = [s for s in reqs["series"]
                  if s["labels"] == {"verb": "fit", "status": "ok"}]
    assert fit_series and fit_series[0]["value"] >= 1.0
    assert "vedalia_service_op_seconds" in got.metrics

    prom = client.metrics(format="prometheus")
    assert "# TYPE vedalia_server_requests_total counter" in prom.exposition
    assert prom.metrics  # exposition rides alongside the dict, not instead


def test_metrics_verb_reports_disabled_switch():
    server = VedaliaServer(backend="jnp")
    client = VedaliaClient(server=server)
    got = client.metrics()
    assert got.enabled is False
    assert got.metrics == {}  # nothing recorded while disabled


def test_metrics_verb_bad_format():
    client = VedaliaClient(server=VedaliaServer(backend="jnp"))
    with pytest.raises(protocol.RemoteError) as ei:
        client.metrics(format="xml")
    assert ei.value.code == "invalid_argument"


def test_metrics_verb_against_old_server():
    """A pre-verb server answers `bad_request` (unknown kind); the client
    surfaces the usual typed RemoteError, no special casing."""
    server = VedaliaServer(backend="jnp")

    def old_transport(raw: str) -> str:
        kind, _ = protocol.parse_request(raw)
        if kind == "metrics":
            return protocol.make_error(
                kind, "bad_request", f"unknown request kind {kind!r}")
        return server.handle_raw(raw)

    client = VedaliaClient(transport=old_transport)
    assert client.hello().protocol_version == protocol.PROTOCOL_VERSION
    with pytest.raises(protocol.RemoteError) as ei:
        client.metrics()
    assert ei.value.code == "bad_request"
    assert "unknown request kind" in str(ei.value)


# -- trace ids across the wire, restore, and eviction ------------------------


def test_wire_propagation_client_to_server():
    obs.enable()
    _, client, fit = _fit_client()
    client_fit, = [s for s in trace.spans() if s.name == "client.fit"]
    server_fit, = [s for s in trace.spans() if s.name == "server.fit"]
    assert server_fit.trace_id == client_fit.trace_id
    assert server_fit.parent_id == client_fit.span_id  # wire, not ambient


def test_trace_ids_across_snapshot_restore_and_rebind():
    obs.enable()
    server, client, fit = _fit_client()
    client.view(fit.handle_id)  # establishes session + cursor

    restored = snapshot_lib.restore_server(
        snapshot_lib.snapshot_server(server))
    client.rebind(server=restored)
    # Stale session + stale cursor against the restored shard: recovery
    # reopens a session and the unknown cursor degrades to a full resync.
    result = client.view(fit.handle_id,
                         since=client.cursors[fit.handle_id])
    assert result.resync

    spans = trace.spans()
    # Ids survive the restore cleanly re-issued: the process mints every
    # span id from one nonce+counter, so nothing collides pre/post restore.
    ids = [s.span_id for s in spans]
    assert len(ids) == len(set(ids))
    # The post-rebind view is one trace end to end: the recovery chain
    # (view -> not_found -> open_session -> retried view) shares the ids
    # of the client spans that issued it.
    client_views = [s for s in spans if s.name == "client.view"]
    server_views = [s for s in spans if s.name == "server.view"]
    assert len(server_views) == 3  # pre-restore, failed stale, retried
    parents = {s.span_id for s in client_views}
    assert all(s.parent_id in parents for s in server_views)
    retried, = [s for s in client_views if s.attrs.get("retry")]
    joined = [s for s in server_views if s.parent_id == retried.span_id]
    assert len(joined) == 1
    assert joined[0].trace_id == retried.trace_id


def test_trace_ids_across_session_eviction():
    obs.enable()
    server, c1, fit = _fit_client(max_sessions=1)
    c1.view(fit.handle_id)
    c2 = VedaliaClient(server=server)
    c2.view(fit.handle_id)  # second session evicts c1's (max_sessions=1)
    # Recovery re-issues c1's session; its cursor died with the session,
    # so the delta request degrades to a full resync, never an error.
    result = c1.view(fit.handle_id, since=c1.cursors[fit.handle_id])
    assert result.resync

    ids = [s.span_id for s in trace.spans()]
    assert len(ids) == len(set(ids))
    # Distinct client calls are distinct traces — eviction recovery must
    # not fuse c1's trace with c2's.
    c1_retries = {s.trace_id for s in trace.spans()
                  if s.name == "client.view" and s.attrs.get("retry")}
    assert c1_retries  # the eviction actually forced a retry
    assert len({s.trace_id for s in trace.spans()}) >= 4
