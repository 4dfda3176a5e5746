"""The raw trace reader, sampler phases, program spans and their shares."""

import os
import re

import pytest

import _paths  # noqa: F401
from repro.obs import trace
from vbench import harness, peaks, phases, tracing, xspace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REFIT_PR12 = os.path.join(DATA, "v5e_refit.xplane.pb")


def test_xspace_reads_the_name_stack_of_each_op():
    """The v5e trace's XLA operations carry their JAX name stack in event
    metadata (`tf_op`), with the source line beside it."""
    md = xspace.op_metadata(REFIT_PR12)
    assert list(md) == ["/device:TPU:0", "/device:CUSTOM:Megascale Trace"]
    ops = md["/device:TPU:0"]
    tf_ops = {t for t, _src in ops.values() if t}
    assert len(tf_ops) == 36
    assert any(re.match(r"jit\(run_many\)/.*/gather:$", t) for t in tf_ops)
    assert any(t.startswith("jit(log_likelihood)/") for t in tf_ops)
    assert all(src.endswith(tuple("0123456789"))
               for t, src in ops.values() if t.startswith("jit(log_"))


def test_raw_reader_matches_profile_data():
    """`phases.extract` reads the same window, operations and benchmark
    annotations as `tracing.extract` (to the nanosecond)."""
    base, ext = tracing.extract(REFIT_PR12), phases.extract(REFIT_PR12)
    for key in ("window_ns", "devices", "host"):
        assert ext[key] == base[key]
    assert [len(v) for v in ext["phases"].values()] == [
        len(v) for v in ext["devices"].values()]


def _run_on(rec):
    run = harness.Run(cell=harness.find_cell("amazon.refit"), seed=1,
                      seconds=1.0, trace=True, t_start=0.0)
    run.device_trace = rec
    run.peaks = peaks.peaks_for("TPU v5 lite")
    run.requests = [harness.Request("refine_batch", 0.0, 1.0, True, 1.0e6)]
    return run


@pytest.mark.parametrize("name,value", [
    ("device_idle_share.refit", 91.92913593860925),
    ("sampler_outside_kernel_share.refit", 92.6563400358192),
    ("lda_gibbs_resample_batched_roofline", 19.8564458391612),
])
def test_accepted_readers_read_the_same(name, value):
    """Each accepted device-trace reader reads on the recorded trace what it
    read before phases and program spans were named, from the record as
    the harness keeps it and from the record with both added."""
    read = harness.metric_reader(name).read
    assert read(_run_on(tracing.extract(REFIT_PR12))) == pytest.approx(
        value, rel=1e-12)
    assert read(_run_on(phases.extract(REFIT_PR12))) == pytest.approx(
        value, rel=1e-12)


@pytest.mark.parametrize("tf_op,kernel,want", [
    ("jit(run_many)/while/body/closed_call/jit(sweep_many)/gather/vmap()/"
     "gather:", False, "gather"),
    ("jit(sweep_many)/vmap(count_rebuild)/scatter-add:", False,
     "count_rebuild"),
    ("jit(mh_sweep)/jit(mh_resample)/alias_tables/while/body/sort:", False,
     "alias_tables"),
    ("jit(sweep_many)/noise/vmap(jit(_gumbel))/jit(_uniform)/xor:", False,
     "noise"),
    ("jit(log_likelihood)/perplexity/while/body/closed_call/gather:", False,
     "perplexity"),
    # A primitive named like a phase is not a scope.
    ("jit(log_likelihood)/while/body/closed_call/gather:", False, "other"),
    ("jit(sweep_many)/lda_gibbs_resample_batched/pallas_call:", True,
     "kernel"),
    ("", False, "other"),
])
def test_phase_of_an_op(tf_op, kernel, want):
    assert phases.phase(tf_op, kernel) == want


def _record():
    """A request span over nested program spans; a `while` op whose body is
    a gather; a kernel; two XLA programs (nanoseconds)."""
    return {
        "window_ns": [0.0, 100.0],
        "devices": {"/device:TPU:0": [["while", 0.0, 40.0, False],
                                      ["fusion", 5.0, 30.0, False],
                                      ["lda_gibbs", 50.0, 10.0, True]]},
        "phases": {"/device:TPU:0": ["other", "gather", "kernel"]},
        "host": [["vbench.refine_batch", 0.0, 100.0],
                 ["client.refine_batch", 1.0, 98.0],
                 ["server.refine_batch", 2.0, 90.0],
                 ["service.refine_many", 3.0, 80.0],
                 ["batch.launch", 10.0, 20.0]],
        "modules": {"/device:TPU:0": [["jit_run_many", 0.0, 60.0],
                                      ["jit__pad", 70.0, 1.0]]},
    }


def test_split_by_phase_span_and_program():
    got = phases.split(_record())
    assert got["busy_s"] == pytest.approx(50e-9)
    # The loop's self time is its own; phases and kernel add up to busy.
    assert got["phase_s"] == pytest.approx(
        {"other": 10e-9, "gather": 30e-9, "kernel": 10e-9})
    assert sum(got["phase_share"].values()) == pytest.approx(100.0)
    assert got["other_by_program"] == [["jit_run_many", pytest.approx(1e-8)]]
    # Span self time: each span less the spans nested in it.
    assert got["layer_s"]["wire"] == pytest.approx((8.0 + 10.0) * 1e-9)
    assert got["layer_s"]["service"] == pytest.approx(60e-9)
    assert got["layer_s"]["batch"] == pytest.approx(20e-9)
    assert got["layer_share"]["service"] == pytest.approx(60.0)
    # Idle [40, 50] and [60, 100]: 49 of its 50 ns under a program span.
    assert got["idle_s"] == pytest.approx(50e-9)
    assert got["idle_under_program_span_share"] == pytest.approx(98.0)
    # ... named by the innermost span open across each part of it.
    assert {n: v for n, v, _pct in got["idle_by_span"]} == pytest.approx({
        "service.refine_many": 33e-9, "server.refine_batch": 9e-9,
        "client.refine_batch": 7e-9, "vbench.refine_batch": 1e-9})
    assert got["requests"] == 1 and got["programs_per_request"] == 2.0
    # The longest gap is named by the innermost span open across it.
    gaps = tracing.reduce(_record())["breakdown"]["idle_gaps"]
    assert gaps[0] == ["service.refine_many", pytest.approx(40e-9)]


def _span(name, start, end):
    return trace.Span(trace_id="t", span_id=name, parent_id=None, name=name,
                      start_s=start, duration_s=end - start)


@pytest.mark.parametrize("metric,value", [
    ("wire_share.refit", 18.0), ("service_share.corpus", 60.0),
    ("batch_host_share.refit", 20.0)])
def test_span_shares_read_the_program_spans(monkeypatch, metric, value):
    spans = [_span("client.refine_batch", 1.0, 99.0),
             _span("server.refine_batch", 2.0, 92.0),
             _span("service.refine_many", 3.0, 83.0),
             _span("batch.launch", 10.0, 30.0),
             _span("client.refine_batch", 150.0, 160.0)]  # after the window
    monkeypatch.setattr(trace, "spans", lambda: spans)
    run = _run_on(None)
    run.window = (0.0, 100.0)
    assert harness.metric_reader(metric).read(run) == pytest.approx(value)
    # A program without the layer's spans reads nothing.
    monkeypatch.setattr(trace, "spans", lambda: spans[:2])
    got = harness.metric_reader(metric).read(run)
    assert got is None or metric.startswith("wire_share")


#: Short traced windows of each cell on a v5e with the program's spans and
#: phase scopes (cut with `xspace.drop_planes`; checkout path replaced byte
#: for byte), and the phases each must show.
RECORDED = {
    "v5e_refit_spans.xplane.pb": {"gather", "noise", "count_rebuild",
                                  "perplexity", "kernel", "other"},
    "v5e_corpus_spans.xplane.pb": {"gather", "noise", "alias_tables",
                                   "count_rebuild", "perplexity", "kernel",
                                   "other"},
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_raw_reader_matches_profile_data_with_program_spans(name):
    """On traces that hold the program's spans too, `phases.extract` still
    reads what `tracing.extract` reads (to the nanosecond), and adds the
    program's spans to `host` and nothing else. The accepted readers read
    `tracing.extract`'s record; this keeps the two readers equal."""
    path = os.path.join(DATA, name)
    base, ext = tracing.extract(path), phases.extract(path)
    assert ext["window_ns"] == base["window_ns"]
    assert ext["devices"] == base["devices"]
    ours = [h for h in ext["host"] if h[0].startswith(tracing.REQUEST_PREFIX)]
    assert ours == base["host"]
    spans = [h for h in ext["host"] if h not in ours]
    assert spans and all(h[0].startswith(phases.SPAN_PREFIXES)
                         for h in spans)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_phases_add_up_to_busy(name):
    """Phase shares, the kernel's and `other` add up to the busy time the
    accepted readers see (to the few ns by which ops overlap without
    nesting), and every phase the cell runs is named."""
    path = os.path.join(DATA, name)
    got = phases.split(phases.extract(path))
    busy = tracing.reduce(tracing.extract(path))["busy_s"]
    assert got["busy_s"] == pytest.approx(busy, rel=1e-12)
    assert sum(got["phase_s"].values()) == pytest.approx(busy, rel=1e-6)
    assert sum(got["phase_share"].values()) == pytest.approx(100.0)
    assert set(got["phase_s"]) == RECORDED[name]
    assert got["programs_per_request"] >= 1


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_idle_is_named_by_program_spans(name):
    """The program's spans reach the trace: the longest idle gaps are named
    by them, where the harness's record names them all by its request."""
    path = os.path.join(DATA, name)
    rec = phases.extract(path)
    named = tracing.reduce(rec)["breakdown"]["idle_gaps"]
    plain = tracing.reduce(tracing.extract(path))["breakdown"]["idle_gaps"]
    assert all(n.startswith(tracing.REQUEST_PREFIX) for n, _s in plain)
    assert named[0][0].startswith(phases.SPAN_PREFIXES)
    assert [s for _n, s in named] == [s for _n, s in plain]
    got = phases.split(rec)
    assert got["idle_under_program_span_share"] > 90.0
    assert got["layer_s"]["wire"] > 0 and got["layer_s"]["service"] > 0


def test_other_is_split_by_operation_kind():
    """Operations without a phase are named by XLA program, HLO category
    and output shape: on the refit fixture the largest is the count
    rebuild's scatter into the V x K word-topic table (50,000 x 12), which
    the TPU compiler emits as a custom fusion without `tf_op`."""
    assert phases.kind("%fusion = f32[600000]{0:T(1024)} fusion(...)",
                       {"hlo_category": "custom fusion"}) == (
        "custom fusion f32[600000]")
    assert phases.kind("%c = (s32[3]{0}, u32[]) copy-start(...)", {}) == (
        "? s32[3]")
    rec = phases.extract(os.path.join(DATA, "v5e_refit_spans.xplane.pb"))
    assert [len(v) for v in rec["kinds"].values()] == [
        len(v) for v in rec["devices"].values()]
    got = phases.split(rec)
    assert got["other_by_op"][0][:2] == ["jit_run_many",
                                         "custom fusion f32[600000]"]
    assert sum(v for _p, _k, v in got["other_by_op"]) <= (
        got["phase_s"]["other"] * (1 + 1e-9))
