"""Trace reduction, work model and peak table of the benchmark."""

import os

import pytest

import _paths  # noqa: F401
from vbench import peaks, tracing, work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _rec(ops, window=(0.0, 100.0), host=()):
    return {"window_ns": list(window), "devices": {"/device:TPU:0": ops},
            "host": list(host)}


def test_idle_share_is_union_of_intervals():
    # Overlapping and nested operations count once; the part of an
    # operation outside the window does not count.
    ops = [["a", 10.0, 20.0, False], ["b", 20.0, 20.0, False],
           ["c", 25.0, 5.0, False], ["d", 90.0, 30.0, False]]
    red = tracing.reduce(_rec(ops))
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx((30.0 + 10.0) * 1e-9)


def test_busy_is_averaged_over_devices():
    rec = _rec([["a", 0.0, 50.0, False]])
    rec["devices"]["/device:TPU:1"] = [["a", 0.0, 100.0, False]]
    assert tracing.reduce(rec)["busy_s"] == pytest.approx(75e-9)


def test_kernel_name_match():
    name = ('%alias_mh_sweep.1 = s32[1,4096]{1,0:T(1,128)S(1)} custom-call('
            's32[4096,128] %a), custom_call_target="tpu_custom_call"')
    assert tracing.op_name(name) == "alias_mh_sweep"
    assert tracing.is_kernel(name)
    batched = '%alias_mh_sweep_batched = s32[2] custom-call()'
    assert tracing.op_name(batched) == "alias_mh_sweep_batched"
    assert tracing.op_name("%fusion.12 = f32[8] fusion(%x)") == "fusion"
    ops = [["alias_mh_sweep", 0.0, 10.0, True],
           ["alias_mh_sweep_batched", 20.0, 5.0, True],
           ["fusion", 40.0, 10.0, False]]
    red = tracing.reduce(_rec(ops), kernels=("alias_mh_sweep",))
    assert red["kernel_s"]["alias_mh_sweep"] == pytest.approx(10e-9)
    assert red["custom_call_s"] == pytest.approx(15e-9)


def test_self_time_and_idle_gaps_named_by_host():
    # A loop's event spans its body: the breakdown gives it self time only.
    ops = [["while", 0.0, 40.0, False], ["fusion", 5.0, 30.0, False]]
    host = [["vbench.request", 0.0, 100.0], ["vbench.inner", 60.0, 20.0]]
    red = tracing.reduce(_rec(ops, host=host))
    by = dict(red["breakdown"]["device_ops"])
    assert by["while"] == pytest.approx(10e-9)
    assert by["fusion"] == pytest.approx(30e-9)
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["vbench.inner", pytest.approx(60e-9)]


def test_recorded_chip_trace():
    """A trace recorded on a v5e chip: the reduction finds the window, the
    fused Gibbs kernel and a busy time inside the window."""
    path = os.path.join(DATA, "v5e_refit.xplane.pb")
    rec = tracing.extract(path)
    assert list(rec["devices"]) == ["/device:TPU:0"]
    red = tracing.reduce(rec, kernels=("lda_gibbs_resample_batched",))
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 < red["kernel_s"]["lda_gibbs_resample_batched"] <= red["busy_s"]
    assert red["custom_call_s"] >= red["kernel_s"][
        "lda_gibbs_resample_batched"]
    assert len(red["breakdown"]["device_ops"]) == 10


def test_work_model_hand_checked():
    # K=4: per token 2*4*4 + 12 + 4 = 48 bytes and 16 flops; 3 docs and
    # 5 words read and written once: 2 * 8 * 4 * 4 = 256 bytes.
    w = work.sweep_work(tokens=10, docs=3, words_used=5, k=4)
    assert w.flops == 160.0
    assert w.bytes == 480.0 + 256.0
    t, bound = w.least_time({"flops_per_s": 1.0, "bytes_per_s": 1e6})
    assert bound == "flops" and t == 160.0
    t, bound = w.least_time({"flops_per_s": 1e6, "bytes_per_s": 1.0})
    assert bound == "bytes" and t == 736.0


def test_peaks_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
