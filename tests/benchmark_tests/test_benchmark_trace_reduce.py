"""The reduction from a profiler trace to busy time, on a small trace
recorded on the chip (TPU v5 lite, PR 24's first chip call: six runs of one
small jitted step with 50 ms sleeps between them)."""

import os

import pytest

from benchmark.harness import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "small.xplane.pb")


def test_union_counts_overlaps_once():
    assert trace_reduce.union_ns([(0, 10), (5, 12), (20, 25), (21, 22)]) == 17
    assert trace_reduce.union_ns([]) == 0


def test_fixture_has_one_device_plane_with_ops_and_modules():
    planes = trace_reduce.device_lines(FIXTURE)
    assert sorted(planes) == [0]
    assert len(planes[0]["XLA Ops"]) == 48
    assert len(planes[0]["XLA Modules"]) == 12


def test_busy_time_of_the_fixture():
    r = trace_reduce.reduce_trace(FIXTURE, chips=1)
    assert r["busy_s"] == pytest.approx(0.000134282, rel=1e-6)
    assert r["module_runs"] == 12
    # busy time is the union of the operations, under the sum of the programs
    modules = trace_reduce.device_lines(FIXTURE)[0]["XLA Modules"]
    assert 0 < r["busy_s"] <= sum(d for _n, _s, d in modules) / 1e9
    assert r["device_ops"][0][0] == "fusion" and len(r["device_ops"]) <= 10
    assert r["device_ops"][0][1] == pytest.approx(8.9991e-05, rel=1e-4)
    # six steps with 50 ms sleeps: the longest gaps are those sleeps
    gaps = r["idle_gaps"]
    assert len(gaps) <= 10 and gaps[0][0].startswith("before jit_")
    assert 0.04 < gaps[0][1] < 0.08


def test_short_names():
    assert trace_reduce.short_name(
        "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion.3"
    assert trace_reduce.short_name("jit_step(532)") == "jit_step"


def test_no_device_plane_or_too_few_is_an_error_not_a_zero(tmp_path):
    with pytest.raises(trace_reduce.NoDeviceEvents):
        trace_reduce.reduce_trace(FIXTURE, chips=2)
    with pytest.raises(trace_reduce.NoDeviceEvents):
        trace_reduce.find_xplane(str(tmp_path))
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(open(FIXTURE, "rb").read())
    assert trace_reduce.find_xplane(str(tmp_path)).endswith("h.xplane.pb")
