"""The reduction from a trace to device time: on intervals written by
hand, and on a small trace recorded on a v5e:2x2 (``record_trace.py``)."""

from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data" / "dp4_small.xplane.pb"


def test_union_and_overlap():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [[0, 3], [5, 8]]
    assert trace.length(u) == 6
    assert trace.overlap(u, trace.union([(2, 6)])) == 2


def test_self_time_of_nested_ops():
    ops = [("while.1", 0, 10), ("fusion.1", 1, 3), ("fusion.2", 4, 8), ("copy.1", 12, 13)]
    got = {n: (t, leaf) for n, _, _, t, leaf in trace.self_times(ops)}
    assert got == {"while.1": (4, False), "fusion.1": (2, True),
                   "fusion.2": (4, True), "copy.1": (1, True)}


def test_op_names_and_collectives():
    assert trace.op_name("%fusion.12 = f32[8] fusion(f32[8] %p)") == "fusion.12"
    assert trace.is_collective(
        "%psum.7 = f32[8]{0:T(128)} all-reduce(f32[8]{0:T(128)S(6)} %div.5), channel_id=1")
    assert trace.is_collective("%collective-permute-start.3 = (f32[4]{0}, f32[4]{0}) "
                               "collective-permute-start(f32[4]{0} %x)")
    assert not trace.is_collective(
        "%fusion.7 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %all-reduce.2)")


@pytest.fixture(scope="module")
def small():
    return trace.reduce(str(DATA), steps=3)


def test_recorded_trace_devices_and_window(small):
    assert small["devices"] == 4
    assert 0 < small["busy_s"] <= small["window_s"]
    assert 0 <= small["idle_share_max"] < 1


def test_recorded_trace_collectives(small):
    assert 0 < small["collective_s"] < small["busy_s"]
    assert 0 <= small["exposed_s"] <= small["collective_s"]
    names = [n for n, _ in small["device_ops"]]
    assert any(trace.is_collective(n) for n in names)


def test_recorded_trace_pinned(small):
    # per device and step: a matmul, then an all-reduce and a ring pass of
    # [1024, 1024] bf16 with nothing running beside them, so all of their
    # time is exposed (read once from this file, kept to catch a change of
    # the reduction)
    assert small["collective_s"] == pytest.approx(2.5745925e-4, rel=1e-9)
    assert small["exposed_s"] == pytest.approx(2.5745925e-4, rel=1e-9)
    assert small["busy_s"] == pytest.approx(3.366025e-4, rel=1e-9)
    assert small["window_s"] == pytest.approx(8.215911e-3, rel=1e-9)
