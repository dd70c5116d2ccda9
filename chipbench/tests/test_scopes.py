"""The op paths of a trace (``xplane_meta.py``) and device time by the
program's named scopes (``scopes.py``): on the small trace of
``record_trace.py``, and on a scoped trace of the program's trainer recorded
on one v5e (``record_scoped_trace.py``)."""

import importlib.metadata
import importlib.util
from pathlib import Path

import pytest

from chipbench import scopes, trace, xplane_meta

DATA = Path(__file__).resolve().parent / "data"
DP4 = DATA / "dp4_small.xplane.pb"


def xplane_pb2():
    """TensorFlow's own ``xplane_pb2``, loaded from its file alone: the
    ``tensorflow`` package takes seconds to import and is not needed."""
    try:
        dist = importlib.metadata.distribution("tensorflow")
    except importlib.metadata.PackageNotFoundError:
        pytest.skip("TensorFlow is not installed")
    path = Path(dist.locate_file("tensorflow/tsl/profiler/protobuf/xplane_pb2.py"))
    if not path.exists():
        pytest.skip(f"no {path}")
    spec = importlib.util.spec_from_file_location("xplane_pb2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    except ImportError as e:
        pytest.skip(f"TensorFlow's xplane_pb2 does not import: {e}")
    return mod


def reference_tf_ops(path) -> dict:
    pb = xplane_pb2()
    space = pb.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {k: m.name for k, m in plane.stat_metadata.items()}
        ops = {}
        for meta in plane.event_metadata.values():
            for st in meta.stats:
                if names.get(st.metadata_id) == "tf_op":
                    ops[meta.name] = (st.str_value if st.WhichOneof("value") == "str_value"
                                      else names[st.ref_value])
        out[plane.name] = ops
    return out


def test_reader_gives_the_op_paths():
    ops = xplane_meta.tf_ops(str(DP4))["/device:TPU:0"]
    fusion = [p for n, p in ops.items() if " fusion(" in n and "dot_general" in p]
    assert fusion == ["jit(body)/shard_map/dot_general:"]
    assert [p for n, p in ops.items() if n.startswith("%psum_invariant.")] == [
        "jit(body)/shard_map/psum_invariant:"]


@pytest.mark.parametrize("name", ["dp4_small.xplane.pb", "moe_small_scoped.xplane.pb"])
def test_reader_agrees_with_tensorflow(name):
    path = DATA / name
    got = xplane_meta.tf_ops(str(path))
    want = reference_tf_ops(path)
    assert got == want
    assert sum(len(v) for v in got.values()) > 0


def test_scope_buckets_sum_to_busy_time():
    got = scopes.reduce(str(DP4), steps=3)
    small = trace.reduce(str(DP4), steps=3)
    assert sum(got["scopes"].values()) * 3 == pytest.approx(small["busy_s"], rel=1e-9)
    # no scope in this trace: its matmul is unscoped, its two collectives not
    assert set(got["scopes"]) == {scopes.COLLECTIVES, scopes.UNSCOPED}
    assert "host_gap_s" not in got and got["spans"] == {}


@pytest.fixture(scope="module")
def scoped():
    return scopes.reduce(str(DATA / "moe_small_scoped.xplane.pb"), steps=3)


def test_scoped_trace_has_every_layer_of_the_step(scoped):
    # a one-chip step: every scope but the gradient sync's, no collective
    assert set(scoped["scopes"]) == set(scopes.SCOPES) - {"grad_sync"} | {scopes.UNSCOPED}
    assert sorted(scoped["spans"]) == sorted(
        ["trainer.control", "trainer.input", "trainer.shard_batch", "trainer.dispatch",
         "trainer.wait", "trainer.record", "trainer.checkpoint"])


def test_scoped_trace_pinned(scoped):
    # seconds per traced step of Granite's structure at the smoke widths on
    # one v5e, where routing and dispatch outweigh the tiny matmuls (read
    # once from this file, kept to catch a change of the reduction)
    assert scoped["scopes"] == pytest.approx({
        "moe_dispatch": 1.4276633e-4, "attention": 1.5213333e-5,
        "unscoped": 1.3570333e-5, "ffn": 1.1211333e-5,
        "optimizer": 7.261333e-6, "embed_head": 5.090667e-6,
        "layers": 4.241667e-6}, rel=1e-6)
    assert scoped["host_gap_s"] == pytest.approx(1.453935e-3, rel=1e-6)
    busy = trace.reduce(str(DATA / "moe_small_scoped.xplane.pb"), steps=3)["busy_s"]
    assert sum(scoped["scopes"].values()) * 3 == pytest.approx(busy, rel=1e-9)
