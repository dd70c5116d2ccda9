"""The contract between the harness and a configuration's reference module
(``chipbench/reference/__init__.py``), on the CPU: each configuration file
names its module, the runner, ``control.py`` and ``train_mfu`` reach the
model through it alone, and what moved into ``reference/common.py`` reads
what it read before. Also the per-layer metrics that read the program's
named scopes from a traced run's record."""

import dataclasses
import json
import subprocess
import sys
import types

import jax
import pytest

from chipbench import BENCH, ROOT, reference, run
from chipbench.reference import common, decoder
from chipbench.runners import train
from chipbench.tests.tiny import spec

DATA = BENCH / "tests" / "data"
BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in BENCH_JSON["configs"]}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_accepts_the_registry_entry(name):
    cfg_file = CONFIGS[name]
    ref = reference.load(cfg_file)
    cfg = ref.program_config(cfg_file)
    assert cfg.n_layers == cfg_file["num_hidden_layers"]
    assert ref.Arch.from_config(cfg_file).vocab == cfg_file["vocab_size"]


@pytest.mark.parametrize("key,value,field", [
    ("hidden_size", 1024, "d"),
    ("num_key_value_heads", 4, "kv_heads"),
    ("intermediate_size", 8192, "ffn"),
    ("qkv_bias", False, "qkv_bias"),
    ("tie_word_embeddings", False, "tie_embeddings"),
])
def test_a_changed_width_or_tie_is_refused(key, value, field):
    cfg_file = dict(CONFIGS["qwen2-1.5b"], **{key: value})
    with pytest.raises(ValueError, match=rf": (\w+, )*{field}(, \w+)* differ;"):
        decoder.program_config(cfg_file)


def test_an_untied_program_is_refused(monkeypatch):
    from repro.configs import registry

    get = registry.get
    monkeypatch.setattr(registry, "get", lambda name: dataclasses.replace(
        get(name), tie_embeddings=False))
    with pytest.raises(ValueError, match="tie_embeddings differ"):
        decoder.program_config(CONFIGS["qwen2-1.5b"])


@pytest.mark.parametrize("cfg_file,says", [
    ({"registry_id": "x"}, "names no 'reference'"),
    ({"registry_id": "x", "reference": "nonesuch"}, "'nonesuch' is no module"),
])
def test_a_missing_or_unknown_reference_is_named(cfg_file, says):
    with pytest.raises(ValueError, match=says):
        reference.load(cfg_file)


@pytest.mark.parametrize("workload", ["qwen2-train", "granite-moe-train",
                                      "qwen2-dp4-sharded"])
def test_tiny_reference_reads_as_before(workload):
    """The reference's three readings at the tiny size, bit for bit as they
    were recorded before its shared parts moved to ``common.py``."""
    seed = 2**31 + 9
    want = json.loads((DATA / "tiny_reference_readings.json").read_text())
    s = spec(workload)
    cfg_file, traffic = s["config_file"], s["traffic_file"]
    feed = train.Feed(traffic, cfg_file["vocab_size"], seed)
    batches = [tuple(x.reshape(traffic["chips"], -1, x.shape[-1])
                     for x in (b["tokens"], b["labels"]))
               for b in map(feed.make, range(3))]
    got = decoder.follow(decoder.Arch.from_config(cfg_file),
                         common.Optim.from_traffic(traffic), seed, batches,
                         jax.devices()[:1])
    assert got == want[f"{workload}/{seed}"]


def test_vocab_padding_pads_the_vocabulary_axis_alone():
    S = jax.ShapeDtypeStruct
    ref = {"embed": S((500, 8), "float32"), "w": S((8, 8), "float32"),
           "head": S((8, 500), "float32")}
    prog = {"embed": S((512, 8), "float32"), "w": S((8, 8), "float32"),
            "head": S((8, 512), "float32")}
    assert train.vocab_padding(ref, prog, 500) == {
        "embed": ((0, 12), (0, 0)), "w": ((0, 0), (0, 0)), "head": ((0, 0), (0, 12))}
    with pytest.raises(ValueError, match=r"\['w'\]"):
        train.vocab_padding(ref, dict(prog, w=S((8, 9), "float32")), 500)
    with pytest.raises(ValueError, match="named and nested"):
        train.vocab_padding(ref, {"embed": prog["embed"]}, 500)


def test_job_and_train_mfu_call_the_named_reference(monkeypatch):
    """A reference module that is not in ``chipbench/reference/`` but is
    registered under its package is what the runner and ``train_mfu`` use."""
    calls = []

    def spy(fn):
        def wrapped(*a, **kw):
            calls.append(fn.__name__)
            return fn(*a, **kw)
        return wrapped

    def train_flops_per_token(cfg_file, seq_len):
        return 1e9

    stand_in = types.ModuleType("chipbench.reference.stand_in")
    stand_in.Arch = decoder.Arch
    for fn in (decoder.program_config, decoder.init_weights, decoder.follow,
               decoder.delta_norms, train_flops_per_token):
        setattr(stand_in, fn.__name__, spy(fn))
    monkeypatch.setitem(sys.modules, stand_in.__name__, stand_in)

    s = spec("qwen2-train")
    s["config_file"] = dict(s["config_file"], reference="stand_in")
    job = train.Job(s, jax.devices()[:1])
    try:
        assert job.ref is stand_in
        job.first_steps(2**31 + 7, 1)
        job.follow(2**31 + 7, 1)
    finally:
        job.close()
    assert set(calls) == {"program_config", "init_weights", "follow", "delta_norms"}

    peak = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]["bf16_flops_per_s"]
    rec = {"device_kind": "TPU v5 lite", "config_file": s["config_file"],
           "seq_len": 64, "tokens_per_s": peak / 1e9, "chips": 1}
    assert run.metric_reader("train_mfu")(rec) == pytest.approx(100.0)
    assert calls[-1] == "train_flops_per_token"


@pytest.fixture(scope="module")
def printed():
    """What ``python3 -m chipbench.scopes`` prints for the scoped trace."""
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.scopes",
         str(DATA / "moe_small_scoped.xplane.pb"), "--steps", "3"],
        cwd=ROOT, capture_output=True, text=True, check=True, timeout=300)
    return json.loads(out.stdout)


@pytest.mark.parametrize("metric,scope", [("attention_ms", "attention"),
                                          ("moe_dispatch_ms", "moe_dispatch")])
def test_scope_metrics_read_what_scopes_prints(printed, metric, scope):
    rec = {"trace": train.read_trace(str(DATA / "moe_small_scoped.xplane.pb"), 3)}
    assert run.metric_reader(metric)(rec) == 1e3 * printed["scopes"][scope]
    # the record keeps what trace.reduce gives, for the other metrics
    assert rec["trace"]["busy_s"] > 0 and rec["trace"]["steps"] == 3


@pytest.mark.parametrize("metric", ["attention_ms", "moe_dispatch_ms"])
def test_scope_metrics_are_silent_without_the_scope(metric):
    assert run.metric_reader(metric)({"trace": None}) is None
    rec = {"trace": train.read_trace(str(DATA / "dp4_small.xplane.pb"), 3)}
    assert run.metric_reader(metric)(rec) is None
