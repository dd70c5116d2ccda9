"""The program's own measurement: named scopes in the compiled train step,
the training loop's host spans, and the compile counter.

The scopes are checked in the op metadata of the compiled step (what a
device trace carries as each op's ``tf_op`` path), with the benchmark's
rule for laying an op to its innermost scope (``chipbench/scopes.py``).
The spans are read back from a CPU profile with the benchmark's reader
(``chipbench/trace.py``).
"""

import functools
import gc
import glob
import importlib.util
import json
import os
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import scopes, trace
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.data.pipeline import SyntheticLM
from repro.runtime import tracing
from repro.runtime.fault_tolerance import StepWatchdog
from repro.train import Trainer, TrainerOptions
from repro.train.train_step import abstract_train_state, make_train_step

MODEL_SCOPES = ("layers", "attention", "ffn", "embed_head")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(hlo_text: str) -> set[str]:
    return set(OP_NAME.findall(hlo_text))


def by_scope(names) -> dict[str, set[str]]:
    out = {}
    for n in names:
        s = scopes.scope_of(n)
        if s is not None:
            out.setdefault(s, set()).add(n)
    return out


def compiled_step(name: str, wrap=lambda f: f, remat: str = "full") -> str:
    cfg = registry.get(name, smoke=True)
    tc = TrainConfig(remat=remat)
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32) for k in ("tokens", "labels")}
    step = jax.jit(wrap(make_train_step(cfg, tc)), donate_argnums=0)
    return step.lower(abstract_train_state(cfg, tc), batch).compile().as_text()


def compiled_step_ops(name: str) -> set[str]:
    return op_names(compiled_step(name))


@pytest.fixture(scope="module")
def dense_ops():
    return compiled_step_ops("qwen2-1.5b")


@pytest.fixture(scope="module")
def moe_ops():
    return compiled_step_ops("granite-moe-1b-a400m")


def check_model_scopes(names, want):
    got = by_scope(names)
    for s in want:
        assert any("transpose(" not in n for n in got.get(s, ())), f"{s} not in the forward"
    for s in set(want) - {"optimizer", "grad_sync"}:
        assert any("transpose(" in n for n in got.get(s, ())), f"{s} not in the backward"


def test_dense_step_carries_scopes(dense_ops):
    check_model_scopes(dense_ops, MODEL_SCOPES + ("optimizer",))
    assert "moe_dispatch" not in by_scope(dense_ops)


def test_moe_step_carries_scopes(moe_ops):
    check_model_scopes(moe_ops, MODEL_SCOPES + ("moe_dispatch", "optimizer"))


DP4_OPS = """
import json, jax
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.data.pipeline import SyntheticLM, shard_batch
from repro.launch.mesh import make_mesh
from repro.parallel import context as pctx
from repro.train import Trainer, TrainerOptions

cfg = registry.get("granite-moe-1b-a400m", smoke=True)
mesh = make_mesh((4,))
with jax.set_mesh(mesh):
    pctx.set_mesh(mesh)
    tc = TrainConfig(remat="full", sync_algorithm="planned_sharded",
                     bucket_bytes=1 << 16)
    src = SyntheticLM(cfg.vocab_size, 32, 8)
    tr = Trainer(cfg, tc, src, mesh=mesh,
                 options=TrainerOptions(ckpt_dir=CKPT_DIR))
    state = tr.init_or_restore()
    batch = shard_batch(src.batch(0), mesh)
    text = tr._step_fn.lower(state, batch, tr._plan_codes).compile().as_text()
print("OPS", json.dumps(sorted(set(OP_NAME.findall(text)))))
"""


def test_planned_sharded_step_carries_all_scopes(subproc, tmp_path):
    code = (f"import re\nOP_NAME = re.compile({OP_NAME.pattern!r})\n"
            f"CKPT_DIR = {str(tmp_path)!r}\n" + DP4_OPS)
    out = subproc(code, devices=4)
    names = json.loads(out.split("OPS", 1)[1])
    check_model_scopes(names, scopes.SCOPES)
    # the sync's collectives and its buckets' local work are under the scope
    sync = by_scope(names)["grad_sync"]
    assert any("psum" in n or "ppermute" in n or "all_gather" in n for n in sync), sorted(sync)[:20]


def test_scope_rule_takes_the_innermost_scope(dense_ops, moe_ops):
    # paths as the compiled step has them, transforms and all
    in_layers = lambda n: "(layers)" in n or "/layers/" in n
    layers_only = [n for n in dense_ops if in_layers(n) and not any(
        s in n for s in ("attention", "ffn"))]
    assert layers_only and all(scopes.scope_of(n) == "layers" for n in layers_only)
    nested = [n for n in dense_ops | moe_ops if in_layers(n) and "/attention/" in n]
    assert nested and all(scopes.scope_of(n) == "attention" for n in nested)
    in_moe = [n for n in moe_ops if "/ffn/" in n and in_layers(n)]
    assert in_moe and all(scopes.scope_of(n) == "ffn" for n in in_moe)
    assert scopes.scope_of(
        "jit(step_body)/transpose(jvp(layers))/while/body/dynamic_update_slice") == "layers"
    assert scopes.scope_of("jit(step_body)/transpose(jvp(embed_head))/dot_general:") == "embed_head"
    # a scope's name inside another word, or a jit of that name, is no scope
    assert scopes.scope_of("jit(step_body)/jit(_take)/gather") is None
    assert scopes.scope_of("jit(layers_norm)/add") is None
    assert scopes.scope_of("jit(body)/shard_map/psum_invariant:") is None


def test_a_scope_leaves_the_compiled_program_alone():
    path = Path(__file__).resolve().parents[1] / "tools" / "canon_hlo.py"
    spec = importlib.util.spec_from_file_location("canon_hlo", path)
    canon_hlo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(canon_hlo)

    def scoped(step):
        @functools.wraps(step)          # the same module name
        def f(state, batch):
            with jax.named_scope("outer"):
                return step(state, batch)
        return f

    plain = compiled_step("qwen2-1.5b")
    outer = compiled_step("qwen2-1.5b", wrap=scoped)
    assert "outer/" in outer and "outer/" not in plain
    assert canon_hlo.canon(outer) == canon_hlo.canon(plain)
    # and a change of the program shows
    assert canon_hlo.canon(compiled_step("qwen2-1.5b", remat="none")) != canon_hlo.canon(plain)


def profile_trainer(tmp_path, steps=3):
    cfg = registry.get("qwen2-1.5b", smoke=True)
    tc = TrainConfig(remat="full", total_steps=steps)
    tr = Trainer(cfg, tc, SyntheticLM(cfg.vocab_size, 32, 2),
                 options=TrainerOptions(ckpt_dir=str(tmp_path / "ckpt"), log_every=1))
    log_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(log_dir)
    try:
        tr.run(steps)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return tr, path


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    return profile_trainer(tmp_path_factory.mktemp("trainer"))


def test_trainer_spans_in_loop_order(profiled):
    _, path = profiled
    _, host = trace.load(path)
    spans = sorted((s, e, n) for n, s, e in host if n.startswith("trainer."))
    names = [n for _, _, n in spans]
    assert names == list(tracing.SPANS) * 3 + ["trainer.checkpoint"]
    for (_, e, _), (s, _, _) in zip(spans, spans[1:]):
        assert e <= s, "spans overlap"


def test_trainer_counts_compiles_per_step(profiled):
    tr, _ = profiled
    assert [h["step"] for h in tr.history] == [1, 2, 3]
    first, *rest = [h["compiles"] for h in tr.history]
    assert first >= 1 and rest == [0, 0]
    assert tr.compiles == first and tr.compile_s > 0


def test_compile_counter_counts_only_while_open():
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones(3)
    before = tracing.counted.seconds
    outer, inner = tracing.CompileCounter(), tracing.CompileCounter()
    jax.jit(lambda x: x - 7)(x)                  # compiles with no counter open
    assert tracing.counted.seconds == before
    with outer:
        with inner:
            f(x)
        f(x)                                     # cached: no compile
    assert (inner.compiles, outer.compiles) == (1, 0)
    assert inner.seconds > 0 and tracing.counted.seconds > before


def test_compile_seconds_count_nested_traces_once():
    # each inner jit records a trace event inside the outer trace's
    inner = [jax.jit(functools.partial(lambda x, i: x * i, i=i)) for i in range(100)]
    f = jax.jit(lambda x: sum(g(x) for g in inner))
    x = jnp.ones(3)
    counter, events = tracing.CompileCounter(), []
    listener = lambda event, secs, **kw: events.append((event, secs))
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        # a full collection of earlier tests' garbage inside the window would
        # add to the wall time and to no event
        gc.collect()
        t0 = time.time()                         # the clock JAX stamps them with
        with counter:
            f(x).block_until_ready()
        wall = time.time() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    summed = sum(secs for e, secs in events if e in tracing.COMPILE_TIME_EVENTS)
    assert counter.compiles == 1
    assert 0 < counter.seconds <= wall < summed


def test_straggler_event_carries_compile_seconds():
    t = iter(range(100))
    wd = StepWatchdog(threshold=2.0, warmup=2, clock=lambda: next(t))
    for step in range(3):
        wd.start()
        wd.stop(step)
    wd._t0 = -100                                # a 100+ s step
    wd.stop(3, compile_s=97.5)
    (ev,) = wd.events
    assert (ev.step, ev.compile_s) == (3, 97.5)
