"""Fault-tolerant re-planning (DESIGN.md §12): FailureMask identity, degraded
builders/validators, plan-cache isolation, degraded planning across both
backends, the online SyncController plan swap, the trainer's degradation /
straggler hooks, and the device-level no-retrace E2E.

The conformance oracles for degraded schedules live in
tests/test_collective_conformance.py (the failure-mask lane); this file
covers everything around them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.core import planner, simulator, timing, wrht
from repro.core.plan_cache import PlanCache, PlanKey
from repro.core.topology import FailureMask
from repro.data.pipeline import CorpusLM
from repro.runtime.fault_tolerance import (FailureInjector, StepWatchdog,
                                           StragglerEvent)
from repro.train import Trainer, TrainerOptions
from repro.train import train_step as TS

# ≥1 dead arc + ≥1 dead λ: the ISSUE's acceptance mask shape
MASK = FailureMask(dead_segments=((0, 1),), dead_wavelengths=((2, 0),))
# both fibers cut at two distinct spans: the ring is severed
SEVERED = FailureMask(dead_segments=((0, 0), (1, 0), (0, 2), (1, 2)))


# ---------------------------------------------------------------------------
# the mask itself
# ---------------------------------------------------------------------------

def test_mask_canonical_hashable_fingerprint():
    a = FailureMask(dead_segments=((0, 3), (0, 1), (0, 3)),
                    dead_wavelengths=((5, 2), (1, 0)))
    b = FailureMask(dead_segments=((0, 1), (0, 3)),
                    dead_wavelengths=((1, 0), (5, 2)))
    assert a == b and hash(a) == hash(b)
    assert a.fingerprint() == b.fingerprint() != "ok"
    assert FailureMask().empty and FailureMask().fingerprint() == "ok"
    assert FailureMask.from_lists(a.to_lists()) == a
    with pytest.raises(ValueError, match="lane"):
        FailureMask(dead_segments=((2, 0),))


def test_effective_wavelengths_and_group_size_shrink():
    two_dead = FailureMask(dead_wavelengths=((0, 0), (0, 1), (3, 2)))
    assert wrht.effective_wavelengths(8) == 8
    assert wrht.effective_wavelengths(8, two_dead) == 6
    assert wrht.effective_wavelengths(1, two_dead) == 1  # floored
    assert (wrht.feasible_group_size(8, failures=two_dead)
            <= wrht.feasible_group_size(8))


# ---------------------------------------------------------------------------
# degraded building: line topology routable, severed ring is not
# ---------------------------------------------------------------------------

def test_line_topology_builds_every_collective():
    line = FailureMask(dead_segments=((0, 2), (1, 2)))
    for coll in wrht.COLLECTIVES:
        try:
            sched = wrht.build_collective_schedule(coll, 8, 8, 1e6,
                                                   failures=line)
        except wrht.DegradedInfeasibleError:
            # flip-only collectives (the one-step all-to-all) may hit the
            # hop budget going the long way; trees must route
            assert coll == "alltoall"
            continue
        assert sched.failures == line


def test_severed_ring_is_infeasible():
    for coll in wrht.COLLECTIVES:
        with pytest.raises(wrht.DegradedInfeasibleError):
            wrht.build_collective_schedule(coll, 8, 8, 1e6, failures=SEVERED)


# ---------------------------------------------------------------------------
# plan cache: healthy and degraded plans never mix
# ---------------------------------------------------------------------------

def test_plan_cache_isolation(tmp_path):
    cache = PlanCache(disk_dir=tmp_path)
    k_ok = PlanKey(8, 8)
    k_bad = PlanKey(8, 8, failures=MASK)
    assert k_ok != k_bad
    assert k_ok.filename() != k_bad.filename()
    assert "-Fok-" in k_ok.filename()
    assert f"-F{MASK.fingerprint()}-" in k_bad.filename()

    s_ok, s_bad = cache.schedule(k_ok), cache.schedule(k_bad)
    assert s_ok.failures is None
    assert s_bad.failures == MASK
    # distinct entries: a second lookup of each hits its own plan
    assert cache.schedule(k_ok) is s_ok
    assert cache.schedule(k_bad) is s_bad

    # disk tier round-trips per-fingerprint artifacts independently
    cache.profile(k_bad)
    fresh = PlanCache(disk_dir=tmp_path)
    assert fresh.peek_profile(k_ok) is None          # never served the mask's
    assert fresh.peek_profile(k_bad) is not None
    assert (tmp_path / k_bad.filename()).exists()

    # the empty mask IS the healthy key (one entry, one artifact)
    assert PlanKey(8, 8, failures=FailureMask()) == k_ok
    assert PlanKey(8, 8, failures=FailureMask()).filename() == k_ok.filename()


# ---------------------------------------------------------------------------
# timing / simulator / planner under a mask
# ---------------------------------------------------------------------------

def test_degraded_times_never_beat_healthy():
    # every degraded schedule is also a valid healthy schedule, so the tuned
    # healthy optimum is a lower bound on the degraded one
    d = np.array([1e6, 1e8])
    healthy = timing.collective_times("allreduce", 16, d)
    degraded = timing.collective_times("allreduce", 16, d, failures=MASK)
    assert (np.asarray(degraded.total_s) >= np.asarray(healthy.total_s)
            - 1e-12).all()

    t_ok = simulator.run_collective("allreduce", 16, 1e8)
    t_bad = simulator.run_collective("allreduce", 16, 1e8, failures=MASK)
    assert t_bad.total_s >= t_ok.total_s - 1e-12


def test_fixed_schedule_baselines_reject_masks():
    with pytest.raises(ValueError, match="fixed schedule"):
        simulator.run_optical("ring", 16, 1e6, failures=MASK)


def test_planner_degraded_both_backends():
    sizes = [1 << 16, 1 << 22]
    for backend in ("analytic", "simulated"):
        plans = planner.plan_buckets(8, sizes, backend=backend,
                                     collective="reduce_scatter",
                                     failures=MASK)
        assert len(plans) == 2
        assert all(p.strategy in ("flat", "alltoall") for p in plans)
    # the simulated backend is exact: a severed ring has no feasible plan
    with pytest.raises(wrht.DegradedInfeasibleError):
        planner.plan_buckets(8, sizes, backend="simulated", failures=SEVERED)


# ---------------------------------------------------------------------------
# injector + straggler policy
# ---------------------------------------------------------------------------

def test_injector_degradation_one_shot_and_reset():
    inj = FailureInjector((5,), degrade_at={3: MASK})
    assert inj.degradation(2) is None
    assert inj.degradation(3) is MASK
    assert inj.degradation(3) is None          # one-shot
    with pytest.raises(Exception):
        inj.check(5)
    inj.check(5)                               # already fired
    inj.reset()
    assert inj.degradation(3) is MASK          # re-armed
    with pytest.raises(Exception):
        inj.check(5)


def test_injector_rejects_non_mask_at_construction():
    """The degrade_at satellite: a wrong value type fails at construction
    with a pointed error, not steps later inside Trainer.replan."""
    with pytest.raises(TypeError, match=r"degrade_at\[3\].*FailureMask"):
        FailureInjector(degrade_at={3: {"dead_segments": [(0, 1)]}})
    with pytest.raises(TypeError, match="got NoneType"):
        FailureInjector(degrade_at={0: None})
    FailureInjector(degrade_at={3: MASK})   # the real thing still works


def test_watchdog_deque_window_and_warmup():
    """The O(window) list.pop(0) is gone: the history is a bounded deque,
    and the warmup (previously hard-coded at 4) is a constructor arg."""
    ticks = iter(float(i) for i in range(10**6)).__next__

    wd = StepWatchdog(threshold=3.0, window=4, warmup=1,
                      clock=lambda: ticks())
    assert wd._times.maxlen == 4
    # warmup=1: the second step can already be flagged
    wd.start(); wd.stop(0)                        # dt = 1.0 (recorded)
    wd.start()
    for _ in range(8):                            # burn 8 ticks -> dt = 9.0
        ticks()
    wd.stop(1)
    assert [e.step for e in wd.events] == [1]
    # the window really bounds the median history
    for s in range(2, 12):
        wd.start(); wd.stop(s)
    assert len(wd._times) == 4

    # default warmup matches the historical 4-sample behaviour
    assert StepWatchdog().warmup == 4
    with pytest.raises(ValueError, match="warmup"):
        StepWatchdog(warmup=0)


def test_sync_controller_cumulative_and_recovery_memo():
    """Cumulative degradation (mask union) then recovery: fresh masks
    re-plan, previously-seen masks — including the healthy one — are memo
    hits (``last_replan_cached``), so the heal leg costs ~nothing."""
    tc = TrainConfig(sync_algorithm="planned_sharded", bucket_bytes=1 << 10)
    ctrl = TS.SyncController(_abstract_grads(), tc, _StubMesh())
    healthy = ctrl.arrays()

    ctrl.replan(MASK)
    assert not ctrl.last_replan_cached          # fresh degraded plan
    bigger = MASK.union(FailureMask(dead_wavelengths=((2, 1),)))
    assert bigger.covers(MASK)
    ctrl.replan(bigger)
    assert not ctrl.last_replan_cached          # union is a new mask
    ctrl.replan(MASK)                           # storm recedes partially
    assert ctrl.last_replan_cached
    restored = ctrl.replan(FailureMask())       # full recovery
    assert ctrl.last_replan_cached and ctrl.failures is None
    for k in healthy:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(healthy[k]))
    assert ctrl.replan_count == 4


def _smoke_trainer(tmp_path, **opt_kwargs):
    cfg = registry.get("qwen2-1.5b", smoke=True)
    tc = TrainConfig(lr=1e-3, total_steps=12, warmup_steps=2, remat="none")
    src = CorpusLM(cfg.vocab_size, 16, 4)
    return Trainer(cfg, tc, src, mesh=None,
                   options=TrainerOptions(ckpt_dir=tmp_path, log_every=100,
                                          **opt_kwargs))


def test_straggler_checkpoint_policy(tmp_path):
    """A flagged straggler under policy="checkpoint" forces an early save:
    step 8 takes 20 fake seconds vs a 1 s median, so a checkpoint must land
    at step 9 even though ckpt_every would first fire at step 12."""
    tr = _smoke_trainer(tmp_path, ckpt_every=100,
                        straggler_policy="checkpoint")
    ticks = []
    t = 0.0
    for s in range(12):
        dt = 20.0 if s == 8 else 1.0
        ticks += [t, t + dt]
        t += dt
    fake = iter(ticks).__next__
    tr.watchdog = StepWatchdog(tr.options.watchdog_threshold,
                               on_straggler=tr._on_straggler,
                               clock=lambda: float(fake()))
    tr.run(12)
    assert len(tr.watchdog.events) == 1 and tr.watchdog.events[0].step == 8
    assert 9 in tr.ckpt.steps(), tr.ckpt.steps()
    assert not tr._ckpt_requested


def test_straggler_policy_callable_and_validation(tmp_path):
    seen = []
    tr = _smoke_trainer(tmp_path / "cb", straggler_policy=seen.append)
    ev = StragglerEvent(step=7, duration_s=9.0, median_s=1.0)
    tr._on_straggler(ev)
    assert seen == [ev] and not tr._ckpt_requested
    with pytest.raises(ValueError, match="straggler_policy"):
        _smoke_trainer(tmp_path / "bad", straggler_policy="reboot")


def test_replan_requires_controller(tmp_path):
    tr = _smoke_trainer(tmp_path)       # auto mode: no controller
    assert tr.controller is None
    with pytest.raises(RuntimeError, match="planned_sharded"):
        tr.replan(MASK)


# ---------------------------------------------------------------------------
# SyncController: the online plan swap (unit level)
# ---------------------------------------------------------------------------

class _StubMesh:
    """Just enough mesh for the planner: named axes + sizes."""
    axis_names = ("data", "pod")
    shape = {"data": 4, "pod": 2}


def _abstract_grads():
    return {k: jax.ShapeDtypeStruct((n,), jnp.float32)
            for k, n in (("a", 37), ("b", 129), ("c", 513))}


def test_sync_controller_replan_swaps_codes():
    tc = TrainConfig(sync_algorithm="planned_sharded", bucket_bytes=1 << 10)
    ctrl = TS.SyncController(_abstract_grads(), tc, _StubMesh())
    healthy = ctrl.arrays()
    assert set(healthy) == {"rs:data", "rs:pod", "ag:data", "ag:pod"}
    assert all(v.dtype == jnp.int32 for v in healthy.values())

    degraded = ctrl.replan(MASK)
    assert ctrl.replan_count == 1 and ctrl.failures == MASK
    assert ctrl.last_replan_s is not None and ctrl.last_replan_s >= 0
    # shape/dtype invariance is the no-retrace contract
    for k in healthy:
        assert degraded[k].shape == healthy[k].shape
        assert degraded[k].dtype == healthy[k].dtype

    # an empty mask restores the healthy plan exactly
    restored = ctrl.replan(FailureMask())
    assert ctrl.failures is None
    for k in healthy:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(healthy[k]))


def test_sync_controller_infeasible_keeps_previous_plan():
    tc = TrainConfig(sync_algorithm="planned_sharded", bucket_bytes=1 << 10)
    ctrl = TS.SyncController(_abstract_grads(), tc, _StubMesh(),
                             backend="simulated")
    before = ctrl.plans
    with pytest.raises(wrht.DegradedInfeasibleError):
        ctrl.replan(SEVERED)
    assert ctrl.plans is before and ctrl.failures is None
    assert ctrl.replan_count == 0


# ---------------------------------------------------------------------------
# device-level E2E: mid-run plan swap with NO retrace (8 simulated devices)
# ---------------------------------------------------------------------------
# The jitted body counts its own traces; swapping healthy -> degraded codes
# must not add one.

NO_RETRACE = """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.configs.base import TrainConfig
from repro.core.topology import FailureMask
from repro.train import train_step as TS

def smap(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={'data', 'pod'})

mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ('data', 'pod'))
tc = TrainConfig(sync_algorithm="planned_sharded", bucket_bytes=1 << 10)
rng = np.random.default_rng(0)
tree = {k: rng.normal(size=(8, n)).astype(np.float32)
        for k, n in (('a', 37), ('b', 129), ('c', 513))}

ctrl = TS.SyncController(
    jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], jnp.float32),
                 tree),
    tc, mesh)

TRACES = 0
def body(stacked, codes):
    global TRACES
    TRACES += 1
    local = jax.tree.map(lambda x: x[0], stacked)
    out, _ = TS.sync_gradients(local, tc, mesh, sync_plans=ctrl.plans,
                               plan_codes=codes)
    return jax.tree.map(lambda x: x[None], out)

spec = P(('data', 'pod'))
healthy = ctrl.arrays()
in_specs = (jax.tree.map(lambda _: spec, tree),
            jax.tree.map(lambda _: P(), healthy))
step = jax.jit(smap(body, mesh, in_specs, jax.tree.map(lambda _: spec, tree)))

got0 = step(tree, healthy)
mask = FailureMask(dead_segments=((0, 1),), dead_wavelengths=((2, 0),))
degraded = ctrl.replan(mask)
got1 = step(tree, degraded)          # swapped plan, same compiled step
assert TRACES == 1, TRACES           # <- the no-retrace acceptance criterion
assert ctrl.last_replan_s is not None

# cumulative degradation: the storm worsens (mask union), then recedes back
# to healthy — the heal leg is a plan-memo hit and STILL no retrace
worse = mask.union(FailureMask(dead_wavelengths=((2, 1),)))
assert worse.covers(mask)
got2 = step(tree, ctrl.replan(worse))
assert not ctrl.last_replan_cached   # fresh degraded plan
healed = ctrl.replan(None)
assert ctrl.last_replan_cached       # recovery = zero planner work
got3 = step(tree, healed)
assert TRACES == 1, TRACES           # one compile across the whole storm
for k in healthy:
    np.testing.assert_array_equal(np.asarray(healed[k]),
                                  np.asarray(healthy[k]))
for k, v in tree.items():
    want = np.asarray(v).mean(axis=0)
    for got in (got0, got1, got2, got3):
        assert np.abs(np.asarray(got[k]) - want[None]).max() < 1e-5, k
print('NO_RETRACE_OK', ctrl.replan_count, '%.3fms' % (1e3 * ctrl.last_replan_s))
"""


def test_midrun_plan_swap_no_retrace(subproc):
    assert "NO_RETRACE_OK" in subproc(NO_RETRACE)


# trainer-level E2E on a typed mesh: the injector reports a mask mid-run and
# the trainer re-plans through the controller with no retrace of the jitted
# step.
TRAINER_REPLAN = """
import jax, numpy as np
from jax.sharding import AxisType
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.core.topology import FailureMask
from repro.data.pipeline import SyntheticLM
from repro.runtime.fault_tolerance import FailureInjector
from repro.train import Trainer, TrainerOptions
from repro.parallel import context as pctx

cfg = registry.get("qwen2-1.5b", smoke=True)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,)*3)
mask = FailureMask(dead_segments=((0, 1),), dead_wavelengths=((1, 0),))
with jax.set_mesh(mesh):
    pctx.set_mesh(mesh)
    tc = TrainConfig(lr=1e-3, total_steps=6, warmup_steps=2, remat="none",
                     sync_algorithm="planned_sharded", bucket_bytes=1 << 20)
    src = SyntheticLM(cfg.vocab_size, 16, 8)
    tr = Trainer(cfg, tc, src, mesh=mesh,
                 options=TrainerOptions(ckpt_dir=CKPT_DIR, ckpt_every=100,
                                        log_every=100),
                 injector=FailureInjector(degrade_at={3: mask}))
    assert tr.controller is not None
    state = tr.run(6)
assert tr.controller.replan_count == 1
assert tr.controller.failures == mask
sizes = getattr(tr._step_fn, "_cache_size", None)
if sizes is not None:
    assert tr._step_fn._cache_size() == 1, tr._step_fn._cache_size()
loss = float(tr.history[-1]["loss"]) if tr.history else 0.0
assert np.isfinite(np.asarray(jax.tree.leaves(state["params"])[0])).all()
print("TRAINER_REPLAN_OK", tr.controller.replan_count)
"""


def test_trainer_replans_midrun_multidevice(subproc, tmp_path):
    code = f"CKPT_DIR = {str(tmp_path)!r}\n" + TRAINER_REPLAN
    assert "TRAINER_REPLAN_OK" in subproc(code, timeout=900)


# trainer-level E2E of the CLOSED loop (DESIGN.md §14): no injected mask —
# the FaultManager observes a transient fault through the simulator probe,
# confirms it, replans, then heals back to the healthy plan via a memo hit.
TRAINER_FAULT_LOOP = """
import jax, numpy as np
from jax.sharding import AxisType
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.core.simulator import observe_faults
from repro.core.topology import FaultTimeline, FlapSchedule
from repro.data.pipeline import SyntheticLM
from repro.runtime.fault_tolerance import FaultManager, ReplanPolicy
from repro.train import Trainer, TrainerOptions
from repro.parallel import context as pctx

cfg = registry.get("qwen2-1.5b", smoke=True)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,)*3)
# λ 0 at node 2 dies during steps [2, 5), then heals
timeline = FaultTimeline((FlapSchedule("wavelength", (2, 0),
                                       down_intervals=((2, 5),)),))
mgr = FaultManager(lambda s: observe_faults(timeline, s),
                   ReplanPolicy(confirm_k=2, recover_k=2, cooldown_steps=2))
with jax.set_mesh(mesh):
    pctx.set_mesh(mesh)
    tc = TrainConfig(lr=1e-3, total_steps=10, warmup_steps=2, remat="none",
                     sync_algorithm="planned_sharded", bucket_bytes=1 << 20)
    src = SyntheticLM(cfg.vocab_size, 16, 8)
    tr = Trainer(cfg, tc, src, mesh=mesh,
                 options=TrainerOptions(ckpt_dir=CKPT_DIR, ckpt_every=100,
                                        log_every=100),
                 fault_manager=mgr)
    assert tr.controller is not None
    state = tr.run(10)
# degrade once (confirmed at step 3), heal once (readmitted after cooldown)
assert mgr.replan_count == 2, mgr.history
assert mgr.current_mask is None           # fully healed
assert tr.controller.failures is None
assert tr.controller.last_replan_cached   # the heal leg was a memo hit
assert [h["applied"] for h in mgr.history] == [True, True]
assert np.isfinite(np.asarray(jax.tree.leaves(state["params"])[0])).all()
print("FAULT_LOOP_OK", mgr.replan_count)
"""


def test_trainer_closed_fault_loop_multidevice(subproc, tmp_path):
    code = f"CKPT_DIR = {str(tmp_path)!r}\n" + TRAINER_FAULT_LOOP
    assert "FAULT_LOOP_OK" in subproc(code, timeout=900)
