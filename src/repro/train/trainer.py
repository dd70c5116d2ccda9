"""Training loop: checkpointing, auto-resume, watchdog, failure recovery.

The loop is deliberately restart-transparent: the data source is a pure
function of the step index and the train state carries its own step counter,
so ``Trainer.run()`` after a crash (or an ``InjectedFailure``) resumes from
the latest checkpoint and produces bit-identical results to an uninterrupted
run — asserted by tests/test_fault_tolerance.py.

Each stretch of a step runs in a host span named in
``repro.runtime.tracing.SPANS``, and the step call in the trainer's compile
counter: ``Trainer.compiles`` / ``compile_s``, and ``compiles`` in each
``history`` entry for its step. Once the step has compiled,
``Trainer.attention_paths`` says how many of its attention layers run the
fused kernel and how many ``blocked_attention`` (logged at INFO). Where
the experts are a held share, each ``history`` entry and log line carries
the share of routed entries that went to a held expert and the share of
those dropped by capacity (``MOE_COUNTERS``).
"""

from __future__ import annotations

import collections
import logging
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import Checkpointer
from repro.configs.base import ModelConfig, TrainConfig
from repro.data.pipeline import shard_batch
from repro.runtime.fault_tolerance import (
    FailureInjector, FaultManager, InjectedFailure, StepWatchdog)
from repro.models.layers import FUSED_KERNEL
from repro.runtime.tracing import CompileCounter, PathCounter, span
from .train_step import (
    abstract_train_state, make_train_state, make_train_step,
    train_state_specs)

log = logging.getLogger("repro.trainer")

# the step's counters of an expert layer that holds a share of the experts
# (models.layers.moe_apply), logged with the loss
MOE_COUNTERS = ("moe_held_share", "moe_dropped_share")


@dataclass
class TrainerOptions:
    ckpt_dir: str | Path = "checkpoints"
    ckpt_every: int = 50
    keep_n: int = 3
    max_restarts: int = 3
    watchdog_threshold: float = 3.0
    log_every: int = 10
    # what a flagged straggler triggers: "log" (record only), "checkpoint"
    # (force an early checkpoint so the likely restart loses less), or a
    # callable(StragglerEvent) for custom policies (e.g. re-shard/elastic)
    straggler_policy: object = "log"


@dataclass
class Trainer:
    cfg: ModelConfig
    tc: TrainConfig
    source: object                      # .batch(step) -> host batch dict
    mesh: object | None = None
    options: TrainerOptions = field(default_factory=TrainerOptions)
    injector: FailureInjector | None = None
    # the closed-loop fault-management path (DESIGN.md §14): detector-driven
    # masks into replan(); the injector's degrade_at stays as the manual
    # escape hatch for deterministic tests
    fault_manager: FaultManager | None = None

    def __post_init__(self):
        policy = self.options.straggler_policy
        if not callable(policy) and policy not in ("log", "checkpoint"):
            raise ValueError(
                f"unknown straggler_policy {policy!r} "
                "(expected 'log', 'checkpoint' or a callable)")
        self.ckpt = Checkpointer(self.options.ckpt_dir, keep_n=self.options.keep_n)
        self.watchdog = StepWatchdog(self.options.watchdog_threshold,
                                     on_straggler=self._on_straggler)
        raw_step = make_train_step(self.cfg, self.tc, self.mesh)
        # the online re-plan controller (planned_sharded only): kept off the
        # jitted callable, which jax.jit would strip (DESIGN.md §12)
        self.controller = getattr(raw_step, "controller", None)
        # with a mesh the state's layout is pinned (train_state_specs): the
        # step returns it as it came in, so the next step hits the same
        # executable instead of one GSPMD re-laid out
        self._state_shardings = None
        if self.mesh is not None:
            specs = train_state_specs(abstract_train_state(self.cfg, self.tc),
                                      self.mesh, self.tc.fsdp)
            self._state_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
        # the state is donated: the old params and optimizer state are dead
        # once the step returns (Checkpointer.save copies to the host before
        # the next step), so the step updates them in place instead of
        # holding two copies on the device
        self._step_fn = jax.jit(raw_step, donate_argnums=0,
                                out_shardings=(self._state_shardings, None))
        self._plan_codes = (None if self.controller is None
                            else self.controller.arrays())
        self._ckpt_requested = False
        self._compile = CompileCounter()
        self._paths = PathCounter()
        self.attention_paths: dict[str, int] | None = None
        self._stepped = False
        self.history: list[dict] = []
        if self.fault_manager is not None:
            self.fault_manager.attach(self.replan)

    # --------------------------------------------------------- fault hooks
    def _on_straggler(self, event):
        if self.fault_manager is not None:
            # stragglers are a pre-failure symptom — feed the detector
            # (DESIGN.md §14) before applying the local policy
            self.fault_manager.observe_straggler(event)
        policy = self.options.straggler_policy
        if callable(policy):
            policy(event)
            return
        log.warning("straggler at step %d: %.3fs vs median %.3fs "
                    "(%.3fs compiling)", event.step, event.duration_s,
                    event.median_s, event.compile_s)
        if policy == "checkpoint":
            self._ckpt_requested = True

    def replan(self, failure_mask=None):
        """Swap in degraded (or restored-healthy) gradient-sync schedules
        for the running jitted step (DESIGN.md §12).  The watchdog/injector
        path calls this with the reported
        :class:`~repro.core.topology.FailureMask`; the new plan takes effect
        on the next step with **no retrace** — the strategy-code arrays are
        traced inputs of the already-compiled step."""
        if self.controller is None:
            raise RuntimeError(
                "replan() needs the online re-plan controller — only the "
                "sharded modes (sync_algorithm='planned_sharded' or "
                "'planned_pipelined') build one")
        self._plan_codes = self.controller.replan(failure_mask)
        log.warning("re-planned gradient sync (mask=%s, %.1f ms)",
                    self.controller.failures,
                    1e3 * self.controller.last_replan_s)
        return self._plan_codes

    # -------------------------------------------------------------- state
    def init_or_restore(self):
        """The newest checkpoint in ``ckpt_dir``, else a fresh state from
        ``tc.seed``.  With a mesh the state is laid out over all of its
        devices as the step returns it, not left on the default one."""
        steps = self.ckpt.steps()
        if steps:
            target = abstract_train_state(self.cfg, self.tc)
            specs = (None if self.mesh is None else jax.tree.map(
                lambda s: s.spec, self._state_shardings))
            state = self.ckpt.restore(steps[-1], target, mesh=self.mesh,
                                      spec_tree=specs)
            log.info("restored checkpoint at step %d", steps[-1])
            return state
        init = jax.jit(partial(make_train_state, self.cfg, self.tc),
                       out_shardings=self._state_shardings)
        return init(jax.random.key(self.tc.seed))

    # ---------------------------------------------------------------- run
    def run(self, total_steps: int | None = None):
        total = total_steps if total_steps is not None else self.tc.total_steps
        restarts = 0
        while True:
            try:
                return self._run_inner(total)
            except InjectedFailure as e:
                restarts += 1
                log.warning("%s — restart %d/%d", e, restarts,
                            self.options.max_restarts)
                if restarts > self.options.max_restarts:
                    raise

    @property
    def compiles(self) -> int:
        """Compilations made by this trainer's step calls, cumulative."""
        return self._compile.compiles

    @property
    def compile_s(self) -> float:
        """Their seconds: tracing, lowering and the backend's compile or
        persistent-cache load (``repro.runtime.tracing``)."""
        return self._compile.seconds

    def _count_attention_paths(self, *args) -> dict[str, int]:
        """The step's attention layers by path: as traced (each layer's
        choice in Python), then as compiled. A layer traced onto the kernel
        runs ``blocked_attention`` where the compiled step holds no kernel
        (a platform other than the TPU). ``args`` are the step call's;
        lowering them again hits the executable the call compiled."""
        counts = collections.Counter({path: n for (layer, path), n
                                      in self._paths.counts.items()
                                      if layer == "attention"})
        if counts["kernel"]:
            text = self._step_fn.lower(*args).compile().as_text()
            if FUSED_KERNEL not in text:
                counts["blocked"] += counts.pop("kernel")
        return dict(counts)

    def _run_inner(self, total: int):
        state = self.init_or_restore()
        step = int(jax.device_get(state["step"]))
        while step < total:
            with span("trainer.control", step=step):
                if self.fault_manager is not None:
                    # primary replan path: telemetry -> hysteresis -> mask
                    # (DESIGN.md §14); infeasible proposals keep the
                    # previous plan per the manager's ReplanPolicy
                    self.fault_manager.on_step(step)
                if self.injector is not None:
                    self.injector.check(step)
                    mask = self.injector.degradation(step)
                    if mask is not None:
                        self.replan(mask)
            with span("trainer.input", step=step):
                host_batch = self.source.batch(step)
            with span("trainer.shard_batch", step=step):
                batch = shard_batch(host_batch, self.mesh)
            compiles, compile_s = self.compiles, self.compile_s
            args = (batch,) if self._plan_codes is None else (batch, self._plan_codes)
            with (span("trainer.dispatch", step=step), self._compile,
                  self._paths):
                self.watchdog.start()
                state, metrics = self._step_fn(state, *args)
            with span("trainer.wait", step=step):
                jax.block_until_ready(metrics["loss"])
            with span("trainer.record", step=step):
                compiles = self.compiles - compiles
                compile_s = self.compile_s - compile_s
                if compiles and self._stepped:
                    log.warning("%d compile(s) at step %d (%.2fs)", compiles,
                                step, compile_s)
                self._stepped = True
                dt = self.watchdog.stop(step, compile_s)
                if compiles and self.attention_paths is None:
                    self.attention_paths = self._count_attention_paths(state, *args)
                    log.info("attention layers a step, by path: %s",
                             self.attention_paths)
                step += 1
                if step % self.options.log_every == 0 or step == total:
                    m = {k: float(jax.device_get(v)) for k, v in metrics.items()}
                    m.update(step=step, sec_per_step=dt, compiles=compiles)
                    self.history.append(m)
                    moe = "".join(f", {k} {m[k]:.4f}" for k in MOE_COUNTERS if k in m)
                    log.info("step %d loss %.4f%s (%.2fs)", step, m["loss"], moe, dt)
            with span("trainer.checkpoint", step=step):
                if self._ckpt_requested:
                    self._ckpt_requested = False
                    log.warning("straggler policy: forcing early checkpoint "
                                "at step %d", step)
                    self.ckpt.save(step, state)
                if step % self.options.ckpt_every == 0 or step == total:
                    self.ckpt.save(step, state)
        with span("trainer.checkpoint", step=step):
            self.ckpt.wait()
        return state
