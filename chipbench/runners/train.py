"""Training cells: the program's ``Trainer`` driven by the benchmark's feed.

Set-up builds one trainer, gives it the seed's weights (made by the
benchmark, not by the program), and drives it through its first steps with
the window's own call (``Trainer.run``) and feed. Those steps compile the
step and are the ones the reference follows. Then the same trainer runs on
into the measured window, which the feed's clock closes by raising
``WindowClosed`` from the trainer's next call for data. No checkpoint is
written: the trainer's checkpointer is replaced by one that keeps nothing.

What the program gives back for the comparison with the reference:
the loss of each of the first steps (``Trainer.history``), the norms of the
first clipped gradient per leaf (from the first Adam moment after one step:
``m / (1 - b1)``), and the norms of each leaf's change after the last of
those steps.
"""

from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

from chipbench import compare, reference, scopes, trace as trace_mod
from chipbench.feed import Feed
from chipbench.reference.common import Optim, leaf_norms, seed_key


class WindowClosed(Exception):
    """Raised from the feed to end the trainer's run."""


class _NoCheckpoint:
    def steps(self):
        return []

    def save(self, step, tree, blocking=False):
        pass

    def wait(self):
        pass


class Clock:
    """Called by the feed on every call for data. The first ``warmup``
    calls pass; the next starts the window; every call after it closes one
    step. The first call at or after ``seconds`` closes the window. With
    ``trace_steps`` it then starts the profiler and lets a lead-in step and
    ``trace_steps`` more run before it stops the profiler and raises."""

    def __init__(self, warmup: int, seconds: float, trace_steps: int = 0,
                 trace_dir: str | None = None):
        self.warmup, self.seconds = warmup, seconds
        self.trace_steps, self.trace_dir = trace_steps, trace_dir
        self.calls = 0
        self.times: list[float] = []
        self.closed = False
        self.tracing = False
        self._traced = 0

    def __call__(self, step):
        now = time.perf_counter()
        self.calls += 1
        if self.calls <= self.warmup:
            return
        if not self.closed:
            self.times.append(now)
            if now - self.times[0] < self.seconds:
                return
            self.closed = True
            if not self.trace_steps:
                raise WindowClosed
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.tracing = True
            return
        self._traced += 1
        if self._traced > self.trace_steps:
            self.tracing = False
            jax.profiler.stop_trace()
            raise WindowClosed

    @property
    def steps(self) -> int:
        return len(self.times) - 1

    @property
    def seconds_run(self) -> float:
        return self.times[-1] - self.times[0]


def vocab_padding(ref_shapes, program_shapes, vocab: int):
    """Per leaf, the ``jnp.pad`` widths that turn the reference's leaf into
    the program's: none where the shapes agree, zero rows after the
    ``vocab`` ids where they differ along one axis of ``vocab`` entries
    alone (the program pads its vocabulary). Any other difference raises."""
    if jax.tree.structure(ref_shapes) != jax.tree.structure(program_shapes):
        raise ValueError("the reference's weights are not named and nested as the "
                         f"program's parameters: {jax.tree.structure(ref_shapes)} "
                         f"against {jax.tree.structure(program_shapes)}")

    def widths(path, r, p):
        r, p = r.shape, p.shape
        differ = [a for a, b in zip(r, p) if a != b]
        if len(r) == len(p) and differ in ([], [vocab]) and all(a <= b for a, b in zip(r, p)):
            return tuple((0, b - a) for a, b in zip(r, p))
        raise ValueError(f"{jax.tree_util.keystr(path)}: the reference's leaf is "
                         f"{r}, the program's {p}; they may differ along the "
                         f"vocabulary axis ({vocab} ids) alone")

    return jax.tree_util.tree_map_with_path(widths, ref_shapes, program_shapes)


class Job:
    """The program's trainer for one cell, built once; seeds swap in."""

    def __init__(self, spec: dict, devices):
        from repro.configs.base import TrainConfig
        from repro.launch.mesh import make_mesh
        from repro.train import Trainer, TrainerOptions
        from repro.train.train_step import abstract_train_state

        self.cfg_file, self.traffic = spec["config_file"], spec["traffic_file"]
        self.ref = reference.load(self.cfg_file)
        self.arch = self.ref.Arch.from_config(self.cfg_file)
        self.optim = Optim.from_traffic(self.traffic)
        self.devices = devices
        self.cfg = self.ref.program_config(self.cfg_file)
        self.tc = TrainConfig(**self.traffic["train_config"])
        dims = self.traffic.get("mesh")
        self.mesh = make_mesh(tuple(dims)) if dims else None
        self.feed = Feed(self.traffic, self.cfg.vocab_size, seed=0)
        self._tmp = tempfile.TemporaryDirectory(prefix="chipbench_ckpt_")
        with self.context():
            self.trainer = Trainer(self.cfg, self.tc, self.feed, mesh=self.mesh,
                                   options=TrainerOptions(ckpt_dir=self._tmp.name))
        self.trainer.ckpt = _NoCheckpoint()
        self.abstract = abstract_train_state(self.cfg, self.tc)
        self._build = self._state_maker()

    @contextlib.contextmanager
    def context(self):
        if self.mesh is None:
            yield
            return
        from repro.parallel import context as pctx

        with jax.set_mesh(self.mesh):
            pctx.set_mesh(self.mesh)
            try:
                yield
            finally:
                pctx.set_mesh(None)

    def _state_maker(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train.train_step import train_state_specs

        abstract, arch, init = self.abstract, self.arch, self.ref.init_weights
        pad = vocab_padding(jax.eval_shape(lambda k: init(arch, k), seed_key(0)),
                            abstract["params"], self.cfg_file["vocab_size"])

        def build(key):
            w = init(arch, key)
            params = jax.tree.map(lambda x, p, s: jnp.pad(x, p).astype(s.dtype),
                                  w, pad, abstract["params"])
            zeros = lambda t: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), t)
            return {"params": params,
                    "opt": {"m": zeros(abstract["opt"]["m"]),
                            "v": zeros(abstract["opt"]["v"]),
                            "count": jnp.zeros((), jnp.int32)},
                    "step": jnp.zeros((), jnp.int32)}

        made = jax.eval_shape(build, seed_key(0))
        if (jax.tree.structure(made) != jax.tree.structure(abstract)
                or jax.tree.leaves(made) != jax.tree.leaves(abstract)):
            raise ValueError("the program's train state is not the params, "
                             "Adam moments and counters the benchmark builds")
        shardings = None
        if self.mesh is not None:
            specs = train_state_specs(abstract, self.mesh, self.tc.fsdp)
            shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                                     is_leaf=lambda x: isinstance(x, P))
        return jax.jit(build, out_shardings=shardings)

    def _hand(self, state):
        box = [state]
        self.trainer.init_or_restore = box.pop

    def first_steps(self, seed: int, n: int):
        """The seed's state through its first ``n`` steps of the trainer.
        Returns (state, readings, seconds of the first step)."""
        self.feed.reseed(seed)
        with self.context():
            state = self._build(seed_key(seed))
            losses, grad = [], None
            t0 = time.perf_counter()
            for k in range(1, n + 1):
                self._hand(state)
                state = self.trainer.run(k)
                if k == 1:
                    first_step_s = time.perf_counter() - t0
                    grad = [float(x) / (1 - self.tc.b1)
                            for x in leaf_norms(state["opt"]["m"])]
                losses.append(float(self.trainer.history[-1]["loss"]))
            delta = self.ref.delta_norms(state["params"], self.arch, seed)
        return state, {"loss": losses, "grad": grad, "delta": delta}, first_step_s

    def window(self, state, clock: Clock) -> None:
        """Run the trainer on from ``state`` until the clock closes."""
        self.feed.on_call = clock
        self._hand(state)
        del state
        # what set-up left behind is never garbage the window should walk
        gc.collect()
        gc.freeze()
        try:
            with self.context():
                self.trainer.run(10**12)
        except WindowClosed:
            pass
        finally:
            self.feed.on_call = None
            if clock.tracing:
                jax.profiler.stop_trace()
            gc.unfreeze()
        gc.collect()

    def blocks(self, step: int, rows: slice = slice(None)):
        """Step ``step``'s rows as [chips, rows per chip, S] blocks: one
        block is what one chip of the mesh is given."""
        b = self.feed.make(step)
        chips = self.traffic["chips"]
        return tuple(x.reshape(chips, -1, x.shape[-1])[:, rows] for x in
                     (b["tokens"], b["labels"]))

    def follow(self, seed: int, n: int, prec: str = "f32", rows=slice(None),
               blocks: slice = slice(None)):
        """The reference over the seed's first ``n`` steps."""
        batches = [tuple(x[blocks] for x in self.blocks(k, rows)) for k in range(n)]
        nb = batches[0][0].shape[0]
        nd = max(d for d in range(1, len(self.devices) + 1) if nb % d == 0)
        return self.ref.follow(self.arch, self.optim, seed, batches,
                               self.devices[:nd], prec)

    def close(self):
        self._tmp.cleanup()


def read_trace(path: str, steps: int) -> dict:
    """A traced window's device time (``trace.reduce``) and its device time
    by the program's named scopes with the host's spans (``scopes.reduce``),
    in one record."""
    return {**scopes.reduce(path, steps), **trace_mod.reduce(path, steps)}


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return None if None in peaks else max(peaks)


def run(spec: dict, seed: int, seconds: float, trace: bool, devices,
        t_start: float, log) -> dict:
    traffic = spec["traffic_file"]
    n_check = traffic["check_steps"]
    job = Job(spec, devices)
    state, prog, first_step_s = job.first_steps(seed, n_check)
    log(f"first {n_check} steps: loss {prog['loss']}; first step "
        f"{first_step_s:.3f}s (compile or cache load, and one step)")
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    clock = Clock(traffic["warmup_steps"], seconds,
                  traffic["trace_steps"] if trace else 0, trace_dir)
    job.window(state, clock)
    del state
    mem = peak_bytes(devices)
    log(f"window: {clock.steps} steps in {clock.seconds_run:.4f}s; "
        f"{job.feed.tokens_per_step} tokens a step; peak bytes in use {mem}")
    step_ms = [1e3 * (b - a) for a, b in zip(clock.times, clock.times[1:])]
    rec = {
        "setup_s": clock.times[0] - t_start,
        "first_step_s": first_step_s,
        "step_ms": step_ms,
        "tokens_per_s": clock.steps * job.feed.tokens_per_step / clock.seconds_run,
        "chips": len(devices),
        "seq_len": traffic["seq_len"],
        "config_file": spec["config_file"],
        "device_kind": devices[0].device_kind,
        "memory_peak_bytes": mem,
        "steps_run": n_check + traffic["warmup_steps"] + clock.steps
        + (traffic["trace_steps"] + 1 if trace else 0),
        "losses": [h["loss"] for h in job.trainer.history],
        "trace": None,
    }
    if trace:
        rec["trace"] = read_trace(trace_mod.find_xplane(trace_dir), traffic["trace_steps"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    live = sum(x.nbytes for x in jax.live_arrays())
    log(f"bytes still live on the devices before the reference: {live}")
    t = time.perf_counter()
    reference = job.follow(seed, n_check)
    log(f"reference: loss {reference['loss']} in {time.perf_counter() - t:.1f}s")
    rec["numbers"] = compare.numbers(prog, reference)
    job.close()
    return rec
