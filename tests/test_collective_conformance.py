"""Property-based differential conformance harness for the scheduled
collective algebra (DESIGN.md §11).

Three layers, each independent of the machinery it checks:

1. **Schedule semantics vs a plain-Python oracle** — every
   ``(collective, n, m, w, max_hops, rwa)`` cell builds a schedule and
   replays it through :func:`interpret_schedule`, a deliberately naive
   per-object interpreter (dict-of-sets, one row at a time) that shares no
   code with the vectorized data-flow in ``repro.core.wrht``.  The oracle's
   end state must match the collective's semantic spec AND the repo's own
   vectorized simulation, bit for bit.
2. **Payload accounting** — chunked collectives carry exactly ``d/n`` per
   transfer, tree collectives the constant full ``d``; wavelength counts
   stay within ``w`` and every lightpath within the hop budget.
3. **Device-twin equivalence** — each scheduled collective's shard_map body
   (``repro.core.collectives``) runs on 8 simulated devices and must
   reproduce the same ownership semantics (device ``i`` owns chunk ``i``,
   broadcast fills every device with the root's value, the all-to-all is a
   message transpose).

The hypothesis sweep widens layer 1; the ``deep`` lane re-runs it with
``REPRO_DEEP_EXAMPLES`` (default 300) examples on the scheduled CI job.
"""

import os
import textwrap

import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.core import compose, wrht
from repro.core.topology import FailureMask, Ring
from repro.core.wavelength import (
    FailedResourceError,
    InsertionLossError,
    WavelengthConflictError,
    validate_no_conflicts,
)

ALL_COLLECTIVES = tuple(wrht.COLLECTIVES)


# ---------------------------------------------------------------------------
# layer 1: the independent oracle
# ---------------------------------------------------------------------------

def interpret_schedule(sched: wrht.WRHTSchedule) -> dict:
    """Naive per-row replay: ``state[(node, chunk)]`` is the set of original
    contributions held in node's partial of that chunk (chunk 0 stands for
    the whole vector on unchunked collectives).  Reads precede writes within
    a step; ``broadcast`` steps overwrite, everything else accumulates."""
    n = sched.n
    chunked = wrht.COLLECTIVES[sched.collective].chunked
    chunks_axis = range(n) if chunked else (0,)
    state = {}
    for v in range(n):
        for c in chunks_axis:
            if sched.collective == "all_gather":
                state[(v, c)] = {v} if c == v else set()
            else:
                state[(v, c)] = {v}
    for step in sched.steps:
        b = step.transfers
        incoming: dict[tuple[int, int], set] = {}
        for row in range(len(b)):
            src, dst = int(b.src[row]), int(b.dst[row])
            c = int(step.chunks[row]) if step.chunks is not None else 0
            incoming.setdefault((dst, c), set()).update(state[(src, c)])
        for key, vals in incoming.items():
            if step.kind == "broadcast":
                state[key] = set(vals)
            else:
                state[key] |= vals
    return state


def check_cell(collective: str, n: int, m: int | None, w: int,
               max_hops: int | None, rwa: str, d: float = 1e6,
               failures: FailureMask | None = None) -> None:
    degraded = failures is not None and not failures.empty
    try:
        sched = wrht.build_collective_schedule(
            collective, n, w, d, m=m, max_hops=max_hops, rwa=rwa,
            failures=failures)
    except wrht.DegradedInfeasibleError:
        # the uniform infeasibility signal of degraded building — a valid
        # outcome under a mask (severed ring, no surviving λ, ...), never
        # valid on a healthy fabric
        assert degraded
        return
    except WavelengthConflictError:
        # only the single-step all-to-all can run out of wavelengths —
        # either at the ⌈n²/8⌉ budget precheck or in First Fit itself
        # (the bound is necessary, not sufficient for a greedy RWA)
        assert collective == "alltoall" and not degraded
        return
    except InsertionLossError:
        assert collective == "alltoall" and max_hops is not None
        assert not degraded
        assert n // 2 > max_hops
        return
    check_schedule(sched, collective, n, w, max_hops=max_hops, d=d,
                   failures=failures)


def check_schedule(sched: wrht.WRHTSchedule, collective: str, n: int, w: int,
                   max_hops: int | None = None, d: float = 1e6,
                   failures: FailureMask | None = None) -> None:
    """Layers 1+2 against an already-built schedule — factored out of
    :func:`check_cell` so composed constituent views
    (:meth:`~repro.core.compose.ComposedSchedule.constituent_view`) run
    through the *identical* oracle machinery as plain schedules."""
    spec = wrht.COLLECTIVES[collective]
    degraded = failures is not None and not failures.empty

    # ---- structural: RWA + hop budget + wavelength budget + failure mask
    ring = Ring(max(n, 2), w)
    for step in sched.steps:
        validate_no_conflicts(step.transfers, ring.n, w, max_hops=max_hops,
                              failures=failures)
        assert step.wavelengths <= w
    if degraded:
        assert sched.failures == failures

    # ---- payload accounting per the spec ----
    want_bits = d / n if spec.chunked else d
    for step in sched.steps:
        if len(step.transfers):
            assert (step.transfers.bits == want_bits).all(), (
                collective, n, step.kind)

    # ---- semantics: oracle end state matches the spec ----
    state = interpret_schedule(sched)
    full = set(range(n))
    if collective == "allreduce":
        assert all(state[(v, 0)] == full for v in range(n))
    elif collective == "broadcast":
        root = wrht.broadcast_root(sched)
        if n > 1:
            assert all(state[(v, 0)] == {root} for v in range(n))
    elif collective == "reduce_scatter":
        # node i owns the complete reduction of chunk i
        assert all(state[(v, v)] == full for v in range(n))
    elif collective == "all_gather":
        # every node holds every chunk, each carrying exactly its originator
        assert all(state[(v, c)] == {c}
                   for v in range(n) for c in range(n))
    else:  # alltoall: every ordered pair exchanged exactly once
        if n > 1:
            b = sched.steps[0].transfers
            pairs = sorted(zip(b.src.tolist(), b.dst.tolist()))
            assert pairs == sorted((i, j) for i in range(n) for j in range(n)
                                   if i != j)
            assert np.array_equal(sched.steps[0].chunks, b.dst)

    # ---- differential: the repo's vectorized data-flow agrees row-for-row
    if collective in ("allreduce", "broadcast"):
        got = wrht.simulate_contributions(sched)
        assert got == [frozenset(state[(v, 0)]) for v in range(n)]
    elif collective in ("reduce_scatter", "all_gather"):
        got = wrht.simulate_chunk_contributions(sched)
        assert got == [[frozenset(state[(v, c)]) for c in range(n)]
                       for v in range(n)]


# deterministic sweep: spec-aware axes (the fan-out only exists for trees,
# the reference RWA is spot-checked, hop budgets exercise relays)
def _cells():
    cells = []
    for coll in ALL_COLLECTIVES:
        tree = wrht.COLLECTIVES[coll].tree
        for n in (1, 2, 3, 5, 8, 13, 16):
            for w in (2, 8, 64):
                for m in ((None, 2, 3) if tree else (None,)):
                    cells.append((coll, n, m, w, None, "fast"))
        cells.append((coll, 33, 3 if tree else None, 8, None, "fast"))
        cells.append((coll, 64, None, 8, None, "fast"))
        # hop budgets: relays for the trees, reach checks for the mesh
        for hops in (2, 5):
            cells.append((coll, 16, None, 8, hops, "fast"))
            cells.append((coll, 33, None, 64, hops, "fast"))
        # the reference (per-object greedy) RWA must agree
        cells.append((coll, 13, None, 4, None, "reference"))
        cells.append((coll, 16, 3 if tree else None, 64, 3, "reference"))
    return cells


@pytest.mark.parametrize("coll", ALL_COLLECTIVES)
def test_conformance_sweep(coll):
    for cell in _cells():
        if cell[0] == coll:
            check_cell(*cell)


def test_reduce_scatter_then_all_gather_composes_to_allreduce():
    """The ZeRO-style decomposition: chain the RS oracle's end state into
    the AG oracle — every node must end with the full reduction of every
    chunk, i.e. the composition is semantically an all-reduce."""
    n, w = 13, 8
    rs = wrht.build_collective_schedule("reduce_scatter", n, w, 1e6)
    ag = wrht.build_collective_schedule("all_gather", n, w, 1e6)
    state = interpret_schedule(rs)
    # hand the owned shards to the all-gather as its initial ownership
    ag_state = {(v, c): set() for v in range(n) for c in range(n)}
    for v in range(n):
        ag_state[(v, v)] = set(state[(v, v)])
    for step in ag.steps:
        b = step.transfers
        incoming = {}
        for row in range(len(b)):
            src, dst = int(b.src[row]), int(b.dst[row])
            c = int(step.chunks[row])
            incoming.setdefault((dst, c), set()).update(ag_state[(src, c)])
        for key, vals in incoming.items():
            ag_state[key] |= vals
    full = set(range(n))
    assert all(ag_state[(v, c)] == full for v in range(n) for c in range(n))


def test_validate_schedule_catches_semantic_violations():
    """The in-repo validator must reject a schedule whose data-flow breaks
    its collective's spec (differential guard on the validator itself)."""
    sched = wrht.build_collective_schedule("reduce_scatter", 8, 8, 1e6)
    sched.steps = sched.steps[:-1]          # drop the last ring step
    with pytest.raises(AssertionError, match="reduce-scatter semantics"):
        wrht.validate_schedule(sched)

    sched = wrht.build_collective_schedule("all_gather", 8, 8, 1e6)
    sched.steps = sched.steps[1:]
    with pytest.raises(AssertionError, match="all-gather semantics"):
        wrht.validate_schedule(sched)

    sched = wrht.build_collective_schedule("broadcast", 9, 4, 1e6)
    sched.steps = sched.steps[:-1]
    with pytest.raises(AssertionError, match="broadcast semantics"):
        wrht.validate_schedule(sched)

    sched = wrht.build_collective_schedule("alltoall", 8, 64, 1e6)
    batch = sched.steps[0].transfers
    sched.steps[0] = wrht.Step(
        "alltoall", 0,
        type(batch)(batch.src[:-1], batch.dst[:-1], batch.direction[:-1],
                    batch.bits[:-1], batch.wavelength[:-1]),
        chunks=sched.steps[0].chunks[:-1])
    with pytest.raises(AssertionError, match="all-to-all semantics"):
        wrht.validate_schedule(sched)


def test_collective_steps_closed_forms():
    for n in (2, 5, 16, 100):
        assert wrht.collective_steps("reduce_scatter", n) == n - 1
        assert wrht.collective_steps("all_gather", n) == n - 1
        assert wrht.collective_steps("alltoall", n) == 1
        for m in (2, 3, 5):
            sched = wrht.build_collective_schedule("broadcast", n, 64, 1.0,
                                                   m=m)
            assert sched.num_steps == wrht.collective_steps("broadcast", n,
                                                            m=m)
    assert wrht.collective_steps("allreduce", 1) == 0


def test_plan_field_normalization():
    """Non-tree collectives must not fragment plan-cache keys on (m, a2a)."""
    assert wrht.collective_plan_fields("reduce_scatter", 7, False) == (None, True)
    assert wrht.collective_plan_fields("alltoall", 3, False) == (None, True)
    assert wrht.collective_plan_fields("broadcast", 7, True) == (7, False)
    assert wrht.collective_plan_fields("allreduce", 7, False) == (7, False)
    with pytest.raises(ValueError, match="unknown collective"):
        wrht.coerce_collective("scatter_gather")


# ---------------------------------------------------------------------------
# failure-mask lane: degraded schedules must satisfy the same oracles
# ---------------------------------------------------------------------------
# Degraded building only *re-routes* (direction flips, O/E/O relay detours)
# and *shrinks budgets* — it never changes what data moves where, so every
# semantic oracle above applies unchanged.  check_cell additionally runs the
# structural validator WITH the mask, proving no schedule touches a dead
# arc/λ/transceiver, and accepts DegradedInfeasibleError as the one valid
# alternative outcome.

def _failure_masks(n: int) -> list[FailureMask]:
    return [
        # one dead CW span
        FailureMask(dead_segments=((0, 1),)),
        # one dead λ at one node
        FailureMask(dead_wavelengths=((n // 2, 0),)),
        # the ISSUE's acceptance cell: ≥1 dead arc AND ≥1 dead λ (plus a
        # dead transceiver for good measure)
        FailureMask(dead_segments=((1, n // 3),),
                    dead_wavelengths=((0, 0),),
                    dead_transceivers=((n // 2, 1),)),
        # both fibers cut at one span: the ring degenerates to a line —
        # still routable (every pair has a one-sided path)
        FailureMask(dead_segments=((0, 2), (1, 2))),
        # ring severed at two distinct spans on both lanes: some pairs are
        # unreachable — builders must raise DegradedInfeasibleError, which
        # check_cell accepts (and would reject on a healthy fabric)
        FailureMask(dead_segments=((0, 0), (1, 0), (0, n // 2), (1, n // 2))),
    ]


@pytest.mark.parametrize("coll", ALL_COLLECTIVES)
def test_conformance_failure_masks(coll):
    for n in (4, 5, 8, 16):
        for mask in _failure_masks(n):
            check_cell(coll, n, None, 8, None, "fast", failures=mask)
            check_cell(coll, n, None, 8, 3, "fast", failures=mask)
    # tree fan-outs and the reference RWA under the combined mask
    mask = _failure_masks(16)[2]
    if wrht.COLLECTIVES[coll].tree:
        check_cell(coll, 16, 3, 8, None, "fast", failures=mask)
    check_cell(coll, 13, None, 4, None, "reference", failures=mask)


def test_empty_mask_is_healthy():
    """FailureMask.empty must normalize to the healthy build bit-for-bit."""
    healthy = wrht.build_collective_schedule("allreduce", 16, 8, 1e6)
    masked = wrht.build_collective_schedule("allreduce", 16, 8, 1e6,
                                            failures=FailureMask())
    assert masked.failures is None
    assert wrht.simulate_contributions(masked) == \
        wrht.simulate_contributions(healthy)
    assert masked.num_steps == healthy.num_steps


def test_validator_rejects_failed_resources():
    """Negative lane: a healthy schedule run against a mask that kills a
    resource it uses must trip FailedResourceError — for each of the three
    resource kinds (arc, λ, transceiver)."""
    n = w = 8
    sched = wrht.build_collective_schedule("allreduce", n, w, 1e6)
    b = sched.steps[0].transfers
    assert len(b), "first step unexpectedly empty"
    lane, start, _hops = b.arcs(n)
    # covered directed span of row 0
    dead_arc = FailureMask(dead_segments=((int(lane[0]), int(start[0]) % n),))
    with pytest.raises(FailedResourceError, match="dead fiber span"):
        validate_no_conflicts(b, n, w, failures=dead_arc)
    # the λ row 0 adds at its source
    dead_lam = FailureMask(
        dead_wavelengths=((int(b.src[0]), int(b.wavelength[0])),))
    with pytest.raises(FailedResourceError, match="dead wavelength"):
        validate_no_conflicts(b, n, w, failures=dead_lam)
    # row 0's transmit-side transceiver
    dead_trx = FailureMask(dead_transceivers=((int(b.src[0]), int(lane[0])),))
    with pytest.raises(FailedResourceError, match="dead transceiver"):
        validate_no_conflicts(b, n, w, failures=dead_trx)
    # the degraded builder's own output never trips any of these
    degraded = wrht.build_collective_schedule("allreduce", n, w, 1e6,
                                              failures=dead_arc)
    for step in degraded.steps:
        validate_no_conflicts(step.transfers, n, w, failures=dead_arc)


# ---------------------------------------------------------------------------
# composed lane: interleaved schedules still satisfy every constituent oracle
# ---------------------------------------------------------------------------
# The composer (DESIGN.md §13) re-assigns wavelengths on fused slots but must
# never change what data moves where: each constituent view of a composed
# pipeline is run through the *same* check_schedule machinery as a plain
# build — structural RWA under the mask, payload accounting, the naive oracle
# AND the vectorized differential, per collective.

def check_composed_cell(start: str, n: int, w: int, depth: int,
                        max_hops: int | None = None, d: float = 1e6,
                        failures: FailureMask | None = None,
                        offsets: tuple | None = None) -> None:
    degraded = failures is not None and not failures.empty
    colls = compose.pipeline_collectives(start, depth)
    try:
        composed = compose.build_pipeline_schedule(
            start, n, w, d, depth, max_hops=max_hops, failures=failures,
            offsets=offsets)
    except wrht.DegradedInfeasibleError:
        assert degraded
        return
    except WavelengthConflictError:
        assert "alltoall" in colls and not degraded
        return
    except InsertionLossError:
        assert "alltoall" in colls and max_hops is not None
        assert not degraded
        return
    compose.validate_composed(composed)
    assert composed.depth == depth
    assert composed.num_steps <= composed.serial_steps
    for j, coll in enumerate(colls):
        check_schedule(composed.constituent_view(j), coll, n, w,
                       max_hops=max_hops, d=d, failures=failures)


@pytest.mark.parametrize("start", ALL_COLLECTIVES)
def test_composed_conformance_sweep(start):
    for n in (2, 3, 5, 8, 16):
        for w in (1, 2, 8, 64):
            for depth in (1, 2, 3, 4):
                check_composed_cell(start, n, w, depth)
    # staggered starts (the bucket pipeline's ramp-up shape)
    check_composed_cell(start, 8, 8, 3, offsets=(0, 1, 2))
    # hop-budgeted fusion
    check_composed_cell(start, 16, 8, 2, max_hops=3)


def test_composed_heterogeneous_mix_conformance():
    """A mix the partner map never produces — a reduce-scatter with a
    broadcast prefetch riding the same ring — still satisfies both
    constituent oracles after interleaving."""
    n, w, d = 13, 8, 1e6
    rs = wrht.build_collective_schedule("reduce_scatter", n, w, d)
    bc = wrht.build_collective_schedule("broadcast", n, w, d)
    composed = compose.compose_schedules([rs, bc])
    compose.validate_composed(composed)
    check_schedule(composed.constituent_view(0), "reduce_scatter", n, w, d=d)
    check_schedule(composed.constituent_view(1), "broadcast", n, w, d=d)


@pytest.mark.parametrize("start", ("reduce_scatter", "all_gather",
                                   "broadcast"))
def test_composed_conformance_failure_masks(start):
    for n in (4, 8, 16):
        for mask in _failure_masks(n):
            check_composed_cell(start, n, 8, 2, failures=mask)
    check_composed_cell(start, 16, 8, 3,
                        failures=_failure_masks(16)[2])


# ---------------------------------------------------------------------------
# hypothesis sweep (layer 1, randomized) — fast lane + scheduled deep lane
# ---------------------------------------------------------------------------

DEEP_EXAMPLES = int(os.environ.get("REPRO_DEEP_EXAMPLES", "300"))

if HAVE_HYPOTHESIS:
    _strategy = dict(
        coll=st.sampled_from(ALL_COLLECTIVES),
        n=st.integers(min_value=1, max_value=33),
        m=st.one_of(st.none(), st.integers(min_value=2, max_value=9)),
        w=st.sampled_from([1, 2, 4, 8, 64]),
        max_hops=st.one_of(st.none(), st.integers(min_value=1, max_value=8)),
        rwa=st.sampled_from(["fast", "reference"]),
    )

    @settings(max_examples=25, deadline=None)
    @given(**_strategy)
    def test_conformance_hypothesis(coll, n, m, w, max_hops, rwa):
        check_cell(coll, n, m, w, max_hops, rwa)

    @pytest.mark.deep
    @settings(max_examples=DEEP_EXAMPLES, deadline=None)
    @given(**_strategy)
    def test_conformance_hypothesis_deep(coll, n, m, w, max_hops, rwa):
        check_cell(coll, n, m, w, max_hops, rwa)

    # randomized failure masks: raw draws are reduced mod (n, w) inside the
    # test so the strategy stays independent of the drawn cell size
    _fail_strategy = dict(
        coll=st.sampled_from(ALL_COLLECTIVES),
        n=st.integers(min_value=2, max_value=33),
        w=st.sampled_from([2, 4, 8, 64]),
        max_hops=st.one_of(st.none(), st.integers(min_value=2, max_value=8)),
        segs=st.lists(st.tuples(st.integers(0, 1), st.integers(0, 99)),
                      max_size=3),
        lams=st.lists(st.tuples(st.integers(0, 99), st.integers(0, 63)),
                      max_size=3),
        trx=st.lists(st.tuples(st.integers(0, 99), st.integers(0, 1)),
                     max_size=2),
    )

    def _mask_cell(coll, n, w, max_hops, segs, lams, trx):
        mask = FailureMask(
            dead_segments=tuple((l, s % n) for l, s in segs),
            dead_wavelengths=tuple((v % n, lam % w) for v, lam in lams),
            dead_transceivers=tuple((v % n, l) for v, l in trx))
        check_cell(coll, n, None, w, max_hops, "fast", failures=mask)

    @settings(max_examples=25, deadline=None)
    @given(**_fail_strategy)
    def test_conformance_failure_hypothesis(coll, n, w, max_hops, segs,
                                            lams, trx):
        _mask_cell(coll, n, w, max_hops, segs, lams, trx)

    @pytest.mark.deep
    @settings(max_examples=DEEP_EXAMPLES, deadline=None)
    @given(**_fail_strategy)
    def test_conformance_failure_hypothesis_deep(coll, n, w, max_hops, segs,
                                                 lams, trx):
        _mask_cell(coll, n, w, max_hops, segs, lams, trx)

    # randomized composed pipelines: (start, n, w, depth, stagger) cells,
    # each constituent view re-checked by its own oracle after interleaving
    _composed_strategy = dict(
        start=st.sampled_from(ALL_COLLECTIVES),
        n=st.integers(min_value=2, max_value=17),
        w=st.sampled_from([1, 2, 4, 8, 64]),
        depth=st.integers(min_value=1, max_value=4),
        stagger=st.booleans(),
    )

    def _composed_cell(start, n, w, depth, stagger):
        offsets = tuple(range(depth)) if stagger else None
        check_composed_cell(start, n, w, depth, offsets=offsets)

    @settings(max_examples=25, deadline=None)
    @given(**_composed_strategy)
    def test_composed_conformance_hypothesis(start, n, w, depth, stagger):
        _composed_cell(start, n, w, depth, stagger)

    @pytest.mark.deep
    @settings(max_examples=DEEP_EXAMPLES, deadline=None)
    @given(**_composed_strategy)
    def test_composed_conformance_hypothesis_deep(start, n, w, depth,
                                                  stagger):
        _composed_cell(start, n, w, depth, stagger)
else:  # pragma: no cover - exercised only without hypothesis installed
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_conformance_hypothesis():
        pass


# ---------------------------------------------------------------------------
# layer 3: device-level shard_map twins on 8 simulated devices
# ---------------------------------------------------------------------------

TWINS = """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import collectives as C

def smap(body):
    return jax.shard_map(body, mesh=mesh, in_specs=P('ax'), out_specs=P('ax'),
                         axis_names={'ax'})

S = 8
mesh = Mesh(np.array(jax.devices()).reshape(S,), ('ax',))
rng = np.random.default_rng(0)

def run(body):
    return np.asarray(jax.jit(smap(body))(x))

# 131: odd, the pad paths; 3072: chunks of 3 x 128, the lane-layout view
for n in (131, S * 128 * 3):
    x = jnp.asarray(rng.normal(size=(S, n)).astype(np.float32))
    xs = np.asarray(x)
    total = xs.sum(0)
    pad = (-n) % S
    padded = np.concatenate([total, np.zeros(pad, np.float32)])
    shards = padded.reshape(S, -1)

    # reduce-scatter twins: device i ends owning fully-reduced chunk i — the
    # exact ownership map of the scheduled reduce_scatter collective
    for name, fn in (('ring', C.reduce_scatter_ring),
                     ('alltoall', C.reduce_scatter_alltoall)):
        got = run(lambda st, fn=fn: fn(st[0], 'ax', S)[None])
        assert np.abs(got - shards).max() < 1e-4, ('rs', name, n)

    # all-gather twins: start from the owned shard, end with the
    # concatenation
    for name, fn in (('ring', C.all_gather_ring),
                     ('alltoall', C.all_gather_alltoall)):
        def body(st, fn=fn):
            shard = C.reduce_scatter_ring(st[0], 'ax', S)
            return fn(shard, 'ax', S)[None]
        got = run(body)
        assert np.abs(got - padded[None]).max() < 1e-4, ('ag', name, n)

    # rs+ag composition == psum (the planned_sharded bucket body)
    def rs_ag(st):
        flat = st[0]
        L = flat.shape[0]
        shard = C.reduce_scatter_ring(flat, 'ax', S)
        return C.all_gather_ring(shard, 'ax', S)[:L][None]
    got = run(rs_ag)
    assert np.abs(got - total[None]).max() < 1e-4, n
print('RS_TWINS_OK')
print('AG_TWINS_OK')
print('RS_AG_COMPOSE_OK')

# broadcast twin: every device ends with the root's (device 0) value,
# matching the scheduled broadcast's everyone-holds-exactly-the-root spec
for m in (2, 3, 5):
    got = run(lambda st, m=m: C.broadcast_wrht_tree(st[0], 'ax', S, m=m)[None])
    assert np.abs(got - xs[0][None]).max() == 0.0, m
print('BCAST_TWIN_OK')

# alltoall twin: a message transpose, the device face of the scheduled
# one-step full-mesh exchange
y = jnp.asarray(rng.normal(size=(S, S, 5)).astype(np.float32))
got = np.asarray(jax.jit(smap(lambda st: C.alltoall_ppermute(st[0], 'ax', S)[None]))(y))
assert np.abs(got - np.asarray(y).transpose(1, 0, 2)).max() == 0.0
print('A2A_TWIN_OK')
"""


def test_device_twins_match_scheduled_semantics(subproc):
    out = subproc(TWINS)
    for marker in ("RS_TWINS_OK", "AG_TWINS_OK", "RS_AG_COMPOSE_OK",
                   "BCAST_TWIN_OK", "A2A_TWIN_OK"):
        assert marker in out


PLANNED_SHARDED = """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.configs.base import TrainConfig
from repro.train import train_step as TS

def smap(body, mesh, spec):
    return jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                         axis_names={'data', 'pod'})

mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ('data', 'pod'))
tc = TrainConfig(sync_algorithm="planned_sharded", bucket_bytes=1 << 10)
rng = np.random.default_rng(0)
tree = {k: rng.normal(size=(8, n)).astype(np.float32)
        for k, n in (('a', 37), ('b', 129), ('c', 513))}

plans = TS.plan_gradient_sync(
    jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], jnp.float32),
                 tree),
    tc, mesh, sharded=True)
assert plans.rs_plans and plans.ag_plans
strategies = {p.strategy for pls in plans.rs_plans.values() for p in pls}
assert strategies <= {'flat', 'alltoall'}, strategies

def body(stacked):
    local = jax.tree.map(lambda x: x[0], stacked)
    out, _ = TS.sync_gradients(local, tc, mesh, sync_plans=plans)
    return jax.tree.map(lambda x: x[None], out)

spec = P(('data', 'pod'))
got = jax.jit(smap(body, mesh, spec))(tree)
for k, v in tree.items():
    want = np.asarray(v).mean(axis=0)
    assert np.abs(np.asarray(got[k]) - want[None]).max() < 1e-5, k
print('PLANNED_SHARDED_OK', sorted(strategies))
"""


def test_planned_sharded_sync_equals_mean(subproc):
    """``sync_algorithm="planned_sharded"``'s bucket body (RS down the DP
    axes, AG back up, per-bucket planned strategies) produces exactly the
    DP-mean gradients on a 4×2 device mesh — the device-level face of the
    acceptance criterion (the full train-loop equality runs in
    tests/test_system.py's multi-device E2E)."""
    assert "PLANNED_SHARDED_OK" in subproc(PLANNED_SHARDED)
