"""What the training loop does on the host, by name, and what it compiles.

``span(name, **args)`` marks one stretch of the host's work. It is
``jax.profiler.TraceAnnotation``: under a profiler each span lands in the
host plane on the clock of the device planes, with its arguments as stats
beside the bare name; without one it costs well under a microsecond.
``SPANS`` lists the names the trainer gives its stretches of one step, in
loop order; they never nest in each other.

``CompileCounter`` counts the compilations made on the calling thread while
it is open (``with counter:``). One process-wide ``jax.monitoring`` listener,
registered at import, adds each compile event to the counter opened last on
that thread, and to ``counted``, the process's total under any counter.
A compile is one ``backend_compile_duration`` event: JAX records one on a
persistent-cache hit as well as on a miss, and its span encloses the
cache's retrieval. Its seconds are the union of the spans of those events
and of the tracing and lowering that led to it (``COMPILE_TIME_EVENTS``):
a jit called while another is traced records its own trace inside the
outer one, and a sum of their durations would count it twice.

``PathCounter`` counts, while the step is traced, which path each call of
a layer took where the model chooses between implementations in Python
(``take_path(layer, path)``). A call traced inside ``repeated(n)``, as the
decoder's layer scan traces its one body for n layers, counts n times.
"""

from __future__ import annotations

import collections
import contextlib
import math
import threading

import jax
from jax import monitoring

SPANS = ("trainer.control", "trainer.input", "trainer.shard_batch",
         "trainer.dispatch", "trainer.wait", "trainer.record",
         "trainer.checkpoint")

span = jax.profiler.TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_TIME_EVENTS = (COMPILE_EVENT,
                       "/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration")


class CompileCounter:
    """Compiles and their seconds, cumulative over every stretch in which
    the counter was open on the thread that opened it."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self._spans: list[tuple[float, float]] = []   # disjoint, in order

    def add(self, event: str, start: float, end: float) -> None:
        """Events arrive as they end, so the spans already held that start
        inside this one are nested in it: it replaces them."""
        self.compiles += event == COMPILE_EVENT
        i = len(self._spans)
        while i and self._spans[i - 1][0] >= start:
            i -= 1
        self.seconds += (end - start) - sum(e - s for s, e in self._spans[i:])
        del self._spans[i:]
        self._spans.append((start, end))

    def __enter__(self):
        _stack("open").append(self)
        return self

    def __exit__(self, *exc):
        _stack("open").pop()


_local = threading.local()
counted = CompileCounter()


def _stack(name: str) -> list:
    """This thread's list ``name``: open counters, or ``repeated`` counts."""
    if not hasattr(_local, name):
        setattr(_local, name, [])
    return getattr(_local, name)


def _on_span(event: str, start: float, end: float, **kwargs) -> None:
    if event not in COMPILE_TIME_EVENTS:
        return
    stack = _stack("open")
    if stack:
        stack[-1].add(event, start, end)
        counted.add(event, start, end)


monitoring.register_event_time_span_listener(_on_span)


class PathCounter:
    """Calls of each layer by the path they took, cumulative over every
    trace made while the counter was open on the thread that opened it:
    ``counts[layer, path]``."""

    def __init__(self):
        self.counts: collections.Counter = collections.Counter()

    def __enter__(self):
        _stack("paths").append(self)
        return self

    def __exit__(self, *exc):
        _stack("paths").pop()


def take_path(layer: str, path: str) -> None:
    """One traced call of ``layer`` took ``path``: counted by the counter
    opened last on this thread, as many times as the enclosing
    ``repeated`` scopes run it."""
    stack = _stack("paths")
    if stack:
        stack[-1].counts[layer, path] += math.prod(_stack("repeats"))


@contextlib.contextmanager
def repeated(n: int):
    """What is traced inside runs ``n`` times a call (a scan's body)."""
    reps = _stack("repeats")
    reps.append(n)
    try:
        yield
    finally:
        reps.pop()
