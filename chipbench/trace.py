"""Reduction of a profiler trace (``.xplane.pb``) to device time.

Reads the trace with nothing but JAX (``jax.profiler.ProfileData``). On a
TPU each device plane (``/device:TPU:<n>``) has a line ``XLA Ops``, where
every event is one operation with a start and a duration in nanoseconds,
and a control-flow operation (a ``while``) spans the operations of its
body; and a line ``Async XLA Ops``, where an asynchronous operation spans
its start to its done. Host planes hold the benchmark's spans
(``feed.batch``), on the same clock.

The traced window runs from the start of the second ``feed.batch`` span
(the first step after the profiler started is a lead-in and is left out) to
the end of the last device operation. Within it, per device:

- busy time: the union of the operations' intervals;
- collective time: the union of the collective operations' intervals
  (an HLO opcode, or failing that a name, that contains one of
  ``COLLECTIVES``: XLA may name an all-reduce after the JAX primitive, as
  ``psum.3``), synchronous ones and asynchronous ones from start to done;
- exposed collective time: the part of that union in which no other
  operation of the body (a leaf of ``XLA Ops``) runs on the device;
- idle gaps: the stretches between busy intervals, each named by the host
  span that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
FEED_SPAN = "feed.batch"


OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" =", 1)[0].lstrip("%")


def is_collective(event_name: str) -> bool:
    """Whether the operation of this event text is a collective: by its
    opcode (the first ``word(`` after ``=``; a layout's ``T(8,128)`` follows
    no space), else by its name."""
    head, _, rest = event_name.partition(" = ")
    m = OPCODE.search(rest)
    return any(c in (m.group(1) if m else "") or c in head for c in COLLECTIVES)


def union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(u) -> float:
    return float(sum(e - s for s, e in u))


def overlap(a, b) -> float:
    """Length of the overlap of two unions."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def self_times(ops):
    """(name, start, end, self time, is_leaf) of nested operations: an
    operation's self time is its duration less that of the operations it
    spans."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out, stack = [], []
    for name, s, e in ops:
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, e, e - s, True]
        if stack and e <= stack[-1][2]:
            stack[-1][3] -= e - s
            stack[-1][4] = False
        out.append(rec)
        stack.append(rec)
    return out


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {files}")
    return files[0]


def load(path: str):
    """({device: {"ops": [...], "async": [...]}}, host spans) of a trace;
    each operation is (event text, start ns, end ns)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                               for ev in ln.events if ev.duration_ns > 0]
                     for ln in plane.lines}
            devices[int(m.group(1))] = {"ops": lines.get("XLA Ops", []),
                                        "async": lines.get("Async XLA Ops", [])}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in ln.events if ev.duration_ns > 0)
    return {k: devices[k] for k in sorted(devices) if devices[k]["ops"]}, host


def _label(gap, host) -> str:
    s, e = gap
    best, name = 0.0, "no host span"
    for n, hs, he in host:
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, n
    return name


def _clip(intervals, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in intervals if e > lo and s < hi]


def reduce(path: str, steps: int, top: int = 10) -> dict:
    """Device time in the traced window of ``steps`` training steps."""
    devices, host = load(path)
    if not devices:
        raise RuntimeError(f"no device operations in {path}")
    feeds = sorted(s for n, s, _ in host if n == FEED_SPAN)
    if len(feeds) < 2:
        raise RuntimeError(f"{path}: fewer than two {FEED_SPAN} spans")
    lo = feeds[1]
    hi = max(e for d in devices.values() for _, _, e in d["ops"])
    per_dev, self_time = [], defaultdict(float)
    for d in devices.values():
        ops = _clip(d["ops"], lo, hi)
        nested = self_times(ops)
        busy = union((s, e) for _, s, e in ops)
        coll = union([(s, e) for n, s, e in ops if is_collective(n)]
                     + [(s, e) for n, s, e in _clip(d["async"], lo, hi) if is_collective(n)])
        compute = union((s, e) for n, s, e, _, leaf in nested
                        if leaf and not is_collective(n))
        per_dev.append({"busy": length(busy), "coll": length(coll),
                        "exposed": length(coll) - overlap(coll, compute),
                        "gaps": [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]})
        for n, _, _, t, _ in nested:
            self_time[op_name(n)] += t / 1e9 / len(devices)
    n, window = len(per_dev), hi - lo
    idlest = min(per_dev, key=lambda p: p["busy"])
    gaps = sorted(idlest["gaps"], key=lambda g: g[0] - g[1])[:top]
    return {
        "devices": n,
        "steps": steps,
        "window_s": window / 1e9,
        "busy_s": sum(p["busy"] for p in per_dev) / n / 1e9,
        "idle_share_max": 1.0 - idlest["busy"] / window,
        "collective_s": sum(p["coll"] for p in per_dev) / n / 1e9,
        "exposed_s": sum(p["exposed"] for p in per_dev) / n / 1e9,
        "device_ops": sorted(([k, v] for k, v in self_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_label(g, host), (g[1] - g[0]) / 1e9] for g in gaps],
    }
