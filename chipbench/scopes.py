"""Device time by the program's named scopes, and the host's stretch
between steps, from a profiler trace (``.xplane.pb``).

    python3 -m chipbench.scopes <trace.xplane.pb> --steps <traced steps>

The program names the layers of its training step with ``jax.named_scope``
(``SCOPES``) and the training loop's stretches of one step with host spans
(``repro.runtime.tracing.SPANS``). Over the traced window of ``trace.py``
(from the second ``feed.batch`` span to the last device operation), per
traced step and averaged over the devices:

- ``scopes``: each operation's self time (``trace.self_times``), laid to the
  innermost scope on its ``tf_op`` path (``xplane_meta``). A path component
  is a scope if it is the scope's name, or the name wrapped in transforms
  (``jvp(layers)``, ``transpose(jvp(layers))``). Collectives
  (``trace.is_collective``) go to ``collectives`` whatever their scope, and
  operations under no scope to ``unscoped``; the buckets sum to the busy
  time.
- ``top``: the operations of most self time in each bucket, with their path.
- ``host_gap_s``: the mean, over the steps dispatched in the window, of the
  time from the end of one ``trainer.wait`` span to the end of the next
  ``trainer.dispatch``: the stretch in which the synchronous loop leaves
  the chip with no queued work. Absent where the trace has no such spans.
- ``spans``: each ``trainer.*`` span's mean time per step in the window.
- ``step_s``: the intervals between consecutive ``feed.batch`` spans of the
  trace, the traced steps' times.
"""

from __future__ import annotations

import argparse
import json
import re
import statistics
from collections import defaultdict

from chipbench import trace, xplane_meta

SCOPES = ("layers", "attention", "ffn", "moe_dispatch", "embed_head",
          "optimizer", "grad_sync")
COLLECTIVES = "collectives"
UNSCOPED = "unscoped"
TRANSFORM = re.compile(r"^[A-Za-z_][\w.]*\((.*)\)$")
WAIT_SPAN, DISPATCH_SPAN = "trainer.wait", "trainer.dispatch"


def _bare(component: str) -> str:
    """``transpose(jvp(layers))`` -> ``layers``."""
    while (m := TRANSFORM.match(component)) is not None:
        component = m.group(1)
    return component


def scope_of(tf_op: str) -> str | None:
    """The innermost scope on an op's path (``tf_op`` without its
    ``:type``), or None."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op.rsplit("/", 1)[-1] else tf_op
    for component in reversed(path.split("/")):
        if _bare(component) in SCOPES:
            return _bare(component)
    return None


def _window(devices, host) -> tuple[int, int]:
    """``trace.reduce``'s window: the second ``feed.batch`` to the last op."""
    feeds = sorted(s for n, s, _ in host if n == trace.FEED_SPAN)
    if len(feeds) < 2:
        raise RuntimeError(f"fewer than two {trace.FEED_SPAN} spans")
    return feeds[1], max(e for d in devices.values() for _, _, e in d["ops"])


def host_gaps(host, lo: int, hi: int) -> list[float]:
    """Seconds from the end of the last ``trainer.wait`` before each
    ``trainer.dispatch`` that ends in [lo, hi] to that dispatch's end."""
    waits = sorted(e for n, _, e in host if n == WAIT_SPAN)
    gaps = []
    for d in sorted(e for n, _, e in host if n == DISPATCH_SPAN and lo <= e <= hi):
        before = [w for w in waits if w < d]
        if before:
            gaps.append((d - before[-1]) / 1e9)
    return gaps


def reduce(path: str, steps: int, top: int = 3) -> dict:
    devices, host = trace.load(path)
    if not devices:
        raise RuntimeError(f"no device operations in {path}")
    paths = xplane_meta.tf_ops(path)
    lo, hi = _window(devices, host)
    per = len(devices) * steps
    buckets = defaultdict(float)
    ops = defaultdict(lambda: defaultdict(float))
    for k, d in devices.items():
        tf_op = paths.get(f"/device:TPU:{k}", {})
        for name, _, _, t, _ in trace.self_times(trace._clip(d["ops"], lo, hi)):
            op_path = tf_op.get(name, "")
            b = (COLLECTIVES if trace.is_collective(name)
                 else scope_of(op_path) or UNSCOPED)
            buckets[b] += t / 1e9 / per
            ops[b][(trace.op_name(name), op_path)] += t / 1e9 / per
    spans = defaultdict(float)
    for n, s, e in trace._clip(host, lo, hi):
        if n.startswith("trainer."):
            spans[n] += (e - s) / 1e9 / steps
    feeds = sorted(s for n, s, _ in host if n == trace.FEED_SPAN)
    gaps = host_gaps(host, lo, hi)
    out = {
        "steps": steps,
        "scopes": dict(sorted(buckets.items(), key=lambda kv: -kv[1])),
        "top": {b: [[n, p, t] for (n, p), t in
                    sorted(v.items(), key=lambda kv: -kv[1])[:top]]
                for b, v in ops.items()},
        "spans": dict(spans),
        "step_s": [(b - a) / 1e9 for a, b in zip(feeds, feeds[1:])],
    }
    if gaps:
        out["host_gap_s"] = statistics.fmean(gaps)
    return out


def scope_ms(reduced: dict | None, scope: str) -> float | None:
    """Milliseconds per traced step of ``scope`` in a record that holds
    ``reduce``'s keys, or None where it has none."""
    seconds = (reduced or {}).get("scopes", {}).get(scope)
    return 1e3 * seconds if seconds else None


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--steps", type=int, required=True)
    args = ap.parse_args(argv)
    print(json.dumps(reduce(args.xplane, args.steps), indent=1))


if __name__ == "__main__":
    main()
