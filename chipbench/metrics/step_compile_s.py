"""Seconds the trainer's step calls spent compiling over the run: tracing,
lowering and the backend's compile or load from the persistent cache, as
the program's compile counter sums them (``repro.runtime.tracing.counted``,
which only the trainer's step calls open in a run). Nothing to read where
the program has no such counter."""


def read(rec):
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    return tracing.counted.seconds or None
