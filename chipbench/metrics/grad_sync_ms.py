"""Device time of the collective operations per traced step, averaged over
the devices. Nothing to read where the trace has no collective."""


def read(rec):
    t = rec["trace"]
    if t is None or t["collective_s"] == 0:
        return None
    return 1e3 * t["collective_s"] / t["steps"]
