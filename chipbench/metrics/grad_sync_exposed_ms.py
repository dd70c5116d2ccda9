"""The part of the collective operations' device time per traced step in
which no other operation ran on the same device, averaged over the devices.
Nothing to read where the trace has no collective."""


def read(rec):
    t = rec["trace"]
    if t is None or t["collective_s"] == 0:
        return None
    return 1e3 * t["exposed_s"] / t["steps"]
