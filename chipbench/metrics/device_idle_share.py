"""Share of the traced window in which no operation ran on the device, for
the most idle device of the cell, in percent."""


def read(rec):
    t = rec["trace"]
    return None if t is None else 100.0 * t["idle_share_max"]
