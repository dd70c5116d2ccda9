"""Tokens of every step completed in the window, all chips, over the
window's wall time on the host clock."""


def read(rec):
    return rec["tokens_per_s"]
