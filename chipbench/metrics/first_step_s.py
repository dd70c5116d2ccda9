"""The trainer's first step on the host clock: compilation or a load from
the compile cache, and one execution."""


def read(rec):
    return rec["first_step_s"]
