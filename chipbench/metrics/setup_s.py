"""Process start to the start of the window: weights, compilation or the
compile cache, the planner, the first steps and the warm-up."""


def read(rec):
    return rec["setup_s"]
