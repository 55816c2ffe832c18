"""Median wall (ms) of the window's dataset updates inside the session
(plan_delta merge, CSR patch, re-stage): the program's session/plan_s
histogram."""

from bench import measures


def read(run):
    s = measures.hist_percentile(run, "session/plan_s", 50)
    return None if s is None else s * 1e3
