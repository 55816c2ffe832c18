"""Seconds from process start to the first timed request: data, plan,
compiles (or cache reads), warm-up of every shape the window uses."""


def read(run):
    return run.setup_s
