"""Queries answered per second over whole closed-loop calls."""

from bench import measures


def read(run):
    return measures.queries_per_s(run)
