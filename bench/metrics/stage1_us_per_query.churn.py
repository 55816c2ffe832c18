"""Device microseconds per answered query of the Stage-1 grid kNN ops
(name stack jit(grid_knn)) in the traced window."""

from bench import measures


def read(run):
    return measures.stage_us_per_query(run, "stage1")
