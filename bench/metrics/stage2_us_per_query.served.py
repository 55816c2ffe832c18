"""Device microseconds per answered query of the Stage-2 Eq. (1) ops
(weighted_partial_sums or an AIDW kernel) in the traced window."""

from bench import measures


def read(run):
    return measures.stage_us_per_query(run, "stage2")
