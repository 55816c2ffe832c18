"""Device microseconds per answered query of the Stage-2 ops (alpha and
Eq. (1), the program's aidw.stage2 scope) in the traced window."""

from bench import program_spans


def read(run):
    return program_spans.scope_us_per_query(run, program_spans.STAGE2_SCOPE)
