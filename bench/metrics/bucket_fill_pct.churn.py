"""Real query rows over padded bucket rows (%) the session dispatched in
the window: the program's session/queries and session/bucket_slots
counters."""

from bench import program_spans


def read(run):
    return program_spans.bucket_fill_pct(run)
