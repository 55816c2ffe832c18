"""Megabytes moved between host and device per update applied in the
window: the program's ingest/pulled_bytes_total counter plus its
ingest/staged_bytes_total gauge, over the window."""

from bench import program_spans


def read(run):
    return program_spans.moved_mb_per_update(run)
