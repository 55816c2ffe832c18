"""Median duration (ms) of the window's ingest.merge spans: an update's
host mirror rebuild and CSR merge, with its pull of the table from the
device (exact, from the trace's host events)."""

from bench import program_spans


def read(run):
    return program_spans.span_p50_ms(run, "ingest.merge")
