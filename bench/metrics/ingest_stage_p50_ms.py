"""Median duration (ms) of the window's ingest.stage spans: an update's
plan padding and upload to the device, closed after a fence (exact, from
the trace's host events)."""

from bench import program_spans


def read(run):
    return program_spans.span_p50_ms(run, "ingest.stage")
