"""95th percentile read latency (ms) over every read of the window, from
its due time, in a cell whose reads wait behind updates: the same number
as the end-to-end latency_p95_ms, reported per layer where it is too
unsteady from run to run to hold a bound (PERF.md, section 6)."""

from bench import measures


def read(run):
    return measures.percentile(measures.read_latency_ms(run), 95)
