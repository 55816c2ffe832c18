"""95th-percentile read latency (ms) over every read of the window, from
its due time."""

from bench import measures


def read(run):
    return measures.percentile(measures.read_latency_ms(run), 95)
