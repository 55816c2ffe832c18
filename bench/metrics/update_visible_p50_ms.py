"""Median time (ms) from an update's due time until it is applied and
every later read sees it."""

from bench import measures


def read(run):
    return measures.percentile(measures.update_visible_ms(run), 50)
