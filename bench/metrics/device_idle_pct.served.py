"""Share (%) of the traced window in which no op ran on the device."""

from bench import measures


def read(run):
    return measures.device_idle_pct(run)
