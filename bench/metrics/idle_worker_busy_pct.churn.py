"""Share (%) of the traced window in which no op ran on the device while
the serving worker was in a phase other than waiting for work (its
serving.* spans): the device idle the host causes while it holds work."""

from bench import program_spans


def read(run):
    return program_spans.idle_worker_busy_pct(run)
